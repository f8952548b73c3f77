"""HLO cost model vs known-flop programs (incl. the scan trip-count fix)."""

import jax
import jax.numpy as jnp
import pytest

from repro.launch.hlo_cost import analyze_hlo


def compile_text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


def test_single_matmul_flops():
    m, k, n = 64, 128, 32
    a = jax.ShapeDtypeStruct((m, k), jnp.float32)
    b = jax.ShapeDtypeStruct((k, n), jnp.float32)
    txt = compile_text(lambda a, b: a @ b, a, b)
    cost = analyze_hlo(txt)
    assert cost.flops == pytest.approx(2 * m * k * n, rel=0.01)
    assert cost.dot_count == 1


def test_batched_matmul_flops():
    bsz, m, k, n = 4, 32, 64, 16
    a = jax.ShapeDtypeStruct((bsz, m, k), jnp.float32)
    b = jax.ShapeDtypeStruct((bsz, k, n), jnp.float32)
    txt = compile_text(lambda a, b: jnp.einsum("bmk,bkn->bmn", a, b), a, b)
    cost = analyze_hlo(txt)
    assert cost.flops == pytest.approx(2 * bsz * m * k * n, rel=0.01)


def test_scan_multiplies_by_trip_count():
    """THE fix: cost_analysis counts a scanned layer once; we must count L."""
    L, d = 8, 64
    w = jax.ShapeDtypeStruct((L, d, d), jnp.float32)
    x = jax.ShapeDtypeStruct((4, d), jnp.float32)

    def f(w, x):
        def body(h, wl):
            return jnp.tanh(h @ wl), None
        h, _ = jax.lax.scan(body, x, w)
        return h.sum()

    txt = compile_text(f, w, x)
    cost = analyze_hlo(txt)
    expect = L * 2 * 4 * d * d  # L matmuls
    assert cost.flops == pytest.approx(expect, rel=0.05)
    assert cost.while_count >= 1
    # the builtin cost_analysis undercounts (this is why hlo_cost exists)
    builtin = jax.jit(f).lower(w, x).compile().cost_analysis()
    assert builtin["flops"] < expect / 2


def test_grad_scan_counts_fwd_and_bwd():
    L, d, b = 4, 32, 2
    w = jax.ShapeDtypeStruct((L, d, d), jnp.float32)
    x = jax.ShapeDtypeStruct((b, d), jnp.float32)

    def f(w, x):
        def body(h, wl):
            return jnp.tanh(h @ wl), None
        h, _ = jax.lax.scan(body, x, w)
        return h.sum()

    txt = compile_text(jax.grad(f), w, x)
    cost = analyze_hlo(txt)
    # fwd: L*2*b*d*d ; bwd: 2 matmuls per layer (dh and dW)
    expect = 3 * L * 2 * b * d * d
    assert cost.flops == pytest.approx(expect, rel=0.25)


def test_traffic_scales_with_trip_count():
    L, d = 16, 64
    w = jax.ShapeDtypeStruct((L, d, d), jnp.float32)
    x = jax.ShapeDtypeStruct((4, d), jnp.float32)

    def f(w, x):
        def body(h, wl):
            return jnp.tanh(h @ wl), None
        return jax.lax.scan(body, x, w)[0].sum()

    def f1(w, x):  # single layer for comparison
        return jnp.tanh(x @ w[0]).sum()

    t_l = analyze_hlo(compile_text(f, w, x))
    t_1 = analyze_hlo(compile_text(f1, w, x))
    assert t_l.traffic_bytes > 4 * t_1.traffic_bytes  # grows with L


def test_collectives_counted_with_multiplicity():
    if len(jax.devices()) < 1:
        pytest.skip("needs devices")
    mesh = jax.make_mesh((1,), ("d",))
    s = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())

    def f(x):
        return x * 2

    txt = jax.jit(f, in_shardings=s).lower(
        jax.ShapeDtypeStruct((8, 8), jnp.float32)
    ).compile().as_text()
    cost = analyze_hlo(txt)  # no collectives on 1 device
    assert cost.collective_bytes == 0
