"""Kernel micro-benchmarks: Pallas (interpret) vs jnp reference on CPU.

Wall time in interpret mode is NOT TPU performance — the deliverable here
is (a) correctness at benchmark shapes and (b) the arithmetic-intensity
table each kernel is designed around (FLOPs vs bytes from the BlockSpec
tiling), which is what transfers to the TPU roofline.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np


def timeit(fn, *args, repeat=3):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(repeat):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / repeat, out


def run(smoke: bool = False) -> list[tuple[str, float, str]]:
    rows = []
    key = jax.random.PRNGKey(0)

    # erlang_c: the analytic core's hot recurrence (DESIGN.md §12)
    from repro.kernels.erlang_c import kernel as ek, ref as eref

    a = jnp.linspace(0.5, 256.0, 128, dtype=jnp.float32)
    t_ref, want = timeit(lambda a: eref.erlang_b_table(a, k_hi=512), a)
    t_k, got = timeit(
        lambda a: ek.erlang_b_table_pallas(a, k_hi=512, interpret=True), a
    )
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-7)
    rows.append(("erlang_b_table_ref", t_ref * 1e6, "us lax.scan, 128 lanes x k=512"))
    rows.append(("erlang_b_table_pallas_interp", t_k * 1e6, "us interpret (correctness run)"))

    # l2_match: the paper's matcher bolt
    from repro.kernels.l2_match import kernel as lk, ref as lref

    m, n, d = 256, 128, 64
    a = jax.random.normal(key, (m, d))
    b = jax.random.normal(key, (n, d))
    t_ref, want = timeit(jax.jit(lref.pairwise_sq_l2), a, b)
    t_k, got = timeit(
        lambda a, b: lk.pairwise_sq_l2_pallas(a, b, interpret=True), a, b
    )
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
    flops = 2 * m * n * d
    bytes_ = 4 * (m * d + n * d + m * n)
    rows.append(("l2_match_ref", t_ref * 1e6, f"us jnp ({flops/bytes_:.1f} flop/byte)"))
    rows.append(("l2_match_pallas_interp", t_k * 1e6, "us interpret (correctness run)"))

    # flash attention
    from repro.kernels.flash_attention import kernel as fk, ref as fref

    bb, h, s, dh = 1, 4, 256, 64
    q = jax.random.normal(key, (bb, h, s, dh))
    kk = jax.random.normal(key, (bb, h, s, dh))
    v = jax.random.normal(key, (bb, h, s, dh))
    t_ref, want = timeit(jax.jit(lambda q, k, v: fref.attention(q, k, v)), q, kk, v)
    # The interpret-mode WALL-CLOCK row is deliberately gone (ROADMAP
    # kernels item): the online-softmax recurrence serialises badly when
    # interpreted, so the number only ever read as a bogus regression
    # against the jnp oracle.  What transfers to the TPU roofline is
    # correctness at the bench shape + the bytes the tiling avoids, so
    # that is what the kernel row now reports; a timed row returns when
    # the compiled path lands.
    got = fk.flash_attention_pallas(q, kk, v, bq=64, bk=64, interpret=True)
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)
    naive_bytes = 4 * (bb * h * s * s)  # the materialised logits the kernel avoids
    rows.append(("flash_attention_ref", t_ref * 1e6, "us jnp (materialises S^2)"))
    rows.append((
        "flash_attention_interpret_parity",
        float(np.allclose(got, want, rtol=2e-3, atol=2e-3)),
        f"kernel == jnp oracle at (1,4,256,64) (1.0 = match); avoids "
        f"{naive_bytes/2**20:.0f} MiB logits round-trip; no interpret-mode "
        "wall-clock by design — compiled-path bench tracked in ROADMAP",
    ))

    # decode attention
    from repro.kernels.decode_attention import kernel as dk, ref as dref

    bq, hq, sq, dq = 4, 8, 1024, 64
    q1 = jax.random.normal(key, (bq, hq, dq))
    kc = jax.random.normal(key, (bq, sq, hq, dq))
    vc = jax.random.normal(key, (bq, sq, hq, dq))
    t_ref, want = timeit(jax.jit(lambda q, k, v: dref.decode_attention(q, k, v, jnp.int32(900))), q1, kc, vc)
    t_k, got = timeit(
        lambda q, k, v: dk.decode_attention_pallas(q, k, v, jnp.int32(900), bs=256, interpret=True),
        q1, kc, vc,
    )
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)
    rows.append(("decode_attention_ref", t_ref * 1e6, "us jnp"))
    rows.append(("decode_attention_pallas_interp", t_k * 1e6, "us interpret"))

    # fused swiglu
    from repro.kernels.swiglu import kernel as gk, ref as gref

    t_, d_, f_ = 256, 128, 512
    x = jax.random.normal(key, (t_, d_))
    wg = jax.random.normal(key, (d_, f_)) * 0.05
    wu = jax.random.normal(key, (d_, f_)) * 0.05
    wo = jax.random.normal(key, (f_, d_)) * 0.05
    t_ref, want = timeit(jax.jit(gref.swiglu), x, wg, wu, wo)
    t_k, got = timeit(
        lambda *a: gk.swiglu_pallas(*a, bt=128, bf=128, interpret=True), x, wg, wu, wo
    )
    np.testing.assert_allclose(got, want, rtol=1e-3, atol=1e-2)
    hidden_bytes = 4 * t_ * f_ * 2
    rows.append(("swiglu_ref", t_ref * 1e6, "us jnp"))
    rows.append((
        "swiglu_pallas_interp", t_k * 1e6,
        f"us interpret; keeps {hidden_bytes/2**20:.1f} MiB hidden in VMEM",
    ))

    return rows


def main() -> None:
    for name, val, note in run():
        print(f"{name},{val:.1f},{note}")


if __name__ == "__main__":
    main()
