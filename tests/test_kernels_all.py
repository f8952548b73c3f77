"""Pallas kernels vs jnp oracles (interpret=True), with shape/dtype sweeps."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # no dev deps installed — deterministic fallback sweep
    from _hypothesis_fallback import given, settings, strategies as st

from repro.kernels.decide_fused import ops as ddops, ref as ddref
from repro.kernels.decode_attention import kernel as dk, ref as dref
from repro.kernels.erlang_c import ref as eref
from repro.kernels.flash_attention import kernel as fk, ref as fref
from repro.kernels.gain_topr import kernel as tk, ref as topr_ref
from repro.kernels.rwkv6_scan import kernel as rk, ref as rref
from repro.kernels.ssd_scan import kernel as sk, ref as sref
from repro.kernels.swiglu import kernel as gk, ref as gref


def rand(shape, dtype, seed):
    return jax.random.normal(jax.random.PRNGKey(seed), shape).astype(dtype)


# --------------------------------------------------------------------- #
# flash attention
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-3), (jnp.bfloat16, 3e-2)])
@pytest.mark.parametrize("sq,skv,bq,bk", [(128, 128, 64, 64), (128, 256, 64, 128)])
def test_flash_attention_causal(dtype, tol, sq, skv, bq, bk):
    b, h, dh = 1, 2, 64
    q = rand((b, h, sq, dh), dtype, 0)
    k = rand((b, h, skv, dh), dtype, 1)
    v = rand((b, h, skv, dh), dtype, 2)
    got = fk.flash_attention_pallas(q, k, v, causal=True, bq=bq, bk=bk, interpret=True)
    want = fref.attention(q, k, v, causal=True)
    np.testing.assert_allclose(
        got.astype(jnp.float32), want.astype(jnp.float32), rtol=tol, atol=tol
    )


def test_flash_attention_sliding_window():
    b, h, s, dh = 1, 1, 256, 32
    q, k, v = (rand((b, h, s, dh), jnp.float32, i) for i in range(3))
    got = fk.flash_attention_pallas(q, k, v, causal=True, window=64, bq=64, bk=64, interpret=True)
    want = fref.attention(q, k, v, causal=True, window=64)
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)


def test_flash_attention_bidirectional():
    b, h, s, dh = 2, 1, 128, 32
    q, k, v = (rand((b, h, s, dh), jnp.float32, 10 + i) for i in range(3))
    got = fk.flash_attention_pallas(q, k, v, causal=False, bq=64, bk=64, interpret=True)
    want = fref.attention(q, k, v, causal=False)
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)


# --------------------------------------------------------------------- #
# decode attention
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("length", [1, 100, 256, 511])
def test_decode_attention_lengths(length):
    b, h, s, dh = 2, 4, 512, 32
    q = rand((b, h, dh), jnp.float32, 0)
    kc = rand((b, s, h, dh), jnp.float32, 1)
    vc = rand((b, s, h, dh), jnp.float32, 2)
    got = dk.decode_attention_pallas(q, kc, vc, jnp.int32(length), bs=128, interpret=True)
    want = dref.decode_attention(q, kc, vc, jnp.int32(length))
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)


def test_decode_attention_window():
    b, h, s, dh = 1, 2, 512, 32
    q = rand((b, h, dh), jnp.float32, 3)
    kc = rand((b, s, h, dh), jnp.float32, 4)
    vc = rand((b, s, h, dh), jnp.float32, 5)
    got = dk.decode_attention_pallas(q, kc, vc, jnp.int32(400), window=64, bs=128, interpret=True)
    want = dref.decode_attention(q, kc, vc, jnp.int32(400), window=64)
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)


def test_decode_attention_matches_train_attention_last_row():
    """Decode at length L must equal full attention's last row."""
    b, h, s, dh = 1, 2, 256, 32
    q_full = rand((b, h, s, dh), jnp.float32, 6)
    kc = rand((b, s, h, dh), jnp.float32, 7)
    vc = rand((b, s, h, dh), jnp.float32, 8)
    k_hf = jnp.moveaxis(kc, 2, 1)
    v_hf = jnp.moveaxis(vc, 2, 1)
    full = fref.attention(q_full, k_hf, v_hf, causal=True)
    got = dk.decode_attention_pallas(q_full[:, :, -1], kc, vc, jnp.int32(s), bs=64, interpret=True)
    np.testing.assert_allclose(got, full[:, :, -1], rtol=2e-3, atol=2e-3)


# --------------------------------------------------------------------- #
# swiglu
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-3), (jnp.bfloat16, 5e-2)])
def test_swiglu_fused(dtype, tol):
    t, d, f = 128, 64, 256
    x = rand((t, d), dtype, 0)
    wg, wu = rand((d, f), dtype, 1), rand((d, f), dtype, 2)
    wo = rand((f, d), dtype, 3)
    got = np.asarray(gk.swiglu_pallas(x, wg, wu, wo, bt=64, bf=64, interpret=True), np.float32)
    want = np.asarray(gref.swiglu(x, wg, wu, wo), np.float32)
    # atol scales with output magnitude: bf16 rounding noise on the f=256
    # contraction lands on outputs spanning +-1000.
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol * np.abs(want).max())


@given(
    bt=st.sampled_from([32, 64, 128]),
    bf=st.sampled_from([64, 128, 256]),
)
@settings(max_examples=6, deadline=None)
def test_swiglu_block_invariance(bt, bf):
    t, d, f = 128, 32, 256
    x = rand((t, d), jnp.float32, 9)
    wg, wu, wo = rand((d, f), jnp.float32, 10), rand((d, f), jnp.float32, 11), rand((f, d), jnp.float32, 12)
    got = gk.swiglu_pallas(x, wg, wu, wo, bt=bt, bf=bf, interpret=True)
    want = gref.swiglu(x, wg, wu, wo)
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)


# --------------------------------------------------------------------- #
# rwkv6 scan
# --------------------------------------------------------------------- #
def _ref_rwkv_stream(r, k, v, lw, u, s0, chunk):
    """Chain the single-chunk oracle across chunks."""
    s = r.shape[0]
    outs = []
    state = s0
    for i in range(0, s, chunk):
        o, state = rref.rwkv6_chunk(
            r[i : i + chunk], k[i : i + chunk], v[i : i + chunk], lw[i : i + chunk], u, state
        )
        outs.append(o)
    return jnp.concatenate(outs, axis=0), state


def test_rwkv6_scan_kernel_matches_oracle():
    bh, s, dk_, dv, chunk = 3, 128, 16, 16, 32
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    r = jax.random.normal(ks[0], (bh, s, dk_)) * 0.5
    k = jax.random.normal(ks[1], (bh, s, dk_)) * 0.5
    v = jax.random.normal(ks[2], (bh, s, dv)) * 0.5
    lw = -jax.random.uniform(ks[3], (bh, s, dk_), minval=0.01, maxval=1.5)
    u = jax.random.normal(ks[4], (bh, dk_)) * 0.3
    s0 = jax.random.normal(ks[5], (bh, dk_, dv)) * 0.2
    got_o, got_s = rk.rwkv6_scan_pallas(r, k, v, lw, u, s0, chunk=chunk, interpret=True)
    for i in range(bh):
        want_o, want_s = _ref_rwkv_stream(r[i], k[i], v[i], lw[i], u[i], s0[i], chunk)
        np.testing.assert_allclose(got_o[i], want_o, rtol=5e-3, atol=5e-3)
        np.testing.assert_allclose(got_s[i], want_s, rtol=5e-3, atol=5e-3)


def test_rwkv6_kernel_matches_model_recurrence():
    """Kernel vs the models/ssm.py step recurrence (end-to-end truth)."""
    from repro.models.ssm import rwkv6_step

    bh, s, d = 2, 64, 8
    ks = jax.random.split(jax.random.PRNGKey(1), 5)
    r = jax.random.normal(ks[0], (bh, s, d)) * 0.5
    k = jax.random.normal(ks[1], (bh, s, d)) * 0.5
    v = jax.random.normal(ks[2], (bh, s, d)) * 0.5
    w = jax.random.uniform(ks[3], (bh, s, d), minval=0.5, maxval=0.99)
    # one shared bonus row: the naive loop below treats bh as batch with a
    # single head, so u must be identical across streams
    u = jnp.broadcast_to(jax.random.normal(ks[4], (1, d)) * 0.3, (bh, d))
    s0 = jnp.zeros((bh, d, d))
    got_o, got_s = rk.rwkv6_scan_pallas(r, k, v, jnp.log(w), u, s0, chunk=16, interpret=True)
    # naive recurrence, per stream (treat bh as batch with 1 head)
    state = s0[:, None]
    outs = []
    for t in range(s):
        o, state = rwkv6_step(
            r[:, t, None], k[:, t, None], v[:, t, None], w[:, t, None], u[:1], state
        )
        outs.append(o[:, 0])
    want_o = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(got_o, want_o, rtol=5e-3, atol=5e-3)
    np.testing.assert_allclose(got_s, state[:, 0], rtol=5e-3, atol=5e-3)


# --------------------------------------------------------------------- #
# ssd scan
# --------------------------------------------------------------------- #
def test_ssd_scan_kernel_matches_oracle():
    bh, s, dh, dst, chunk = 2, 128, 16, 8, 32
    ks = jax.random.split(jax.random.PRNGKey(2), 5)
    x = jax.random.normal(ks[0], (bh, s, dh)) * 0.5
    a = -jax.random.uniform(ks[1], (bh, s), minval=0.01, maxval=1.0)
    b = jax.random.normal(ks[2], (bh, s, dst)) * 0.5
    c = jax.random.normal(ks[3], (bh, s, dst)) * 0.5
    s0 = jax.random.normal(ks[4], (bh, dst, dh)) * 0.2
    got_y, got_s = sk.ssd_scan_pallas(x, a, b, c, s0, chunk=chunk, interpret=True)
    for i in range(bh):
        state = s0[i]
        outs = []
        for j in range(0, s, chunk):
            y, state = sref.ssd_chunk(
                x[i, j : j + chunk], a[i, j : j + chunk], b[i, j : j + chunk],
                c[i, j : j + chunk], state,
            )
            outs.append(y)
        want_y = jnp.concatenate(outs, axis=0)
        np.testing.assert_allclose(got_y[i], want_y, rtol=5e-3, atol=5e-3)
        np.testing.assert_allclose(got_s[i], state, rtol=5e-3, atol=5e-3)


# --------------------------------------------------------------------- #
# decide_fused: one pass from offered load to the Program-4 allocation
# --------------------------------------------------------------------- #
def _zoo_decide_case(seeds, extra_budget=24):
    """Zoo-derived decide inputs: random AppGraph topologies (chains,
    splits, joins, leaking loops) stacked into one padded [B, N] batch,
    with a few stable lanes flipped to gang ("group") scaling so both
    sojourn branches appear."""
    from repro.streaming.scenarios import random_appgraph

    tops = [random_appgraph(s).topology() for s in seeds]
    b, n = len(tops), max(t.n for t in tops)
    lam = np.zeros((b, n))
    mu = np.ones((b, n))
    group = np.zeros((b, n), dtype=bool)
    alpha = np.zeros((b, n))
    active = np.zeros((b, n), dtype=bool)
    rng = np.random.default_rng(seeds[0])
    for i, top in enumerate(tops):
        lam[i, : top.n] = top.arrival_rates
        mu[i, : top.n] = [op.mu for op in top.operators]
        active[i, : top.n] = top.arrival_rates > 0
        for lane in range(top.n):
            # group scaling saturates at mu/alpha; only flip lanes with
            # plenty of headroom so every lane stays feasible
            if rng.random() < 0.3 and lam[i, lane] < 0.2 * mu[i, lane] / 0.02:
                group[i, lane] = True
                alpha[i, lane] = 0.02
    k_cur = rng.integers(0, 6, size=(b, n)).astype(np.int32)
    floor = np.where(active, np.floor(lam / mu) + 1, 0).sum(axis=1)
    k_max = (floor + extra_budget).astype(np.int32)
    return lam, mu, group, alpha, active, k_cur, k_max


def _decide(fn, case, k_hi, **kw):
    lam, mu, group, alpha, active, k_cur, k_max = case
    return fn(lam, mu, group=group, alpha=alpha, active=active,
              k_cur=k_cur, k_max=k_max, k_hi=k_hi, **kw)


@pytest.mark.parametrize("seeds,k_hi", [((0, 1, 2, 3), 64), ((4, 5), 1024)])
def test_decide_fused_oracle_matches_numpy_twin_x64(seeds, k_hi):
    """jnp oracle == float64 numpy twin bit-for-bit under x64, across the
    zoo and up to K=1024."""
    case = _zoo_decide_case(seeds)
    with jax.enable_x64(True):
        got = _decide(ddref.batch_decide, case, k_hi)
    want = _decide(ddref.batch_decide_np, case, k_hi)
    for name, g, w in zip(("k4", "k_start", "t_cur", "t4"), got, want):
        np.testing.assert_array_equal(np.asarray(g), w, err_msg=name)


@pytest.mark.parametrize("x64", [False, True])
def test_decide_fused_matches_two_pass_decide_bitwise(x64):
    """The dispatch contract: make_decide_jax with the fused knob on must
    reproduce the two-pass erlang_c->gain_topr decide bit-for-bit on CPU,
    in both float32 and float64."""
    import contextlib

    import repro.core.controller as ctl
    from repro.api.session import ScenarioRunner
    from repro.streaming.scenarios import scenario_matrix

    scens = [
        s.with_(negotiated=False)
        for s in scenario_matrix(4, seed=17, horizon=20.0, warmup=5.0, dt=0.05)
    ]
    with jax.enable_x64(True) if x64 else contextlib.nullcontext():
        r = ScenarioRunner(scens, tick_interval=5.0, backend="jax")
        b, n = len(scens), r.static.n
        rng = np.random.default_rng(5)
        lam = np.abs(rng.normal(2.0, 0.6, (b, n)))
        mu = np.abs(rng.normal(6.0, 0.5, (b, n))) + 1.0
        drop = np.zeros((b, n))
        lam0 = np.abs(rng.normal(2.0, 0.5, b))
        k = np.where(r.static.active, 2, 0).astype(np.int64)
        two = ctl.make_decide_jax(r.static, r._params(), fused=False)(
            lam, mu, drop, lam0, k
        )
        one = ctl.make_decide_jax(r.static, r._params(), fused=True)(
            lam, mu, drop, lam0, k
        )
    for name, a, f in zip(("code", "k_next", "et_cur", "et_target", "applied"),
                          two, one):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(f), err_msg=name)


@pytest.mark.parametrize("seeds,k_hi,j_cap", [
    ((6, 7, 8), 64, None),     # B=3, zoo N is no tile multiple
    ((9, 10), 200, 48),        # truncated window through the kernel too
])
def test_decide_fused_kernel_interpret_matches_oracle(seeds, k_hi, j_cap):
    """Pallas kernel (interpret) vs the float32 oracle: the integer
    decision surface is exact; T gathers compare with the kernel-tier
    tolerance (loop vs vectorized FMA contraction)."""
    case = _zoo_decide_case(seeds)
    f32 = tuple(
        np.asarray(a, dtype=np.float32) if a.dtype.kind == "f" else a for a in case
    )
    got = _decide(ddops.batch_decide, f32, k_hi, j_cap=j_cap,
                  force_kernel=True, interpret=True)
    want = _decide(ddref.batch_decide, f32, k_hi, j_cap=j_cap)
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]), err_msg="k4")
    np.testing.assert_array_equal(
        np.asarray(got[1]), np.asarray(want[1]), err_msg="k_start"
    )
    for name, g, w in zip(("t_cur", "t4"), got[2:], want[2:]):
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(w), rtol=1e-4, atol=1e-6, err_msg=name
        )


def test_decide_fused_jcap_truncation_is_exact():
    """Window truncation to j_cap >= budget is provably lossless: gains
    are non-increasing per lane, so the selected set (ties included) is
    identical to the full-window selection — bitwise, not approximately."""
    case = _zoo_decide_case((11, 12, 13))
    k_max = case[-1]
    with jax.enable_x64(True):
        full = _decide(ddref.batch_decide, case, 128, j_cap=None)
        capped = _decide(ddref.batch_decide, case, 128, j_cap=int(k_max.max()))
    for name, a, b in zip(("k4", "k_start", "t_cur", "t4"), full, capped):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b), err_msg=name)


def test_decide_fused_erlang_unroll_is_bitwise_safe():
    """The scan-unroll perf knob must not change a single bit: unrolling
    only restructures the loop, every lane still runs the same float ops
    in the same order."""
    a = np.abs(np.random.default_rng(3).normal(4.0, 3.0, 96))
    base = np.asarray(eref.erlang_b_table(a, k_hi=512, unroll=1))
    for u in (2, 4, 8):
        np.testing.assert_array_equal(
            np.asarray(eref.erlang_b_table(a, k_hi=512, unroll=u)), base,
            err_msg=f"unroll={u}",
        )
    case = _zoo_decide_case((14, 15))
    u1 = _decide(ddref.batch_decide, case, 64, unroll=1)
    u4 = _decide(ddref.batch_decide, case, 64, unroll=4)
    for name, x, y in zip(("k4", "k_start", "t_cur", "t4"), u1, u4):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y), err_msg=name)


def test_gain_topr_padded_lanes_contribute_zero():
    """The hoisted pad-shape contract: tile padding rides through as zero
    gains, so hand-padding the candidate tile changes nothing — real
    lanes take identically and every padded lane takes exactly zero."""
    rng = np.random.default_rng(7)
    b, n, j = 3, 7, 12
    cand = np.where(rng.random((b, n, j)) < 0.7, rng.gamma(2.0, 1.0, (b, n, j)), 0.0)
    budget = np.array([5, 0, 40], dtype=np.int32)
    base = np.asarray(tk.gain_topr_pallas(cand, budget, interpret=True))
    padded = np.zeros((b, n + 13, j + 5), dtype=cand.dtype)
    padded[:, :n, :j] = cand
    out = np.asarray(tk.gain_topr_pallas(padded, budget, interpret=True))
    np.testing.assert_array_equal(out[:, :n], base)
    np.testing.assert_array_equal(out[:, n:], 0)
    np.testing.assert_array_equal(base, np.asarray(topr_ref.gain_topr(cand, budget)))


def _topr_lane_case(b, n, j, seed=0):
    """Scenarios in three gain patterns — gains from {0.5, 1.0, 1.5}, so
    the threshold ties across operators; all zero; normal gains, about a
    third negative — each under five budgets: 0, 1, exactly its positive
    count, above it, and one drawn in between."""
    rng = np.random.default_rng(seed)
    lane = np.arange(b)
    pattern = ((lane // 5) % 3)[:, None, None]
    ties = rng.choice([0.5, 1.0, 1.5], (b, n, j))
    mixed = rng.normal(0.5, 1.0, (b, n, j))
    cand = np.where(pattern == 0, ties, np.where(pattern == 1, 0.0, mixed))
    pos = (cand > 0).sum(axis=(1, 2))
    pick = lane % 5
    budget = np.select(
        [pick == 0, pick == 1, pick == 2, pick == 3],
        [0, 1, pos, pos + 1 + lane % 7],
        rng.integers(1, pos + 2),
    )
    return cand.astype(np.float32), budget.astype(np.int32)


@pytest.mark.parametrize("j", [1, 21, 22, 64])
@pytest.mark.parametrize("n", [1, 3, 8])
@pytest.mark.parametrize("b", [128, 200, 1027, 4096])
def test_gain_topr_lanes_matches_oracle(b, n, j):
    """The lane layout (scenarios on lanes) takes exactly what the sort
    oracle takes, B a multiple of the lane tile or not."""
    cand, budget = _topr_lane_case(b, n, j, seed=b + n + j)
    got = np.asarray(tk.gain_topr_pallas(cand, budget, interpret=True))
    np.testing.assert_array_equal(got, np.asarray(topr_ref.gain_topr(cand, budget)))


def _pallas_call_names(jaxpr):
    """Names of every ``pallas_call`` in a closed jaxpr, nested ones too."""
    names = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            names.append(eqn.params["name"])
        for sub in jax.core.jaxprs_in_params(eqn.params):
            names += _pallas_call_names(sub)
    return names


@pytest.mark.parametrize("shape,layout", [
    ((1, 3, 22), "gain_topr_pallas"),
    ((127, 3, 22), "gain_topr_pallas"),
    ((1, 384, 1024), "gain_topr_pallas"),  # the planner's merged fleet table
    ((128, 3, 22), "gain_topr_lanes"),
    ((16384, 3, 22), "gain_topr_lanes"),
])
def test_gain_topr_layout_follows_shape(shape, layout):
    """B < 128 (the planner's B = 1 solve among them) keeps the
    per-scenario kernel; B >= 128 takes the lane kernel."""
    args = (jax.ShapeDtypeStruct(shape, jnp.float32),
            jax.ShapeDtypeStruct(shape[:1], jnp.int32))
    jaxpr = jax.make_jaxpr(tk.gain_topr_pallas)(*args).jaxpr
    assert _pallas_call_names(jaxpr) == [layout]


# --------------------------------------------------------------------- #
# compiled-backend lane: real pallas_call on TPU, interpret elsewhere.
# Deselected by default (pytest.ini); CI's test-kernels-compiled job runs
# `-m tpu`, compiling on an accelerator and falling back to the
# force_kernel+interpret path on CPU so the lane never goes dark.
# --------------------------------------------------------------------- #
@pytest.mark.tpu
def test_decide_fused_backend_lane():
    interpret = jax.default_backend() != "tpu"
    case = _zoo_decide_case((20, 21, 22))
    f32 = tuple(
        np.asarray(a, dtype=np.float32) if a.dtype.kind == "f" else a for a in case
    )
    got = _decide(ddops.batch_decide, f32, 128, j_cap=48,
                  force_kernel=True, interpret=interpret)
    want = _decide(ddref.batch_decide, f32, 128, j_cap=48)
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))
    for g, w in zip(got[2:], want[2:]):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-3, atol=1e-5)


@pytest.mark.tpu
def test_gain_topr_backend_lane():
    interpret = jax.default_backend() != "tpu"
    rng = np.random.default_rng(8)
    cand = rng.gamma(2.0, 1.0, (4, 9, 24))
    budget = np.array([3, 12, 0, 100], dtype=np.int32)
    got = np.asarray(tk.gain_topr_pallas(cand, budget, interpret=interpret))
    want = np.asarray(topr_ref.gain_topr(cand, budget))
    np.testing.assert_array_equal(got, want)


def test_ssd_kernel_matches_model_chunked():
    """Kernel vs models/ssm.py ssd_chunked (the train-path implementation)."""
    from repro.models.ssm import ssd_chunked

    bh, s, dh, dst = 2, 64, 8, 4
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    x = jax.random.normal(ks[0], (bh, s, dh)) * 0.5
    a = -jax.random.uniform(ks[1], (bh, s), minval=0.01, maxval=1.0)
    b = jax.random.normal(ks[2], (bh, s, dst)) * 0.5
    c = jax.random.normal(ks[3], (bh, s, dst)) * 0.5
    s0 = jnp.zeros((bh, dst, dh))
    got_y, got_s = sk.ssd_scan_pallas(x, a, b, c, s0, chunk=16, interpret=True)
    want_y, want_s = ssd_chunked(
        x[:, :, None], a[:, :, None], b[:, :, None], c[:, :, None],
        chunk=16, initial_state=s0[:, None],
    )
    np.testing.assert_allclose(got_y, want_y[:, :, 0], rtol=5e-3, atol=5e-3)
    np.testing.assert_allclose(got_s, want_s[:, 0], rtol=5e-3, atol=5e-3)
