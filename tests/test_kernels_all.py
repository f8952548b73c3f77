"""Pallas kernels vs jnp oracles (interpret=True), with shape/dtype sweeps."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # no dev deps installed — deterministic fallback sweep
    from _hypothesis_fallback import given, settings, strategies as st

from repro.kernels.decode_attention import kernel as dk, ref as dref
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
# gain_topr: Program (4)'s top-R selection
# --------------------------------------------------------------------- #
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
