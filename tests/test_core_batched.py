"""Batched analytic core (core/batched.py + kernels/erlang_c) vs the scalar
model — the DESIGN.md §12 agreement guarantees."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.batched import (
    expected_sojourn_batch,
    expected_sojourn_batch_jax,
    gain_table,
    sojourn_from_table,
    sojourn_table,
    sojourn_table_jax,
    solve_traffic_batch,
    solve_traffic_batch_jax,
)
from repro.core.erlang import marginal_benefit
from repro.core.jackson import OperatorSpec, Topology, solve_traffic_equations
from repro.kernels.erlang_c import kernel as ek, ref as eref


def vld_top(lam0=13.0):
    return Topology.chain(
        [("extract", 2.0), ("match", 5.0), ("agg", 50.0)], lam0=lam0
    )


def mixed_top():
    """Replica + chip-group scaling + a zero-traffic operator."""
    ops = [
        OperatorSpec("gang", 3.0, scaling="group", group_alpha=0.05),
        OperatorSpec("rep", 10.0),
        OperatorSpec("idle", 4.0),  # no traffic routed here
    ]
    routing = np.zeros((3, 3))
    routing[0][1] = 1.0
    return Topology(ops, np.array([8.0, 0.0, 0.0]), routing)


# ------------------------------------------------------------------ #
# numpy table vs scalar: bit-exact
# ------------------------------------------------------------------ #
@pytest.mark.parametrize("top", [vld_top(), mixed_top()], ids=["vld", "mixed"])
def test_sojourn_table_bit_identical_to_scalar(top):
    k_hi = 64
    T = sojourn_table(top, k_hi)
    lam = top.arrival_rates
    for i, op in enumerate(top.operators):
        for k in range(k_hi + 1):
            want = op.sojourn(k, lam[i])
            got = T[i, k]
            assert np.isinf(want) == np.isinf(got), (i, k)
            if np.isfinite(want):
                assert got == want, (i, k, got, want)  # bit-identical, not approx


def test_sojourn_table_wide_operator_set_vectorized_path():
    """> 64 operators takes the vectorized recursion branch — must still
    match the scalar model bit-for-bit."""
    n = 80
    ops = [OperatorSpec(f"o{i}", 2.0 + 0.1 * i) for i in range(n)]
    routing = np.zeros((n, n))
    for i in range(n - 1):
        routing[i][i + 1] = 0.9
    top = Topology(ops, np.r_[40.0, np.zeros(n - 1)], routing)
    T = sojourn_table(top, 48)
    lam = top.arrival_rates
    for i in (0, 1, 37, 79):
        op = top.operators[i]
        for k in range(49):
            want = op.sojourn(k, lam[i])
            assert (np.isinf(want) and np.isinf(T[i, k])) or T[i, k] == want


def test_gain_table_matches_marginal_benefit():
    top = vld_top()
    lam = top.arrival_rates
    _, G = gain_table(top, 40)
    for i, op in enumerate(top.operators):
        for k in range(1, 40):
            want = marginal_benefit(k, lam[i], op.mu)
            if np.isinf(want):
                assert np.isinf(G[i, k])
            else:
                assert G[i, k] == want


def test_batch_sojourn_agrees_with_topology_to_1e9():
    top = vld_top()
    rng = np.random.default_rng(0)
    k_min = top.min_feasible_allocation()
    K = k_min[None, :] + rng.integers(0, 12, size=(32, top.n))
    e = expected_sojourn_batch(top, K)
    for r in range(K.shape[0]):
        assert e[r] == pytest.approx(top.expected_sojourn(K[r]), abs=1e-9)


def test_batch_sojourn_infeasible_rows_are_inf():
    top = vld_top()
    K = np.array([[1, 1, 1], [8, 3, 1]])  # row 0 unstable (extract needs 7)
    e = expected_sojourn_batch(top, K)
    assert np.isinf(e[0]) and np.isfinite(e[1])


def test_sojourn_from_table_shapes():
    top = vld_top()
    T = sojourn_table(top, 16)
    per_op, e2e = sojourn_from_table(
        T, np.array([8, 4, 1]), top.arrival_rates, top.lam0_total
    )
    assert per_op.shape == (3,) and np.isscalar(float(e2e))


# ------------------------------------------------------------------ #
# traffic-equation batches
# ------------------------------------------------------------------ #
def test_traffic_batch_matches_scalar_solver():
    top = mixed_top()
    scales = np.array([0.25, 1.0, 3.5])
    lam0_b = scales[:, None] * top.lam0[None, :]
    got = solve_traffic_batch(lam0_b, top.routing)
    for r, s in enumerate(scales):
        want = solve_traffic_equations(s * top.lam0, top.routing)
        np.testing.assert_allclose(got[r], want, atol=1e-9)


def test_traffic_batch_per_scenario_routing():
    top = vld_top()
    p = np.stack([top.routing, 2.0 * top.routing * 0.45])
    lam0_b = np.stack([top.lam0, top.lam0])
    got = solve_traffic_batch(lam0_b, p)
    for r in range(2):
        want = solve_traffic_equations(lam0_b[r], p[r])
        np.testing.assert_allclose(got[r], want, atol=1e-9)


def test_traffic_batch_rejects_bad_routing_shape():
    with pytest.raises(ValueError):
        solve_traffic_batch(np.ones((2, 3)), np.ones((4, 4)))


# ------------------------------------------------------------------ #
# jnp path — vmap/jit-able twin; x64 hits 1e-9, f32 stays loose
# ------------------------------------------------------------------ #
def test_jax_table_agrees_f32():
    top = vld_top()
    T = sojourn_table(top, 40)
    Tj = np.asarray(
        sojourn_table_jax(top.arrival_rates, np.array([2.0, 5.0, 50.0]), k_hi=40)
    )
    assert (np.isinf(T) == np.isinf(Tj)).all()
    m = np.isfinite(T)
    np.testing.assert_allclose(Tj[m], T[m], rtol=1e-5)


def test_jax_table_agrees_1e9_under_x64():
    top = vld_top()
    T = sojourn_table(top, 40)
    with jax.enable_x64(True):
        Tj = np.asarray(
            sojourn_table_jax(
                jnp.asarray(top.arrival_rates), jnp.asarray([2.0, 5.0, 50.0]), k_hi=40
            )
        )
    m = np.isfinite(T)
    np.testing.assert_allclose(Tj[m], T[m], atol=1e-9)


def test_jax_batch_sojourn_and_traffic():
    top = vld_top()
    K = np.array([[8, 4, 1], [9, 5, 1], [12, 7, 2]])
    ej = np.asarray(expected_sojourn_batch_jax(top, K))
    en = expected_sojourn_batch(top, K)
    np.testing.assert_allclose(ej, en, rtol=1e-5)
    lam0_b = np.stack([top.lam0, 2 * top.lam0])
    tj = np.asarray(solve_traffic_batch_jax(lam0_b, top.routing))
    np.testing.assert_allclose(tj, solve_traffic_batch(lam0_b, top.routing), rtol=1e-5)


def test_jax_table_is_vmappable():
    """Batch of tenant arrival vectors through one vmapped table build."""
    mus = jnp.asarray([2.0, 5.0, 50.0])
    lams = jnp.asarray([[13.0, 13.0, 13.0], [6.0, 6.0, 6.0]])
    fn = jax.vmap(lambda lam: sojourn_table_jax(lam, mus, k_hi=16))
    out = np.asarray(fn(lams))
    assert out.shape == (2, 3, 17)
    single = np.asarray(sojourn_table_jax(lams[1], mus, k_hi=16))
    m = np.isfinite(single)
    np.testing.assert_allclose(out[1][m], single[m], rtol=1e-6)


# ------------------------------------------------------------------ #
# Pallas kernel (interpret mode on CPU) vs the scan oracle
# ------------------------------------------------------------------ #
def test_erlang_b_kernel_interpret_matches_ref():
    a = jnp.asarray(np.linspace(0.1, 40.0, 7), dtype=jnp.float32)
    got = ek.erlang_b_table_pallas(a, k_hi=50, interpret=True)
    want = eref.erlang_b_table(a, k_hi=50)
    assert got.shape == (51, 7)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6, atol=1e-7)


def test_erlang_b_kernel_lane_padding():
    a = jnp.asarray(np.linspace(0.5, 10.0, 130), dtype=jnp.float32)  # > 1 lane row
    got = ek.erlang_b_table_pallas(a, k_hi=12, interpret=True)
    want = eref.erlang_b_table(a, k_hi=12)
    assert got.shape == (13, 130)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6, atol=1e-7)


def test_erlang_b_ref_matches_scalar_recursion():
    from repro.core.erlang import erlang_b

    a = jnp.asarray([0.5, 3.0, 9.5])
    tab = np.asarray(eref.erlang_b_table(a, k_hi=30))
    for i, ai in enumerate([0.5, 3.0, 9.5]):
        for k in (0, 1, 7, 30):
            assert tab[k, i] == pytest.approx(erlang_b(k, ai), rel=1e-5)
