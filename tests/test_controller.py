"""Batched controller parity (ISSUE 5, DESIGN.md §14).

The contract: the batched control plane (core/controller.py) is
*bit-identical* to the scalar ``DRSScheduler`` loop it was extracted
from —

* both committed golden decision traces replay unchanged through the
  batched ``ScenarioRunner`` (B=1 ``tick_batch``);
* a shuffled B-stack of zoo scenarios (mixed widths, allocators,
  overload policies, negotiated leases) decides identically to driving
  each scenario through its own per-scenario scheduler;
* the fused jit path (simulate -> measure -> decide -> apply in one
  lax.scan program) agrees with the float64 twin under enable_x64;
* the ``gain_topr`` Pallas kernel matches its jnp oracle exactly in
  interpret mode on CPU, and both match the scalar heap greedy.
"""

import json
import pathlib
import random

import numpy as np
import pytest

try:
    import jax
    import jax.numpy as jnp
except ImportError:  # pragma: no cover - jax is a hard dep of the repo
    jax = None

from repro.core import controller as ctl
from repro.core.allocator import InsufficientResourcesError, _heap_greedy_counts
from repro.core.jackson import UnstableTopologyError
from repro.core.measurer import MeasurementBatch, MeasurementSnapshot, stack_snapshots
from repro.core.negotiator import Machine, Negotiator, ResourcePool
from repro.core.scheduler import DRSScheduler, SchedulerConfig, SchedulerDecision
from repro.api.session import ScenarioRunner
from repro.streaming.batchsim import (
    BatchQueueSim,
    little_wait,
    per_op_service_time,
    visit_sum_sojourn,
)
from repro.streaming.scenarios import (
    fpd_scenario,
    pack_allocations,
    pack_scenarios,
    scenario_matrix,
    vld_scenario,
)

GOLDEN = pathlib.Path(__file__).resolve().parent / "golden"


# --------------------------------------------------------------------------- #
# The pre-extraction reference: one DRSScheduler object per scenario,
# ticked in a Python loop (the PR-4 ScenarioRunner structure, verbatim).
# --------------------------------------------------------------------------- #
def scalar_reference_run(scenarios, tick_interval=10.0):
    arrays = pack_scenarios(scenarios)
    sim = BatchQueueSim(arrays, backend="numpy")
    k = pack_allocations(scenarios, [s.plan_k0() for s in scenarios])
    scheds = []
    for bi, s in enumerate(scenarios):
        scaling, ga = s.graph.scaling_lists()
        negotiator = None
        if s.negotiated:
            size = max(int(s.machine_size), 1)
            pool = ResourcePool(
                [Machine(f"m{i}", size) for i in range(-(-s.k_max // size))]
            )
            negotiator = Negotiator(pool)
            negotiator.ensure(int(k[bi, : s.graph.n].sum()))
        scheds.append(DRSScheduler(
            s.graph.names,
            s.graph.routing_matrix(),
            k[bi, : s.graph.n].copy(),
            SchedulerConfig(
                k_max=None if negotiator is not None else s.k_max,
                t_max=s.t_max,
                tick_interval=tick_interval,
                allocator=s.allocator,
            ),
            negotiator=negotiator,
            scaling=scaling,
            group_alpha=ga,
            speed_factors=s.speed_vector(),
        ))
    decisions = [[] for _ in scenarios]
    steps_per_tick = max(int(round(tick_interval / arrays.dt)), 1)
    while sim.step_index < arrays.steps:
        w = sim.step_window(k, steps_per_tick)
        for bi, (s, sched) in enumerate(zip(scenarios, scheds)):
            n = s.graph.n
            span = w["span"]
            lam_hat = w["offered"][bi, :n] / span
            drop_hat = w["dropped"][bi, :n] / span
            mu = arrays.mu[bi, :n]
            mu_eff = mu if arrays.speed is None else mu * arrays.speed[bi, :n]
            admitted = np.maximum(lam_hat - drop_hat, 0.0)
            wait = little_wait(w["q_mean"][bi, :n], admitted, arrays.dt)
            svc = per_op_service_time(
                w["capacity"][bi, :n], mu_eff, arrays.group[bi, :n]
            )
            lam0 = max(w["ext_admitted"][bi] / span, 0.0)
            sojourn = float(visit_sum_sojourn(admitted, wait, svc, lam0))
            snap = MeasurementSnapshot.from_rates(
                lam_hat, mu, lam0, sojourn, sim.now, drop_hat=drop_hat
            )
            try:
                d = sched.tick_from(snap, sim.now)
            except (InsufficientResourcesError, UnstableTopologyError) as e:
                d = SchedulerDecision(
                    sim.now, "infeasible", sched.k_current.copy(), None,
                    s.k_max, float("inf"), None, snap.sojourn_hat, reason=str(e),
                )
            decisions[bi].append(d)
            if (
                d.action in ("rebalance", "scale_out", "scale_in", "overloaded")
                and d.k_target is not None
            ):
                k[bi, :n] = d.k_target
    return decisions, k


def assert_decisions_identical(batched, scalar):
    assert len(batched) == len(scalar)
    for bi, (b_decs, s_decs) in enumerate(zip(batched, scalar)):
        actions_b = [d.action for d in b_decs]
        actions_s = [d.action for d in s_decs]
        assert actions_b == actions_s, f"scenario {bi}: {actions_b} != {actions_s}"
        for ti, (db, ds) in enumerate(zip(b_decs, s_decs)):
            np.testing.assert_array_equal(
                db.k_current, ds.k_current, err_msg=f"scenario {bi} tick {ti}"
            )
            # bit-identical model values, not approx
            assert db.model_sojourn_current == ds.model_sojourn_current or (
                np.isnan(db.model_sojourn_current)
                and np.isnan(ds.model_sojourn_current)
            ), f"scenario {bi} tick {ti} E[T] drifted"


# --------------------------------------------------------------------------- #
# Golden traces through the batched path at B=1
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("name,factory", [("vld", vld_scenario), ("fpd", fpd_scenario)])
def test_golden_replay_through_batched_controller(name, factory):
    """The committed fixtures (generated pre-extraction) must replay
    bit-for-bit through tick_batch at B=1."""
    want = json.loads((GOLDEN / f"{name}_control_trace.json").read_text())
    s = factory()
    runner = ScenarioRunner([s], tick_interval=want["tick_interval"], backend="numpy")
    reports = runner.run()
    got_actions = list(reports[0].actions)
    got_allocs = [dict(a) for a in reports[0].allocations]
    assert got_actions == want["scenarios"][name]["actions"]
    assert got_allocs == want["scenarios"][name]["allocations"]


@pytest.mark.parametrize("name,factory", [("vld", vld_scenario), ("fpd", fpd_scenario)])
def test_golden_scenarios_batch_vs_scalar_bit_identical(name, factory):
    """B=1 tick_batch vs a hand-rolled per-scenario DRSScheduler loop:
    identical decisions, allocations, and model values."""
    s = factory()
    runner = ScenarioRunner([s], tick_interval=10.0, backend="numpy")
    runner.run()
    scalar_decs, scalar_k = scalar_reference_run([s], tick_interval=10.0)
    assert_decisions_identical(runner.decisions, scalar_decs)
    np.testing.assert_array_equal(runner.k, scalar_k)


# --------------------------------------------------------------------------- #
# Property: a shuffled B-stack decides like B independent scalar loops
# --------------------------------------------------------------------------- #
def test_shuffled_stack_decides_identically_to_scalar_ticks():
    scens = scenario_matrix(8, seed=21, horizon=25.0, warmup=5.0, dt=0.05)
    rng = random.Random(3)
    rng.shuffle(scens)
    runner = ScenarioRunner(scens, tick_interval=5.0, backend="numpy")
    runner.run()
    scalar_decs, scalar_k = scalar_reference_run(scens, tick_interval=5.0)
    assert_decisions_identical(runner.decisions, scalar_decs)
    np.testing.assert_array_equal(runner.k, scalar_k)
    # the matrix must actually exercise the interesting axes
    all_actions = {d.action for decs in runner.decisions for d in decs}
    assert all_actions - {"none"}, "matrix produced only no-ops"


def test_mixed_width_stack_pads_safely():
    """Scenarios of different operator counts share one padded stack."""
    scens = scenario_matrix(6, seed=4, horizon=15.0, warmup=2.0, dt=0.05)
    widths = {s.graph.n for s in scens}
    assert len(widths) > 1, "zoo should produce mixed widths"
    runner = ScenarioRunner(scens, tick_interval=5.0, backend="numpy")
    reports = runner.run()
    for s, r in zip(scens, reports):
        assert set(r.k_final) == set(s.graph.names)


# --------------------------------------------------------------------------- #
# Fused jit loop vs the float64 twin
# --------------------------------------------------------------------------- #
def test_fused_loop_matches_twin_under_x64():
    scens = [
        s.with_(negotiated=False)
        for s in scenario_matrix(4, seed=11, horizon=20.0, warmup=5.0, dt=0.05)
    ]
    with jax.enable_x64(True):
        twin = ScenarioRunner(scens, tick_interval=5.0, backend="numpy")
        r_twin = twin.run()
        fused = ScenarioRunner(scens, tick_interval=5.0, backend="jax")
        assert fused.fused, "static-budget jax runner should take the fused path"
        r_fused = fused.run()
    for a, b in zip(r_twin, r_fused):
        assert list(a.actions) == list(b.actions), a.name
        assert a.k_final == b.k_final, a.name
        assert a.provisioned_total == b.provisioned_total


def test_fused_warm_window_rule_matches_twin():
    """Window warmness is judged in seconds (t0 >= warmup), not rounded
    steps — deadline-miss accounting must agree between backends even
    when warmup is not a multiple of dt."""
    scens = [
        s.with_(negotiated=False, warmup=5.3, dt=0.25, horizon=20.0)
        for s in scenario_matrix(3, seed=6, horizon=20.0, warmup=5.3, dt=0.25)
    ]
    with jax.enable_x64(True):
        twin = ScenarioRunner(scens, tick_interval=5.0, backend="numpy")
        r_twin = twin.run()
        fused = ScenarioRunner(scens, tick_interval=5.0, backend="jax")
        assert fused.fused
        r_fused = fused.run()
    assert twin._windows_warm == fused._windows_warm
    np.testing.assert_array_equal(twin._miss, fused._miss)
    for a, b in zip(r_twin, r_fused):
        assert list(a.actions) == list(b.actions)


def test_fused_loop_float32_smoke():
    """The fused program must run (and make sane decisions) at JAX's
    default float32 precision — the TPU configuration."""
    scens = [
        s.with_(negotiated=False)
        for s in scenario_matrix(3, seed=13, horizon=15.0, warmup=2.0, dt=0.05)
    ]
    runner = ScenarioRunner(scens, tick_interval=5.0, backend="jax")
    assert runner.fused
    reports = runner.run()
    for s, r in zip(scens, reports):
        assert len(r.actions) == runner.arrays.steps // runner._steps_per_tick
        assert sum(r.k_final.values()) <= s.k_max
        assert set(r.actions) <= set(ctl.ACTIONS)


def test_negotiated_scenarios_fall_back_to_twin():
    scens = scenario_matrix(3, seed=2, horizon=15.0, warmup=2.0, dt=0.05)
    scens[0] = scens[0].with_(negotiated=True)
    runner = ScenarioRunner(scens, tick_interval=5.0, backend="jax")
    assert not runner.fused  # leases are Python: batch-boundary hooks
    reports = runner.run()
    assert len(reports) == 3


def test_forcing_fused_past_preconditions_raises():
    from repro.api.graph import GraphValidationError

    scens = scenario_matrix(2, seed=2, horizon=15.0, warmup=2.0, dt=0.05)
    scens[0] = scens[0].with_(negotiated=True)
    with pytest.raises(GraphValidationError):
        ScenarioRunner(scens, tick_interval=5.0, backend="jax", fused=True)
    with pytest.raises(GraphValidationError):
        ScenarioRunner(
            [s.with_(negotiated=False) for s in scens],
            tick_interval=5.0, backend="jax", controlled=False, fused=True,
        )


# --------------------------------------------------------------------------- #
# gain_topr: oracle vs kernel vs scalar greedy
# --------------------------------------------------------------------------- #
def _random_gain_rows(rng, b, n, j):
    cand = np.maximum(rng.normal(0.6, 1.0, (b, n, j)), 0.0)
    cand.sort(axis=-1)
    return cand[..., ::-1].copy()  # non-increasing rows (convexity)


def test_gain_topr_oracle_matches_scalar_greedy():
    from repro.kernels.gain_topr import ref

    rng = np.random.default_rng(0)
    cand = _random_gain_rows(rng, 6, 5, 16).astype(np.float64)
    budgets = np.array([0, 1, 7, 80, 13, 40], dtype=np.int32)
    take = np.asarray(ref.gain_topr(jnp.asarray(cand), jnp.asarray(budgets)))
    for bi in range(cand.shape[0]):
        want = _heap_greedy_counts(cand[bi], int(budgets[bi]))
        np.testing.assert_array_equal(take[bi], want, err_msg=f"lane {bi}")
        assert take[bi].sum() == min(int(budgets[bi]), (cand[bi] > 0).sum())


def test_gain_topr_kernel_interpret_parity():
    from repro.kernels.gain_topr import kernel, ref

    rng = np.random.default_rng(1)
    cand = _random_gain_rows(rng, 7, 6, 20).astype(np.float32)
    budgets = np.array([0, 3, 9, 200, 17, 5, 60], dtype=np.int32)
    want = np.asarray(ref.gain_topr(jnp.asarray(cand), jnp.asarray(budgets)))
    got = np.asarray(
        kernel.gain_topr_pallas(jnp.asarray(cand), jnp.asarray(budgets), interpret=True)
    )
    np.testing.assert_array_equal(got, want)


def test_gain_topr_kernel_breaks_ties_in_row_order():
    from repro.kernels.gain_topr import kernel, ref

    cand = np.zeros((1, 3, 4), np.float32)
    cand[0] = [[2, 1, 1, 0], [2, 1, 0, 0], [1, 1, 1, 0]]
    bud = np.array([5], np.int32)
    want = np.asarray(ref.gain_topr(jnp.asarray(cand), jnp.asarray(bud)))
    got = np.asarray(
        kernel.gain_topr_pallas(jnp.asarray(cand), jnp.asarray(bud), interpret=True)
    )
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(want[0], _heap_greedy_counts(cand[0].astype(np.float64), 5))


# --------------------------------------------------------------------------- #
# The decide's in-place candidates and one-hot price select vs the gathers
# --------------------------------------------------------------------------- #
def _gathered_candidates(G, k_start, active):
    """The candidate rows as gathered before: operator i's row shifted to
    start at its minimal feasible ``k_start``, padded past k_hi with 0."""
    k_hi = G.shape[-1]
    idx = k_start[..., None] + jnp.arange(k_hi, dtype=jnp.int32)
    cand = jnp.take_along_axis(G, jnp.clip(idx, 0, k_hi - 1), axis=-1)
    return jnp.where((idx < k_hi) & active[..., None] & jnp.isfinite(cand), cand, 0.0)


def _gain_case(b, n, k_hi, seed):
    """Gain tables with every edge the decide meets: ``inf`` below a
    random first finite column and ``inf``/NaN scattered anywhere; gains
    from {0, 0.5, 1, 1.5} (ties within and across rows) or normal, about
    a third negative; ``k_start`` over [0, k_hi + 1]; inactive operators;
    budgets 0, 1, inside the positive count, exactly it and above it."""
    rng = np.random.default_rng(seed)
    ties = rng.choice([0.0, 0.5, 1.0, 1.5], (b, n, k_hi))
    mixed = rng.normal(0.5, 1.0, (b, n, k_hi))
    G = np.where(rng.random((b, 1, 1)) < 0.5, ties, mixed)
    k_start = rng.integers(0, k_hi + 2, (b, n)).astype(np.int32)
    G[np.arange(k_hi) < k_start[..., None] - rng.integers(0, 2, (b, n, 1))] = np.inf
    odd = rng.random((b, n, k_hi))
    G[odd < 0.02] = np.inf
    G[odd > 0.98] = np.nan
    active = rng.random((b, n)) < 0.85
    pos = (np.asarray(_gathered_candidates(G, k_start, active)) > 0).sum(axis=(1, 2))
    pick = np.arange(b) % 5
    budget = np.select([pick == 0, pick == 1, pick == 2, pick == 3],
                       [0, 1, pos, pos + 3], rng.integers(1, pos + 2))
    return (G.astype(np.float32), k_start, active, budget.astype(np.int32))


@pytest.mark.parametrize("n,k_hi", [(3, 22), (7, 44)], ids=["vld", "nexmark"])
@pytest.mark.parametrize("impl,b", [("ref", 96), ("pallas", 40), ("lanes", 200)])
def test_masked_candidates_take_what_the_gather_took(impl, b, n, k_hi):
    """Program (4) on G masked in place below k_start takes bitwise what it
    took on the gathered rows, on the oracle and both kernel layouts
    (interpret mode; B < 128 per scenario, B >= 128 lanes)."""
    from repro.kernels.gain_topr import kernel, ref

    G, k_start, active, budget = _gain_case(b, n, k_hi, seed=b + n)
    old = _gathered_candidates(G, k_start, active)
    new = ctl._masked_candidates(jnp.asarray(G), jnp.asarray(k_start), jnp.asarray(active))
    # The same positive gains per row, in other places.
    pos_sorted = lambda c: np.sort(np.where(np.asarray(c) > 0, np.asarray(c), 0.0), axis=-1)
    np.testing.assert_array_equal(pos_sorted(new), pos_sorted(old))
    if impl == "ref":
        topr = ref.gain_topr
    else:
        assert (kernel._lane_tile(b, n, k_hi + (-k_hi) % 8) is not None) == (impl == "lanes")
        topr = lambda c, bud: kernel.gain_topr_pallas(c, bud, interpret=True)
    want = np.asarray(topr(old, budget))
    np.testing.assert_array_equal(np.asarray(topr(new, budget)), want)
    assert (want.sum(axis=-1) == np.minimum(budget, (np.asarray(old) > 0).sum(axis=(1, 2)))).all()


@pytest.mark.parametrize("x64", [False, True], ids=["f32", "f64"])
def test_column_select_is_the_gather_bitwise(x64):
    """The price stage's one-hot select equals ``take_along_axis`` bit for
    bit: leading ``inf`` columns (below the least stable k), ``inf`` and
    NaN elsewhere, k over [0, k_hi] and clipped from outside it."""
    rng = np.random.default_rng(3)
    b, n, k_hi = 64, 7, 44
    T = rng.gamma(2.0, 1e-3, (b, n, k_hi + 1))
    T[np.arange(k_hi + 1) < rng.integers(0, k_hi + 2, (b, n, 1))] = np.inf
    T[rng.random(T.shape) < 0.02] = np.nan
    k_vec = rng.integers(-2, k_hi + 4, (b, n)).astype(np.int32)
    with jax.enable_x64(x64):
        Tj = jnp.asarray(T, dtype=jnp.float64 if x64 else jnp.float32)
        want = jnp.take_along_axis(Tj, jnp.clip(k_vec, 0, k_hi)[..., None], axis=-1)[..., 0]
        uint = np.uint64 if x64 else np.uint32
        # Op by op too: under jit XLA may turn a multiply by a 0/1 mask
        # into the select it stands for, and so hide a NaN.
        for select in (ctl._column_select, jax.jit(ctl._column_select)):
            got = select(Tj, jnp.asarray(k_vec))
            assert got.dtype == want.dtype
            np.testing.assert_array_equal(np.asarray(got).view(uint),
                                          np.asarray(want).view(uint))
        assert np.isinf(np.asarray(want)).any() and np.isnan(np.asarray(want)).any()


def _zoo_decide_case(seeds, extra_budget):
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


@pytest.mark.parametrize("seeds,extra_budget", [((0, 1, 2, 3), 64), ((4, 5), 1024)],
                         ids=["k64", "k1024"])
def test_decide_matches_float64_twin_on_zoo(seeds, extra_budget):
    """The jit decide equals the float64 numpy twin (``tick_batch``) on
    random AppGraph fleets, budgets 64 and 1024 above the floor, gang
    lanes included: codes, allocations and the applied mask exactly."""
    import dataclasses

    from repro.streaming.scenarios import random_appgraph

    lam, mu, group, alpha, _, k_cur, k_max = _zoo_decide_case(seeds, extra_budget)
    graphs = [random_appgraph(s) for s in seeds]
    static = dataclasses.replace(
        ctl.ControllerStatic.from_graphs(graphs), group=group, alpha=alpha
    )
    b = static.batch
    full = lambda v: np.full(b, v)
    params = ctl.ControllerParams(
        t_max=full(np.nan), k_max=k_max.astype(np.int64), headroom=full(1.1),
        scale_in_hysteresis=full(0.8), min_improvement=full(0.05),
        horizon_seconds=full(300.0), allocator=("table",) * b,
    )
    lam0 = np.array([g.topology().lam0_total for g in graphs])
    drop = np.zeros_like(lam)
    k = np.where(static.active, k_cur, 0).astype(np.int64)
    codes = set()
    # Two ticks: the second from the allocation the first applied.
    for _ in range(2):
        rows = ctl.tick_batch(
            MeasurementBatch(lam, mu, lam0, np.full(b, np.nan), 0.0, drop), k, static,
            params,
        ).rows
        with jax.enable_x64(True):
            code, k_next, _, _, applied = map(
                np.asarray, ctl.make_decide_jax(static, params)(lam, mu, drop, lam0, k)
            )
        np.testing.assert_array_equal(code, [r.code for r in rows])
        want = np.zeros_like(k)
        for bi, r in enumerate(rows):  # twin rows are each lane's own width
            want[bi, : len(r.k_next)] = r.k_next
        np.testing.assert_array_equal(k_next, want)
        np.testing.assert_array_equal(applied, [r.applied for r in rows])
        codes |= set(code.tolist())
        k = want
    assert len(codes) > 1, codes


# --------------------------------------------------------------------------- #
# MeasurementBatch plumbing
# --------------------------------------------------------------------------- #
def test_stack_snapshots_roundtrip():
    s1 = MeasurementSnapshot.from_rates([1.0, 2.0], [3.0, 4.0], 1.0, 0.5, 10.0,
                                        drop_hat=[0.1, 0.0])
    s2 = MeasurementSnapshot.from_rates([5.0], [6.0], 5.0, 0.2, 10.0)
    batch = stack_snapshots([s1, s2])
    assert batch.batch == 2 and batch.n == 2
    r1 = batch.row(0, 2)
    np.testing.assert_array_equal(r1.lam_hat, s1.lam_hat)
    np.testing.assert_array_equal(r1.drop_rates(), s1.drop_rates())
    r2 = batch.row(1, 1)
    np.testing.assert_array_equal(r2.lam_hat, s2.lam_hat)
    # padding lanes are inert: finite mu, zero rates
    assert batch.mu_hat[1, 1] == 1.0 and batch.lam_hat[1, 1] == 0.0


def test_measurement_batch_complete_mask():
    batch = MeasurementBatch.from_rates(
        [[1.0, np.nan], [1.0, 2.0]], [[1.0, 1.0], [1.0, 1.0]],
        [1.0, 1.0], [0.1, 0.1], 0.0,
    )
    np.testing.assert_array_equal(batch.complete(), [False, True])
    active = np.array([[True, False], [True, True]])
    np.testing.assert_array_equal(batch.complete(active), [True, True])
