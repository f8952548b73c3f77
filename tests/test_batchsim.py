"""Batch simulator: queue_step kernel vs oracle, numpy twin vs jit,
seed determinism for every process kind, and DES-vs-batchsim conformance
(ISSUE 4; DESIGN.md §13)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import AppGraph, Edge, OpDef
from repro.kernels.queue_step import kernel as qk, ref as qref
from repro.streaming import (
    ArrivalProcess,
    ArrivalTrace,
    BatchQueueSim,
    Scenario,
    ServiceProcess,
    pack_scenarios,
    scenario_matrix,
)
from repro.streaming.scenarios import pack_allocations

ARRIVAL_KINDS = ("exponential", "uniform", "deterministic", "mmpp", "burst")
SERVICE_KINDS = ("exponential", "uniform", "deterministic", "lognormal")


def chain_graph(lam0=10.0):
    return AppGraph(
        [OpDef("a", mu=4.0), OpDef("b", mu=6.0), OpDef("c", mu=20.0)],
        [Edge("a", "b"), Edge("b", "c", multiplicity=0.7),
         Edge("b", "b", multiplicity=0.2)],
        {"a": lam0},
    )


K = {"a": 5, "b": 4, "c": 2}


def scenario(**kw):
    defaults = dict(
        name="t",
        graph=chain_graph(),
        traces={"a": ArrivalTrace(kind="constant", rate=10.0)},
        seed=3,
        horizon=120.0,
        warmup=10.0,
        dt=0.02,
    )
    defaults.update(kw)
    return Scenario(**defaults)


def run_batch(scens, ks, **kw):
    arrays = pack_scenarios(scens)
    sim = BatchQueueSim(arrays, **kw)
    kv = pack_allocations(scens, ks)
    res = sim.run(kv)
    return arrays, kv, res


# ------------------------------------------------------------------ #
# queue_step kernel: Pallas (interpret) vs jnp oracle
# ------------------------------------------------------------------ #
def test_queue_step_kernel_interpret_matches_ref():
    rng = np.random.default_rng(0)
    m = 37
    q = jnp.asarray(rng.uniform(0, 50, m), dtype=jnp.float32)
    inflow = jnp.asarray(rng.uniform(0, 10, m), dtype=jnp.float32)
    cap_s = jnp.asarray(rng.uniform(0, 8, m), dtype=jnp.float32)
    cap_q = jnp.asarray(
        np.where(rng.random(m) < 0.5, rng.uniform(5, 40, m), np.inf), dtype=jnp.float32
    )
    got = qk.queue_step_pallas(q, inflow, cap_s, cap_q, interpret=True)
    want = qref.queue_step(q, inflow, cap_s, cap_q)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-6, atol=1e-6)


def test_queue_step_kernel_lane_padding():
    m = 300  # > 2 lane rows
    q = jnp.linspace(0.0, 30.0, m)
    inflow = jnp.full((m,), 2.0)
    got = qk.queue_step_pallas(q, inflow, jnp.full((m,), 5.0), jnp.full((m,), 10.0),
                               interpret=True)
    want = qref.queue_step(q.astype(jnp.float32), inflow, jnp.full((m,), 5.0),
                           jnp.full((m,), 10.0))
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]), rtol=1e-6)


def test_queue_step_semantics():
    """Served caps at capacity; shed lanes drop the overflow; +inf lanes
    (block / unbounded) never drop."""
    q = jnp.asarray([10.0, 10.0, 10.0])
    inflow = jnp.asarray([8.0, 8.0, 8.0])
    cap_s = jnp.asarray([4.0, 4.0, 4.0])
    cap_q = jnp.asarray([8.0, jnp.inf, 100.0])
    q2, served, dropped = qref.queue_step(q, inflow, cap_s, cap_q)
    np.testing.assert_allclose(np.asarray(served), [4.0, 4.0, 4.0])
    # lane 0: q1=6, space=2 -> admit 2, drop 6; lane 1/2: admit all
    np.testing.assert_allclose(np.asarray(dropped), [6.0, 0.0, 0.0])
    np.testing.assert_allclose(np.asarray(q2), [8.0, 14.0, 14.0])


# ------------------------------------------------------------------ #
# Seed determinism — batch sim
# ------------------------------------------------------------------ #
def test_batchsim_bit_identical_across_runs():
    scens = scenario_matrix(4, seed=5, horizon=20.0, warmup=2.0)
    ks = [s.plan_k0() for s in scens]
    _, _, r1 = run_batch(scens, ks)
    _, _, r2 = run_batch(scens, ks)
    for name in ("offered", "served", "dropped", "q_final", "q_mean",
                 "max_backlog", "ext_admitted"):
        np.testing.assert_array_equal(getattr(r1, name), getattr(r2, name))


def test_batchsim_seed_changes_arrivals():
    s1, s2 = scenario(seed=1), scenario(seed=2)
    assert not np.array_equal(s1.sample_arrivals(), s2.sample_arrivals())
    np.testing.assert_array_equal(s1.sample_arrivals(), scenario(seed=1).sample_arrivals())


def test_batchsim_numpy_twin_matches_jit_x64():
    scens = scenario_matrix(5, seed=7, horizon=15.0, warmup=2.0)
    ks = [s.plan_k0() for s in scens]
    _, _, rn = run_batch(scens, ks, backend="numpy")
    with jax.enable_x64(True):
        _, _, rj = run_batch(scens, ks, backend="jax")
    for name in ("offered", "served", "dropped", "q_final", "q_mean", "ext_admitted"):
        np.testing.assert_allclose(
            getattr(rn, name), getattr(rj, name), rtol=1e-9, atol=1e-9
        )


def test_batchsim_jit_pallas_interpret_agrees():
    scens = scenario_matrix(3, seed=9, horizon=10.0, warmup=1.0)
    ks = [s.plan_k0() for s in scens]
    _, _, rn = run_batch(scens, ks, backend="numpy")
    with jax.enable_x64(True):
        _, _, rk = run_batch(scens, ks, backend="jax", force_kernel=True, interpret=True)
    # float32 kernel inside a float64 scan: loose elementwise agreement
    np.testing.assert_allclose(rk.offered, rn.offered, rtol=1e-4, atol=1e-2)
    np.testing.assert_allclose(rk.dropped, rn.dropped, rtol=1e-3, atol=0.5)
    np.testing.assert_allclose(rk.q_final, rn.q_final, rtol=1e-3, atol=0.5)


# ------------------------------------------------------------------ #
# Seed determinism — event DES, every process kind
# ------------------------------------------------------------------ #
def _des_result(arrival_kind, service_kind, seed=11):
    from repro.streaming import NetworkSimulator, SimConfig

    top = chain_graph().topology()
    kw = {}
    if arrival_kind in ("mmpp", "burst"):
        kw = {"rate2": 25.0, "burst_every": 10.0, "burst_length": 2.0}
    arrivals = [
        ArrivalProcess(rate=float(top.lam0[i]), kind=arrival_kind, **kw)
        for i in range(top.n)
    ]
    services = [ServiceProcess(rate=op.mu, kind=service_kind, cv=0.8)
                for op in top.operators]
    sim = NetworkSimulator(
        top, [5, 4, 2],
        config=SimConfig(seed=seed, horizon=40.0, warmup=5.0, queue_capacity=30,
                         overload_policy="shed-oldest"),
        arrivals=arrivals, services=services,
    )
    return sim.run()


@pytest.mark.parametrize("arrival_kind", ARRIVAL_KINDS)
def test_des_seed_determinism_arrival_kinds(arrival_kind):
    a = _des_result(arrival_kind, "exponential")
    b = _des_result(arrival_kind, "exponential")
    assert a.completed == b.completed
    assert a.dropped == b.dropped
    assert a.mean_sojourn == b.mean_sojourn  # bit-identical, not approx
    np.testing.assert_array_equal(a.per_op_dropped, b.per_op_dropped)
    np.testing.assert_array_equal(a.per_op_max_backlog, b.per_op_max_backlog)
    np.testing.assert_array_equal(a.per_op_arrival_rate, b.per_op_arrival_rate)


@pytest.mark.parametrize("service_kind", SERVICE_KINDS)
def test_des_seed_determinism_service_kinds(service_kind):
    a = _des_result("exponential", service_kind)
    b = _des_result("exponential", service_kind)
    assert a.completed == b.completed and a.mean_sojourn == b.mean_sojourn
    np.testing.assert_array_equal(a.per_op_dropped, b.per_op_dropped)


def test_des_different_seeds_differ():
    a = _des_result("exponential", "exponential", seed=1)
    b = _des_result("exponential", "exponential", seed=2)
    assert a.mean_sojourn != b.mean_sojourn


# ------------------------------------------------------------------ #
# DES-vs-batchsim conformance (DESIGN.md §13 divergence bounds)
# ------------------------------------------------------------------ #
@pytest.mark.parametrize("policy", ["shed-newest", "shed-oldest", "block"])
def test_conformance_stable_sojourn_and_drops(policy):
    """Stable scenario: steady-state visit-sum sojourn within 10% and
    (near-)zero drop rates under every overload policy."""
    s = scenario(arrival_kind="exponential", service_kind="exponential",
                 overload_policy=policy, queue_capacity=40,
                 horizon=300.0, warmup=20.0)
    arrays, kv, res = run_batch([s], [K])
    des = s.simulator(K).run()
    batch_soj = float(res.sojourn(kv, arrays.mu, arrays.group, arrays.alpha,
                                 ca2=arrays.ca2, cs2=arrays.cs2)[0])
    assert batch_soj == pytest.approx(des.mean_visit_sum, rel=0.10)
    batch_drop = res.dropped[0].sum() / max(res.offered[0].sum(), 1e-9)
    des_drop = des.dropped / max(des.per_op_arrival_rate.sum() * 280.0, 1e-9)
    assert batch_drop < 0.01 and des_drop < 0.01
    # per-operator offered rates agree tightly (traffic equations in action)
    np.testing.assert_allclose(
        res.arrival_rate[0], des.per_op_arrival_rate, rtol=0.08
    )


def test_conformance_stable_deterministic_is_tight():
    s = scenario(arrival_kind="deterministic", service_kind="deterministic",
                 horizon=300.0, warmup=20.0)
    arrays, kv, res = run_batch([s], [K])
    des = s.simulator(K).run()
    batch_soj = float(res.sojourn(kv, arrays.mu, arrays.group, arrays.alpha,
                                 ca2=arrays.ca2, cs2=arrays.cs2)[0])
    assert batch_soj == pytest.approx(des.mean_visit_sum, rel=0.03)


@pytest.mark.parametrize("policy", ["shed-newest", "shed-oldest", "block"])
def test_conformance_overloaded_agrees_on_saturation(policy):
    """Overloaded scenario (2x capacity at the source): both simulators
    must flag the same saturated operators; shed policies must agree on
    the aggregate drop rate within 15%."""
    s = scenario(
        traces={"a": ArrivalTrace(kind="constant", rate=30.0)},
        overload_policy=policy, queue_capacity=20,
        seed=5, horizon=200.0, warmup=20.0,
    )
    arrays, kv, res = run_batch([s], [K])
    des = s.simulator(K).run()
    sat_batch = res.saturated(kv, arrays.mu, arrays.group, arrays.alpha)[0]
    cap = np.array([5 * 4.0, 4 * 6.0, 2 * 20.0])
    sat_des = des.per_op_arrival_rate >= cap * (1.0 - 1e-9)
    np.testing.assert_array_equal(sat_batch, sat_des)
    assert sat_batch[0], "source must saturate at 2x capacity"
    if policy == "block":
        assert res.dropped[0].sum() == 0 and des.dropped == 0
        # blocked backlog grows without shedding in both simulators
        assert res.max_backlog[0].max() > 100
        assert des.per_op_max_backlog.max() > 100
    else:
        batch_rate = res.drop_rate[0].sum()
        des_rate = des.per_op_drop_rate.sum()
        assert batch_rate == pytest.approx(des_rate, rel=0.15)


def test_conformance_group_scaling():
    """Chip-gang operators (DESIGN.md §2) get the same gang-collapse in
    both simulators: one effective server at mu * k * eff(k)."""
    graph = AppGraph(
        [OpDef("tok", mu=8.0), OpDef("gang", mu=3.0, scaling="group", group_alpha=0.05)],
        [Edge("tok", "gang")],
        {"tok": 10.0},
    )
    k = {"tok": 3, "gang": 6}
    s = Scenario(name="g", graph=graph,
                 traces={"tok": ArrivalTrace(kind="constant", rate=10.0)},
                 arrival_kind="deterministic", service_kind="deterministic",
                 seed=3, horizon=200.0, warmup=20.0, dt=0.02)
    arrays, kv, res = run_batch([s], [k])
    des = s.simulator(k).run()
    batch_soj = float(res.sojourn(kv, arrays.mu, arrays.group, arrays.alpha,
                                 ca2=arrays.ca2, cs2=arrays.cs2)[0])
    assert batch_soj == pytest.approx(des.mean_visit_sum, rel=0.05)
    # effective gang rate: 3 * 6 / (1 + 0.05 * 5) = 14.4 > 10 -> stable
    assert not res.saturated(kv, arrays.mu, arrays.group, arrays.alpha)[0].any()


# Conformance floor per trace family (ISSUE 9 / DESIGN.md §17): observed
# rel errs with 3-seed DES averaging are ~0.06/0.02/0.13/0.07 — the gates
# leave ~2x headroom while staying under the 0.2 bench assertion.
_FAMILY_TOL = {"constant": 0.12, "diurnal": 0.10, "flash": 0.20, "mmpp": 0.15}


def _family_trace(family, base=10.0, h=240.0):
    if family == "constant":
        return ArrivalTrace(kind="constant", rate=base)
    if family == "diurnal":
        return ArrivalTrace(kind="diurnal", rate=base, amplitude=0.5 * base,
                            period=0.5 * h)
    if family == "flash":
        return ArrivalTrace(kind="flash", rate=base, peak=1.6 * base,
                            t_on=0.4 * h, t_off=0.6 * h)
    return ArrivalTrace(kind="mmpp", rate=0.7 * base, peak=1.5 * base,
                        switch01=0.05, switch10=0.1)


@pytest.mark.parametrize("policy", ["block", "shed-newest", "shed-oldest"])
@pytest.mark.parametrize("family", ["constant", "diurnal", "flash", "mmpp"])
def test_conformance_policy_family_matrix(policy, family):
    """DES vs batchsim visit-sum sojourn across the (overload policy x
    trace family) cross-product.  The DES side is averaged over 3 seeds
    (single-seed flash/mmpp runs have up to ~37% CV, which would make any
    sub-0.2 gate meaningless); the trace realization itself stays pinned
    to the scenario seed on both sides."""
    h = 240.0
    s = scenario(traces={"a": _family_trace(family, h=h)},
                 overload_policy=policy, queue_capacity=60,
                 horizon=h, warmup=20.0, seed=11)
    arrays, kv, res = run_batch([s], [K])
    assert not res.saturated(kv, arrays.mu, arrays.group, arrays.alpha)[0].any()
    batch_soj = float(res.sojourn(kv, arrays.mu, arrays.group, arrays.alpha,
                                  ca2=arrays.ca2, cs2=arrays.cs2)[0])
    des = float(np.mean(
        [s.simulator(K, seed=101 + i).run().mean_visit_sum for i in range(3)]
    ))
    assert batch_soj == pytest.approx(des, rel=_FAMILY_TOL[family])
    # stable matrix: every policy admits everything, so both simulators
    # must agree that (near-)nothing is dropped regardless of policy
    assert res.dropped[0].sum() / max(res.offered[0].sum(), 1e-9) < 0.01


@pytest.mark.slow
@pytest.mark.parametrize("policy", ["shed-newest", "shed-oldest", "block"])
@pytest.mark.parametrize("arrival_kind,service_kind,tol",
                         [("deterministic", "deterministic", 0.03),
                          ("exponential", "exponential", 0.12),
                          ("uniform", "uniform", 0.12)])
def test_conformance_extended_sweep(policy, arrival_kind, service_kind, tol):
    """Long-horizon stable-scenario conformance across the (policy x
    process-kind) cross-product — the `-m slow` CI tier."""
    s = scenario(arrival_kind=arrival_kind, service_kind=service_kind,
                 overload_policy=policy, queue_capacity=60,
                 horizon=600.0, warmup=50.0, seed=17)
    arrays, kv, res = run_batch([s], [K])
    des = s.simulator(K).run()
    batch_soj = float(res.sojourn(kv, arrays.mu, arrays.group, arrays.alpha,
                                 ca2=arrays.ca2, cs2=arrays.cs2)[0])
    assert batch_soj == pytest.approx(des.mean_visit_sum, rel=tol)
    np.testing.assert_allclose(res.arrival_rate[0], des.per_op_arrival_rate, rtol=0.06)
    assert res.dropped[0].sum() / max(res.offered[0].sum(), 1e-9) < 0.01


@pytest.mark.slow
def test_controlled_matrix_32_scenarios():
    """The CI smoke matrix: 32 scenarios end-to-end through the control
    loop; every scenario must finish with a feasible, bounded outcome."""
    from repro.api import ScenarioRunner

    scens = scenario_matrix(32, seed=42, horizon=40.0, warmup=5.0)
    reports = ScenarioRunner(scens, tick_interval=5.0).run()
    assert len(reports) == 32
    for r in reports:
        assert r.provisioned_total >= 1
        assert 0.0 <= r.drop_rate <= 1.0
        assert len(r.actions) == len(r.allocations) > 0
    # the matrix must exercise the interesting action space somewhere
    all_actions = {a for r in reports for a in r.actions}
    assert {"rebalance", "none"} <= all_actions


def test_conformance_flash_crowd_direction():
    """A flash crowd sheds during the burst in both simulators, and the
    batch sim sees the same post-burst recovery (bounded final backlog)."""
    s = scenario(
        traces={"a": ArrivalTrace(kind="flash", rate=8.0, peak=40.0,
                                  t_on=40.0, t_off=60.0)},
        overload_policy="shed-oldest", queue_capacity=25,
        seed=13, horizon=120.0, warmup=10.0,
    )
    arrays, kv, res = run_batch([s], [K])
    des = s.simulator(K).run()
    assert res.dropped[0].sum() > 0 and des.dropped > 0
    assert res.q_final[0].max() < 30  # recovered after the burst
    rel = res.dropped[0].sum() / max(des.dropped, 1)
    assert 0.6 < rel < 1.6
