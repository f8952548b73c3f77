"""Keyed operators (DESIGN.md §20): k hash partitions of a keyed stream,
each M/M/1, with the hot key's share ``h`` of the input on one of them.

* the closed form and its least stable k, against their definitions;
* the numpy table bit for bit against the scalar model, the jnp table
  against the numpy one;
* the jit decide (dense and compacted) against the float64 numpy twin on
  seeded NEXmark-shaped graphs with random hot shares: codes,
  allocations, E[T] and the ``hot_floor`` counter;
* a graph with no keyed operator lowers to the same program either way;
* the paths without per-partition queues refuse keyed graphs;
* the DES: keyed partitions fed by a hashed key stream against the closed
  form, and the pooled floor's hot partition running away.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import numpy as np
import pytest

import repro.core.controller as ctl
from repro.api import AppGraph, Edge, GraphValidationError, OpDef
from repro.core import OperatorSpec, Topology, UnstableTopologyError, stages
from repro.core.batched import sojourn_table, sojourn_table_jax
from repro.core.erlang import expected_sojourn, keyed_sojourn, min_stable_k
from repro.core.measurer import MeasurementBatch
from repro.core.rebalance import RebalanceCostModel
from repro.streaming.des import simulate_allocation

B, K_MAX, PAUSE = 64, 44, 1e-4
# parse, q5_window, q5_max, q7_max, q7_join, q8_join, sink (the NEXmark
# Q5/Q7/Q8 job of chipbench/configs/drs-nexmark-fleet.json).
NAMES = ("parse", "q5_window", "q5_max", "q7_max", "q7_join", "q8_join", "sink")
MU = (2000.0, 12000.0, 5000.0, 5000.0, 2000.0, 1200.0, 5000.0)
KEYED = (False, True, True, False, True, True, False)
EDGES = (("parse", "q5_window", 0.92), ("parse", "q7_max", 0.92), ("parse", "q7_join", 0.92),
         ("parse", "q8_join", 0.08), ("q5_window", "q5_max", 0.01),
         ("q7_max", "q7_join", 0.001), ("q5_max", "sink", 0.05),
         ("q7_join", "sink", 0.001), ("q8_join", "sink", 0.25))


def nexmark(hot, mu=MU, rate=10000.0):
    ops = [OpDef(n, mu=m, scaling="keyed" if kd else "replica", hot_share=h if kd else None)
           for n, m, kd, h in zip(NAMES, mu, KEYED, hot)]
    return AppGraph(ops, [Edge(*e) for e in EDGES], {"parse": rate})


def _fleet(seed):
    """B NEXmark-shaped lanes with random hot shares and service rates."""
    rng = np.random.default_rng(seed)
    graphs = [nexmark(rng.uniform(0.0, 1.0, 7), tuple(np.asarray(MU) * rng.uniform(0.8, 1.2, 7)))
              for _ in range(B)]
    return graphs, ctl.ControllerStatic.from_graphs(graphs), _params(B)


def _params(b):
    full = lambda v: np.full(b, v)
    return ctl.ControllerParams(
        t_max=full(np.nan), k_max=np.full(b, K_MAX, np.int64), headroom=full(1.1),
        scale_in_hysteresis=full(0.8), min_improvement=full(0.05),
        horizon_seconds=full(300.0), allocator=("table",) * b,
    )


def _snapshot(graphs, static, seed):
    """A fresh 5 s Poisson snapshot per lane at a rate uniform on [1000,
    24000] events/s under an allocation near the pooled need (so keyed
    operators' hot partitions may overrun it); drops past each operator's
    capacity (a keyed one's at its hot partition)."""
    rng = np.random.default_rng(seed)
    mu = np.array([[op.mu for op in g.ops] for g in graphs])
    rate = rng.uniform(1000.0, 24000.0, B)
    offered = np.stack([g.with_sources({"parse": r}).topology().arrival_rates
                        for g, r in zip(graphs, rate)])
    k = np.maximum(np.ceil(offered / mu) + rng.integers(-1, 4, (B, 7)), 1).astype(np.int64)
    lam = rng.poisson(offered * 5.0) / 5.0
    cap = ctl.effective_capacity(k, mu, static.group, static.alpha, static.hot)
    drop = np.maximum(lam - cap, 0.0)
    return lam, mu, drop, lam[:, 0] - drop[:, 0], k


def _hot_floor_reference(static, lam_hat, mu, drop, lam0, k):
    """Per lane: keyed operators whose least stable k (the float64 table's
    first finite column) lies above the pooled floor floor(lam/mu) + 1."""
    over = ctl.overloaded_mask_batch(lam_hat, mu, drop, k, static.group, static.alpha,
                                     static.hot)
    capped = ctl.capped_mask_batch(over, static.base_routing, static.active)
    out = np.zeros(B, np.int64)
    for b in range(B):
        top = ctl.clamp_row(NAMES, static.base_routing[b], lam_hat[b], mu[b], lam0[b],
                            over[b], capped[b], ["keyed" if kd else "replica" for kd in KEYED],
                            static.alpha[b], hot_share=static.hot[b])
        table = sojourn_table(top, K_MAX)
        finite = np.isfinite(table)
        first = np.where(finite.any(axis=-1), finite.argmax(axis=-1), K_MAX + 1)
        lam = top.arrival_rates
        pooled = np.floor(lam / mu[b]) + 1
        out[b] = int((np.asarray(KEYED) & (first > pooled)).sum())
    return out


# --------------------------------------------------------------------------- #
# The closed form
# --------------------------------------------------------------------------- #
def test_opdef_validates_hot_share():
    with pytest.raises(GraphValidationError, match="hot_share in"):
        AppGraph([OpDef("a", mu=1.0, scaling="keyed")])
    for h in (-0.1, 1.5, math.nan):
        with pytest.raises(GraphValidationError, match="hot_share in"):
            AppGraph([OpDef("a", mu=1.0, scaling="keyed", hot_share=h)])
    with pytest.raises(GraphValidationError, match="keyed scaling only"):
        AppGraph([OpDef("a", mu=1.0, hot_share=0.5)])
    g = AppGraph([OpDef("a", mu=1.0, scaling="keyed", hot_share=1.0), OpDef("b", mu=1.0)])
    np.testing.assert_array_equal(g.hot_shares(), [1.0, np.nan])
    assert g.topology().operators[0].hot_share == 1.0


@pytest.mark.parametrize("lam,mu", [(3.0, 2.0), (0.5, 4.0), (17.0, 5.0)])
def test_keyed_sojourn_limits(lam, mu):
    # k = 1 is one M/M/1 queue whatever h; h = 1 is one M/M/1 queue at any
    # k; h = 0 hashes evenly: k M/M/1 queues at lam / k.
    for h in (0.0, 0.3, 1.0):
        assert keyed_sojourn(1, lam, mu, h) == pytest.approx(expected_sojourn(1, lam, mu))
    for k in range(1, 9):
        assert keyed_sojourn(k, lam, mu, 1.0) == pytest.approx(expected_sojourn(1, lam, mu))
        want = 1.0 / (mu - lam / k) if lam / k < mu else math.inf
        assert keyed_sojourn(k, lam, mu, 0.0) == pytest.approx(want)
    assert keyed_sojourn(0, lam, mu, 0.5) == math.inf


def test_min_stable_k_keyed():
    rng = np.random.default_rng(7)
    for _ in range(500):
        lam, mu, h = rng.uniform(0.0, 50.0), rng.uniform(0.5, 10.0), rng.uniform(0.0, 1.0)
        if not lam * h < mu:
            with pytest.raises(ValueError, match="hot key"):
                min_stable_k(lam, mu, h)
            with pytest.raises(UnstableTopologyError, match="hot key"):
                OperatorSpec("op", mu, scaling="keyed", hot_share=h).min_feasible_k(lam)
            continue
        k = min_stable_k(lam, mu, h)
        assert math.isfinite(keyed_sojourn(k, lam, mu, h))
        assert k == 1 or not math.isfinite(keyed_sojourn(k - 1, lam, mu, h))
        assert k >= min_stable_k(lam, mu)  # never below the pooled floor
    # The hot key alone sets the floor: 18400 events/s at mu 12000, h 0.5.
    assert (min_stable_k(18400.0, 12000.0), min_stable_k(18400.0, 12000.0, 0.5)) == (2, 4)


def test_tables_match_the_scalar_model():
    rng = np.random.default_rng(3)
    ops = [OperatorSpec(f"o{i}", mu=rng.uniform(1.0, 6.0), scaling="keyed",
                        hot_share=rng.uniform(0.0, 1.0), min_k=int(rng.integers(1, 3)))
           for i in range(6)] + [OperatorSpec("r", mu=3.0), OperatorSpec("g", mu=3.0,
                                                                         scaling="group",
                                                                         group_alpha=0.1)]
    top = Topology(ops, rng.uniform(1.0, 9.0, 8), np.zeros((8, 8)))
    table = sojourn_table(top, 30)
    lam = top.arrival_rates
    want = np.array([[op.sojourn(k, lam[i]) for k in range(31)] for i, op in enumerate(ops)])
    np.testing.assert_array_equal(table, want)  # bit for bit
    for i, op in enumerate(ops):
        finite = np.isfinite(table[i])
        if finite.any():
            assert op.min_feasible_k(lam[i]) == int(finite.argmax())
    hot = np.array([op.hot_share if op.scaling == "keyed" else np.nan for op in ops])
    with jax.enable_x64(True):
        got = np.asarray(sojourn_table_jax(
            lam, np.array([op.mu for op in ops]), k_hi=30,
            group=np.array([op.scaling == "group" for op in ops]),
            alpha=np.array([op.group_alpha for op in ops]),
            min_k=np.array([op.min_k for op in ops]), hot=hot))
    np.testing.assert_allclose(got, table, rtol=1e-12)
    np.testing.assert_array_equal(np.isfinite(got), np.isfinite(table))


# --------------------------------------------------------------------------- #
# The jit decide against the float64 twin
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("seed", [0, 1])
def test_decide_matches_float64_twin(seed):
    """Two ticks on one snapshot: the first from an allocation near the
    pooled need (hot partitions overrun), the second from the first's."""
    graphs, static, params = _fleet(seed)
    lam, mu, drop, lam0, k = _snapshot(graphs, static, seed + 100)
    with jax.enable_x64(True):
        dense = ctl.make_decide_jax(static, params, pause_seconds=PAUSE)
        comp = ctl.make_decide_jax(static, params, pause_seconds=PAUSE, compact=True)
        cache = comp.init_cache()
        codes = []
        for _ in range(2):
            # A pause short enough that the cost gate opens at sub-ms E[T].
            rows = ctl.tick_batch(
                MeasurementBatch(lam, mu, lam0, np.full(B, np.nan), 0.0, drop), k, static,
                params, cost_models=[RebalanceCostModel(pause_cache_miss=PAUSE)] * B,
            ).rows
            want_floor = _hot_floor_reference(static, lam, mu, drop, lam0, k)
            assert want_floor.sum() > 0
            got, repriced, cache = comp(lam, mu, drop, lam0, k, cache)
            for out in (dense(lam, mu, drop, lam0, k), got):
                code, k_next, et_cur, et_target, applied, hot_floor = map(np.asarray, out)
                np.testing.assert_array_equal(code, [r.code for r in rows])
                np.testing.assert_array_equal(k_next, np.stack([r.k_next for r in rows]))
                np.testing.assert_array_equal(applied, [r.applied for r in rows])
                np.testing.assert_array_equal(hot_floor, want_floor)
                for b, r in enumerate(rows):
                    if math.isfinite(r.et_cur):
                        assert et_cur[b] == pytest.approx(r.et_cur, rel=1e-9)
                    if r.et_target is not None and math.isfinite(r.et_target):
                        assert et_target[b] == pytest.approx(r.et_target, rel=1e-9)
            codes += [r.code for r in rows]
            k_last, k = k, k_next
        # The last tick's inputs again: only lanes the keyed trigger holds
        # hot reprice.
        _, repriced, _ = comp(lam, mu, drop, lam0, k_last, cache)
        np.testing.assert_array_equal(repriced, code == ctl._CODE["overloaded"])
    assert {0, 1, 5} <= set(codes), codes  # none, rebalance, overloaded


def test_graph_without_keyed_operator_lowers_the_same():
    plain = AppGraph([OpDef(n, mu=m) for n, m in zip(NAMES, MU)], [Edge(*e) for e in EDGES],
                     {"parse": 5000.0})
    static = ctl.ControllerStatic.from_graphs([plain] * 4)
    assert static.hot is not None and not static.keyed
    bare = dataclasses.replace(static, hot=None)
    params = _params(4)
    args = (np.ones((4, 7)), np.ones((4, 7)), np.zeros((4, 7)), np.ones(4),
            np.ones((4, 7), np.int32))
    text = []
    for st in (static, bare):
        for compact in (None, True):
            stages.clear()
            decide = ctl.make_decide_jax(st, params, compact=compact)
            decide(*args, *((decide.init_cache(),) if compact else ()))
            (program, shapes, _), = stages._programs
            text.append(program.lower(*shapes).as_text())
    stages.clear()
    assert text[0] == text[2] and text[1] == text[3]


def test_paths_without_partitions_refuse_keyed_graphs():
    from repro.streaming.batchsim import BatchArrays
    from repro.streaming.scenarios import Scenario, pack_scenarios, vld_scenario

    graphs, static, params = _fleet(0)
    with pytest.raises(ValueError, match="decide_fused has no keyed"):
        ctl.make_decide_jax(static, params, fused=True)
    arrays = BatchArrays(
        ext=np.zeros((4, B, 7)), routing=static.base_routing, mu=np.ones((B, 7)),
        group=static.group, alpha=static.alpha, cap_queue=np.full((B, 7), np.inf), dt=1.0,
        warmup_steps=0, active=static.active,
    )
    with pytest.raises(ValueError, match="no per-partition queues"):
        ctl.make_fused_loop(arrays, static, params, steps_per_tick=2)
    scen = vld_scenario(negotiated=False)
    assert isinstance(scen, Scenario)
    keyed = AppGraph([OpDef(op.name, mu=op.mu, scaling="keyed", hot_share=0.5)
                      for op in scen.graph.ops], scen.graph.edges,
                     dict(zip(scen.graph.names, scen.graph.lam0_vector())))
    with pytest.raises(ValueError, match="no per-partition queues"):
        pack_scenarios([scen.with_(graph=keyed)])


# --------------------------------------------------------------------------- #
# The DES: partitions fed by a hashed key stream
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("lam,mu,h,k", [(2.0, 2.0, 0.3, 4), (2.0, 2.0, 0.0, 3)])
def test_des_keyed_partitions_match_closed_form(lam, mu, h, k):
    """One keyed operator, 40000 tuples.  Over 16 seeds of (2, 2, 0.3, 4)
    the run's mean sojourn read 0.04 % above the closed form with a
    spread of 1.1 %: 5 % is over four spreads, far below what a wrong
    partition share would move (pooling the same four processors, M/M/4,
    reads 0.50 s against the keyed 0.66 s)."""
    top = Topology([OperatorSpec("op", mu, scaling="keyed", hot_share=h)],
                   np.array([lam]), np.zeros((1, 1)))
    res = simulate_allocation(top, [k], seed=5, horizon=20000.0, warmup=100.0)
    assert res.completed > 35000
    assert res.mean_visit_sum == pytest.approx(keyed_sojourn(k, lam, mu, h), rel=0.05)


def test_des_pooled_floor_overruns_the_hot_partition():
    """q5_window at the top rate, scaled by 1e-4: 1.84 events/s, mu 1.2,
    h 0.5.  The pooled M/M/k floor (2) leaves the hot partition at 1.38 >
    1.2, so its backlog grows all run; the keyed floor (4) holds it at
    1.15 < 1.2."""
    top = Topology([OperatorSpec("q5_window", 1.2, scaling="keyed", hot_share=0.5)],
                   np.array([1.84]), np.zeros((1, 1)))
    pooled, keyed = min_stable_k(1.84, 1.2), min_stable_k(1.84, 1.2, 0.5)
    assert (pooled, keyed) == (2, 4)
    runs = {k: simulate_allocation(top, [k], seed=1, horizon=4000.0, warmup=100.0)
            for k in (pooled, keyed)}
    # Unstable: about (1.38 - 1.2) x 4000 s = 720 tuples queued by the end.
    assert runs[pooled].per_op_max_backlog[0] > 400
    assert runs[keyed].per_op_max_backlog[0] < 100
    assert runs[pooled].mean_visit_sum > 5 * keyed_sojourn(keyed, 1.84, 1.2, 0.5)
