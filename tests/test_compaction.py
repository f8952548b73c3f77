"""Trigger-gated lane compaction for the sparse decide (DESIGN.md §18).

The contract under test: compaction is **output-invisible**.  The
compacted decide memoizes each lane's exact decide inputs and replays
the cached outputs while they are bitwise unchanged (and the lane is not
overloaded); because the decide is a pure function of those inputs, the
replay is provably bit-identical to repricing — so every surface except
the ``repriced`` diagnostic must match the dense run bit for bit:

* the standalone jit decide (``make_decide_jax(compact=...)``) across
  cold / quiet / partially-triggered ticks at swept trigger fractions;

Decisions and allocations (codes, k, applied, every integer aggregate)
are compared **bitwise**.  The ``et_cur``/``et_target`` diagnostics get
the same ~1-ulp rtol the mesh tests use: XLA reassociates the per-lane
``N`` reductions differently at different batch extents, and a compacted
rung IS a different batch extent — the same program property the
sharded/unsharded comparison already tolerates (tests/test_mesh_control.py).
* the whole fused loop over the 32-scenario mixed zoo (the arrival-trace
  mix is the trigger-rate sweep: Poisson-sampled lanes reprice every
  window, deterministic constant lanes go quiet);
* the float64 twin (``tick_batch`` with a :class:`TwinCompactionState`),
  reactive and proactive;
* every committed golden fixture replayed with compaction on.
"""

import json
import math
import pathlib

import numpy as np
import pytest

import repro.core.controller as ctl
from repro.api.session import ScenarioRunner
from repro.core.scheduler import SchedulerConfig
from repro.distributed.sharding import bucket_ladder
from repro.streaming.scenarios import control_trace, scenario_matrix

GOLDEN = pathlib.Path(__file__).resolve().parent / "golden"


def _scens(b, seed=11, horizon=20.0):
    return [
        s.with_(negotiated=False)
        for s in scenario_matrix(b, seed=seed, horizon=horizon, warmup=5.0, dt=0.05)
    ]


def _decide_inputs(static, seed=0, k_fill=2):
    b, n = static.batch, static.n
    rng = np.random.default_rng(seed)
    lam = np.abs(rng.normal(2.0, 0.5, (b, n)))
    mu = np.abs(rng.normal(6.0, 0.5, (b, n))) + 1.0
    drop = np.zeros((b, n))
    lam0 = np.abs(rng.normal(2.0, 0.5, b))
    k = np.where(static.active, k_fill, 0).astype(np.int64)
    return lam, mu, drop, lam0, k


def _assert_decide_match(want, got):
    """(code, k_next, et_cur, et_target, applied): decisions bitwise,
    E[T] diagnostics to the mesh tests' reduction-order rtol."""
    for i in (0, 1, 4):
        np.testing.assert_array_equal(
            np.asarray(want[i]), np.asarray(got[i]), err_msg=f"out[{i}]"
        )
    for i in (2, 3):
        np.testing.assert_allclose(
            np.asarray(want[i]), np.asarray(got[i]), rtol=1e-6,
            err_msg=f"out[{i}]",
        )


def _eq_nan(a, b):
    """Recursive equality where NaN == NaN (JSON traces carry NaN rates)."""
    if isinstance(a, float) and isinstance(b, float):
        return a == b or (math.isnan(a) and math.isnan(b))
    if isinstance(a, dict):
        return set(a) == set(b) and all(_eq_nan(a[k], b[k]) for k in a)
    if isinstance(a, (list, tuple)):
        return len(a) == len(b) and all(_eq_nan(x, y) for x, y in zip(a, b))
    return a == b


# --------------------------------------------------------------------------- #
# The static bucket ladder
# --------------------------------------------------------------------------- #
def test_bucket_ladder_shape():
    assert bucket_ladder(4096) == (256, 1024, 4096)
    assert bucket_ladder(10_000) == (625, 2500, 10_000)
    # the dense rung is always present, tiny extents collapse onto it
    assert bucket_ladder(1) == (1,)
    assert bucket_ladder(3) == (1, 3)
    assert bucket_ladder(7, fractions=(2,)) == (4, 7)
    with pytest.raises(ValueError):
        bucket_ladder(0)
    for b in (1, 5, 16, 100, 4096):
        ladder = bucket_ladder(b)
        assert ladder[-1] == b
        assert all(w1 < w2 for w1, w2 in zip(ladder, ladder[1:]))


# --------------------------------------------------------------------------- #
# Standalone compacted decide: trigger semantics + bit identity
# --------------------------------------------------------------------------- #
def test_compacted_decide_bit_identity_swept_trigger_fractions():
    import jax

    with jax.enable_x64(True):
        scens = _scens(32)
        r = ScenarioRunner(scens, tick_interval=5.0, backend="jax")
        st, pr = r.static, r._params()
        lam, mu, drop, lam0, k = _decide_inputs(st)
        dense = ctl.make_decide_jax(st, pr)
        comp = ctl.make_decide_jax(st, pr, compact=True)
        cache = comp.init_cache()

        def check(lam_t):
            want = dense(lam_t, mu, drop, lam0, k)
            nonlocal cache
            got, repriced, cache = comp(lam_t, mu, drop, lam0, k, cache)
            _assert_decide_match(want, got)
            return int(np.asarray(repriced).sum())

        assert check(lam) == 32  # cold cache: every lane reprices
        assert check(lam) == 0  # unchanged inputs: every lane replays
        for frac in (0.05, 0.25, 0.5, 1.0):
            n_trig = max(int(round(frac * 32)), 1)
            lam2 = lam.copy()
            lam2[:n_trig] *= 1.0 + 0.01 * frac
            assert check(lam2) == n_trig  # exactly the changed lanes
            assert check(lam2) == 0  # ...and they memoize right back


def test_compacted_decide_k_and_custom_ladder_and_nan():
    import jax

    with jax.enable_x64(True):
        scens = _scens(8)
        r = ScenarioRunner(scens, tick_interval=5.0, backend="jax")
        st, pr = r.static, r._params()
        lam, mu, drop, lam0, k = _decide_inputs(st)
        dense = ctl.make_decide_jax(st, pr)
        comp = ctl.make_decide_jax(
            st, pr, compact=ctl.CompactionConfig(b_active_cap=(2, 8))
        )
        cache = comp.init_cache()

        def step(lam_t, k_t):
            want = dense(lam_t, mu, drop, lam0, k_t)
            nonlocal cache
            got, repriced, cache = comp(lam_t, mu, drop, lam0, k_t, cache)
            _assert_decide_match(want, got)
            return int(np.asarray(repriced).sum())

        step(lam, k)
        assert step(lam, k) == 0
        # a k change triggers exactly like a rate change
        k2 = k.copy()
        k2[1, 0] += 1
        assert step(lam, k2) == 1
        # NaN rates (idle windows) memoize too: NaN == NaN in the trigger
        # compare, so a persistently-idle lane goes quiet instead of
        # repricing every tick on NaN != NaN
        lam3 = lam.copy()
        lam3[2] = np.nan
        assert step(lam3, k2) == 1
        assert step(lam3, k2) == 0


# --------------------------------------------------------------------------- #
# The fused loop over the mixed zoo (property test)
# --------------------------------------------------------------------------- #
# Bitwise-equal fused-loop surfaces vs rtol'd E[T] diagnostics (mirrors
# tests/test_mesh_control.py).  ``sojourn`` stays EXACT: it is computed
# from the (never-compacted) simulate windows, and the k feeding them is
# asserted exact.
EXACT = (
    "codes", "k", "applied", "miss", "warm_windows", "k_final", "q_final",
    "offered", "served", "dropped", "ext_admitted", "ext_offered",
    "q_int", "q_max", "sojourn",
)
CLOSE = ("et_cur", "et_target")


def _assert_loop_match(ref, got, extra_exact=()):
    for key in EXACT + tuple(extra_exact):
        np.testing.assert_array_equal(ref[key], got[key], err_msg=key)
    for key in CLOSE:
        np.testing.assert_allclose(ref[key], got[key], rtol=1e-6, err_msg=key)


def _fused_out(scens, compact, **kw):
    import jax

    with jax.enable_x64(True):
        r = ScenarioRunner(scens, tick_interval=5.0, backend="jax",
                           compact=compact, **kw)
        assert r.fused
        run, _ = ctl.make_fused_loop(
            r.arrays, r.static, r._params(),
            steps_per_tick=r._steps_per_tick,
            warmup_seconds=scens[0].warmup,
            proactive=r.proactive_cfg, compact=r.compact,
        )
        return {key: np.asarray(v) for key, v in run(r.k).items()}


@pytest.mark.parametrize("seed", [11, 29])
def test_fused_loop_zoo_compact_bit_identity(seed):
    """The 32-scenario mixed zoo: every decision/measurement surface of
    the compacted fused loop is bitwise equal to the dense loop.  The
    zoo's trace mix is the trigger-rate sweep — Poisson lanes retrigger
    every window, constant/deterministic lanes go quiet."""
    scens = _scens(32, seed=seed)
    ref = _fused_out(scens, None)
    got = _fused_out(scens, True)
    assert "repriced" not in ref and "repriced" in got
    _assert_loop_match(ref, got)


def test_fused_loop_zoo_compact_proactive_bit_identity():
    scens = _scens(16)
    ref = _fused_out(scens, None, proactive=True)
    got = _fused_out(scens, True, proactive=True)
    _assert_loop_match(ref, got, extra_exact=("mpc_used", "confident"))


def test_fused_loop_quiet_lanes_skip_repricing():
    """Deterministic-arrival constant-trace lanes present bitwise
    identical measurements once the transient drains — the trigger must
    stop repricing them (this is the perf claim the bench quantifies;
    Poisson lanes in the same batch keep repricing every window)."""
    from dataclasses import replace

    scens = [
        replace(s.with_(negotiated=False), arrival_kind="deterministic")
        for s in scenario_matrix(8, seed=11, horizon=40.0, warmup=5.0, dt=0.05)
        if "constant" in s.name
    ]
    assert scens, "the matrix zoo lost its constant-trace scenarios"
    ref = _fused_out(scens, None)
    got = _fused_out(scens, True)
    _assert_loop_match(ref, got)
    repriced = got["repriced"]
    assert repriced[0].all()  # cold cache prices densely
    # after the transient the constant lanes are bitwise quiet
    assert not repriced[-1].any(), repriced
    assert repriced.sum() < repriced.size


# --------------------------------------------------------------------------- #
# The float64 twin
# --------------------------------------------------------------------------- #
def test_twin_tick_batch_compact_trace_identical():
    scens = _scens(32)
    ref = control_trace(scens, tick_interval=5.0)
    got = control_trace(scens, tick_interval=5.0, compact=True)
    assert _eq_nan(ref, got)


def test_twin_tick_batch_compact_proactive_trace_identical():
    scens = _scens(8)
    ref = control_trace(scens, tick_interval=5.0, proactive=True)
    got = control_trace(scens, tick_interval=5.0, proactive=True, compact=True)
    assert _eq_nan(ref, got)


def test_twin_compaction_state_replays():
    """The twin's memo actually engages on repeated identical windows
    (same lam/mu/k -> replayed row), and a replayed row is a fresh copy —
    mutating the caller's k must not corrupt the cache."""
    scens = _scens(6)
    r = ScenarioRunner(scens, tick_interval=5.0, backend="numpy", fused=False)
    cstate = ctl.TwinCompactionState.create(len(scens), r.static.n)
    from repro.core.measurer import MeasurementBatch

    lam, mu, drop, lam0, k = _decide_inputs(r.static)
    meas = MeasurementBatch.from_rates(
        lam, mu, lam0, np.full(len(scens), 0.2), 0.0, drop_hat=drop
    )
    out1 = ctl.tick_batch(meas, k, r.static, r._params(), compact_state=cstate)
    assert not cstate.replayed.any()  # cold: every lane priced
    out2 = ctl.tick_batch(meas, k, r.static, r._params(), compact_state=cstate)
    assert cstate.replayed.all()  # identical window: every lane replayed
    for r1, r2 in zip(out1.rows, out2.rows):
        assert r1.action == r2.action
        np.testing.assert_array_equal(r1.k_next, r2.k_next)
    out2.rows[0].k_next[:] = -7  # caller mutation must not reach the cache
    out3 = ctl.tick_batch(meas, k, r.static, r._params(), compact_state=cstate)
    assert (out3.rows[0].k_next >= 0).all()


# --------------------------------------------------------------------------- #
# Goldens replay with compaction on
# --------------------------------------------------------------------------- #
def _golden_entries():
    import importlib.util

    spec = importlib.util.spec_from_file_location("golden_regen", GOLDEN / "regen.py")
    regen = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(regen)
    return {name: (s, pro, tick) for name, s, pro, tick in regen.entries()}


@pytest.mark.parametrize("name", ["vld", "fpd", "vld_proactive", "vld_fused",
                                  "soak"])
def test_golden_trace_replays_with_compaction(name):
    """Compaction is output-invisible: every committed golden fixture
    replays bit-for-bit with the sparse decide ON (twin path)."""
    want = json.loads((GOLDEN / f"{name}_control_trace.json").read_text())
    scenario, proactive, _tick = _golden_entries()[name]
    got = control_trace(
        [scenario], tick_interval=want["tick_interval"], proactive=proactive,
        compact=True,
    )
    w, g = want["scenarios"][name], got["scenarios"][name]
    assert g["actions"] == w["actions"], (
        f"{name} drifted under compaction — the sparse decide changed a "
        "decision, which the §18 exactness contract forbids"
    )
    assert g["allocations"] == w["allocations"]
    assert g["trajectory"] == w["trajectory"]
    for metric in ("drop_rate", "mean_sojourn", "deadline_miss_rate"):
        assert g[metric] == pytest.approx(w[metric], rel=1e-6, abs=1e-9), metric


def test_golden_fused_replays_through_compacted_jit_loop():
    """The jit-eligible golden through the fused jax loop with compaction
    on — pins twin == dense jit == compacted jit on the golden surface."""
    want = json.loads((GOLDEN / "vld_fused_control_trace.json").read_text())
    scenario, proactive, _tick = _golden_entries()["vld_fused"]
    got = control_trace(
        [scenario], tick_interval=want["tick_interval"], proactive=proactive,
        backend="jax", compact=True,
    )
    w, g = want["scenarios"]["vld_fused"], got["scenarios"]["vld_fused"]
    assert g["actions"] == w["actions"]
    assert g["allocations"] == w["allocations"]
    for key in ("k_total", "miss", "warm"):
        assert g["trajectory"][key] == w["trajectory"][key], key


# --------------------------------------------------------------------------- #
# Satellites
# --------------------------------------------------------------------------- #
def test_stack_mixed_fused_decide_error_names_indices():
    configs = [
        SchedulerConfig(k_max=4, fused_decide=(i in (1, 3))) for i in range(5)
    ]
    with pytest.raises(ValueError) as ei:
        ctl.ControllerParams.stack(configs, [4] * 5)
    msg = str(ei.value)
    assert "[1, 3]" in msg and "[0, 2, 4]" in msg


def test_bench_provenance_fields():
    from benchmarks.run import provenance

    p = provenance()
    assert set(p) == {"git_sha", "jax_version", "backend"}
    assert len(p["git_sha"]) == 40 or p["git_sha"] == "unknown"
    assert p["jax_version"] and p["backend"]


def test_mpc_plan_compact_empty_and_subset():
    """Unit check of the eligible-lane MPC gather: no eligible lanes ->
    carry-shaped defaults without calling the planner; a subset matches
    the dense plan on exactly that subset."""
    from repro.forecast.mpc import MPCConfig, mpc_plan, mpc_plan_compact

    b, n, h = 4, 3, 3
    rng = np.random.default_rng(5)
    lam_pred = np.abs(rng.normal(3.0, 0.5, (b, h, n)))
    q0 = np.abs(rng.normal(1.0, 0.3, (b, n)))
    k_cur = np.full((b, n), 2, dtype=np.int64)
    k_max = np.full(b, 12, dtype=np.int64)
    mu = np.abs(rng.normal(6.0, 0.5, (b, n))) + 1.0
    src_mask = np.zeros((b, n), dtype=bool)
    src_mask[:, 0] = True
    kw = dict(
        mu=mu, group=np.zeros((b, n), dtype=bool), alpha=np.zeros((b, n)),
        speed=np.ones((b, n)), active=np.ones((b, n), dtype=bool),
        src_mask=src_mask, cap_queue=np.full((b, n), np.inf),
        t_max=np.full(b, 2.0), span=5.0, cfg=MPCConfig(horizon=h),
        k_hi=16, xp=np,
    )
    dense = mpc_plan(lam_pred, q0, k_cur, k_max=k_max, **kw)
    eligible = np.array([True, False, True, False])
    got = mpc_plan_compact(eligible, lam_pred, q0, k_cur, k_max=k_max, **kw)
    for di, gi in zip(dense, got):
        np.testing.assert_array_equal(
            np.asarray(di)[eligible], np.asarray(gi)[eligible]
        )
    none = mpc_plan_compact(
        np.zeros(b, dtype=bool), lam_pred, q0, k_cur, k_max=k_max, **kw
    )
    assert not np.asarray(none[1]).any()  # any_ok all False
    np.testing.assert_array_equal(none[0], k_cur.astype(np.int32))
