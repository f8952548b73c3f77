"""Batched control-plane benchmark: fused jit decide vs the per-scenario
Python loop (DESIGN.md §14).

The claim: extracting the decision math out of ``DRSScheduler`` into the
batched controller turns B independent measure -> model -> rebalance
loops from B Python interpreter walks per tick (tables, greedy, gates,
object plumbing — the per-query scheduling overhead model-driven
schedulers exist to amortize) into ONE compiled program over ``[B, N]``
arrays.  Rows:

* ``decide_scalar_seconds_B{B}`` — wall-clock for one control tick driven
  through B per-scenario ``DRSScheduler.tick_from`` calls (the PR-4
  ScenarioRunner structure);
* ``decide_jit_seconds_B{B}`` — the same B decisions through the jit
  ``make_decide_jax`` program (post-compile, per-call mean);
* ``speedup_fused_vs_scalar_B64`` — the acceptance gate: >= 20x at B=64;
* ``fused_loop_ticks_per_second_B{B}`` — whole fused simulate -> measure
  -> decide -> apply scan throughput (ticks/s across the batch);
* ``fused_loop_sharded_ticks_per_second_B{B}_D{D}`` — the mesh story
  (DESIGN.md §16): the same fused loop at fleet scale (B=4096 full run,
  B=64 smoke) with the batch axis sharded over the D visible devices, in
  this process (on a CPU host, emulate devices with ``XLA_FLAGS=
  --xla_force_host_platform_device_count=D`` set before jax imports;
  with one visible device these four rows are left out).
  A ``_pinned_..._D1`` twin row runs the identical shard_map program on
  a 1-device mesh; ``sharded_vs_pinned_ratio`` reports the
  device-parallel speedup (only meaningful when the host has cores to
  back emulated devices — the note records the core count);
* ``gain_topr_interpret_parity`` — Pallas top-R kernel vs jnp oracle in
  interpret mode on CPU (1.0 = exact take-for-take agreement);
* ``decide_dense_ticks_per_second_B{B}`` /
  ``decide_compacted_ticks_per_second_B{B}_trig{F}pct`` — the §18
  trigger-gated sparse decide vs the dense decide on a diurnal-zoo
  static stack tiled to fleet extent (B=4096 full / B=256 smoke, plus a
  B=10000 full-run row), with the trigger rate pinned by perturbing
  exactly ``F%`` of the lanes' inputs per tick.  Every compacted tick's
  decisions are asserted **bitwise identical** to the dense decide
  before it is timed (hard fail, smoke included; E[T] diagnostics to the
  mesh tests' ~1-ulp rtol); ``compacted_vs_dense_speedup_B4096_
  trig10pct`` is the acceptance gate (>= 3x, full runs only — smoke
  extents are too small for the ladder to pay);
* ``compacted_peak_live_bytes_B{B}`` — device-reported peak live bytes
  after the compacted sweep via ``jax.local_devices()[0].memory_stats()``
  (``-1.0`` on CPU hosts, which report no allocator stats).
"""

from __future__ import annotations

import os
import sys
import time

import numpy as np

from repro.api.session import ScenarioRunner
from repro.core import controller as ctl
from repro.core.measurer import MeasurementSnapshot
from repro.core.scheduler import DRSScheduler, SchedulerConfig


def _scalar_schedulers(runner: ScenarioRunner):
    """The pre-extraction structure: one DRSScheduler object per scenario."""
    scheds = []
    for bi, s in enumerate(runner.scenarios):
        scaling, group_alpha = s.graph.scaling_lists()
        scheds.append(DRSScheduler(
            s.graph.names,
            s.graph.routing_matrix(),
            runner.k[bi, : s.graph.n].copy(),
            SchedulerConfig(k_max=s.k_max, t_max=s.t_max, allocator=s.allocator),
            scaling=scaling,
            group_alpha=group_alpha,
        ))
    return scheds


def run(smoke: bool = False) -> list[tuple[str, float, str]]:
    import jax
    import jax.numpy as jnp

    rows: list[tuple[str, float, str]] = []
    b = 16 if smoke else 64
    reps = 3 if smoke else 10
    horizon = 30.0
    from repro.streaming.scenarios import scenario_matrix

    scens = [
        s.with_(negotiated=False)
        for s in scenario_matrix(b, seed=5, horizon=horizon, warmup=5.0, dt=0.05)
    ]
    runner = ScenarioRunner(scens, tick_interval=5.0, backend="numpy", fused=False)
    # One real simulated window -> the measurement both paths decide on.
    w = runner.sim.step_window(runner.k, runner._steps_per_tick)
    meas, _ = runner._window_measurement(w)
    rows.append(("controller_scenarios", float(b), f"scenarios, N={runner.arrays.n}"))

    # --- per-scenario Python loop (the PR-4 structure) ------------------- #
    scheds = _scalar_schedulers(runner)
    k0 = runner.k.copy()
    snaps = [
        MeasurementSnapshot.from_rates(
            meas.lam_hat[bi, : s.graph.n], meas.mu_hat[bi, : s.graph.n],
            float(meas.lam0_hat[bi]), float(meas.sojourn_hat[bi]), 0.0,
            drop_hat=meas.drop_hat[bi, : s.graph.n],
        )
        for bi, s in enumerate(runner.scenarios)
    ]
    from repro.core.allocator import InsufficientResourcesError
    from repro.core.jackson import UnstableTopologyError

    t_scalar = []
    for _ in range(reps):
        for bi, sched in enumerate(scheds):
            sched.k_current = k0[bi, : len(sched.names)].copy()
        t0 = time.perf_counter()
        for bi, sched in enumerate(scheds):
            try:
                sched.tick_from(snaps[bi], 0.0)
            except (InsufficientResourcesError, UnstableTopologyError):
                pass  # the runner's infeasible row (PR-4 semantics)
        t_scalar.append(time.perf_counter() - t0)
    scalar_s = float(np.median(t_scalar))
    rows.append((f"decide_scalar_seconds_B{b}", scalar_s,
                 "s per tick, B per-scenario DRSScheduler.tick_from"))

    # --- fused jit batch decide ------------------------------------------ #
    decide = ctl.make_decide_jax(runner.static, runner._params())
    args = (
        jnp.asarray(meas.lam_hat), jnp.asarray(meas.mu_hat),
        jnp.asarray(meas.drop_hat), jnp.asarray(meas.lam0_hat),
        jnp.asarray(k0),
    )
    out = decide(*args)  # compile
    out[1].block_until_ready()
    t_fused = []
    for _ in range(reps):
        t0 = time.perf_counter()
        out = decide(*args)
        out[1].block_until_ready()
        t_fused.append(time.perf_counter() - t0)
    fused_s = float(np.median(t_fused))
    rows.append((f"decide_jit_seconds_B{b}", fused_s,
                 "s per tick, one jit decide over the [B, N] stack"))
    rows.append((
        f"speedup_fused_vs_scalar_B{b}",
        scalar_s / max(fused_s, 1e-12),
        "x fused jit batch-decide vs per-scenario loop "
        "(acceptance: >= 20x at B=64)",
    ))

    # --- whole fused loop: simulate -> measure -> decide -> apply -------- #
    fused_runner = ScenarioRunner(scens, tick_interval=5.0, backend="jax")
    n_ticks = fused_runner.arrays.steps // fused_runner._steps_per_tick
    run_fn, _ = ctl.make_fused_loop(
        fused_runner.arrays, fused_runner.static, fused_runner._params(),
        steps_per_tick=fused_runner._steps_per_tick,
    )
    run_fn(fused_runner.k)["k_final"].block_until_ready()  # compile
    t0 = time.perf_counter()
    run_fn(fused_runner.k)["k_final"].block_until_ready()
    t_loop = time.perf_counter() - t0
    rows.append((
        f"fused_loop_ticks_per_second_B{b}",
        n_ticks * b / t_loop,
        f"scenario-ticks/s, {n_ticks} ticks x B={b} in one lax.scan program",
    ))
    base_tps = n_ticks * b / t_loop

    # --- sharded fused loop at fleet scale, over the visible devices ----- #
    b_shard = 64 if smoke else 4096
    if len(jax.devices()) < 2:
        # A 1-device "mesh" row would only repeat the pinned baseline.
        print(
            "bench_controller: sharded rows skipped, 1 device visible (set "
            "XLA_FLAGS=--xla_force_host_platform_device_count=D on a CPU host)",
            file=sys.stderr,
        )
    else:
        rows.extend(_sharded_rows(b_shard, b, base_tps, horizon))

    # --- §18 trigger-gated compacted decide vs dense --------------------- #
    rows.extend(_compaction_rows(smoke))

    # --- gain_topr kernel parity (interpret mode on CPU) ----------------- #
    from repro.kernels.gain_topr import kernel as topr_kernel, ref as topr_ref

    rng = np.random.default_rng(7)
    cand = np.maximum(rng.normal(0.5, 1.0, (8, 6, 24)), 0.0).astype(np.float32)
    cand.sort(axis=-1)
    cand = cand[..., ::-1].copy()
    budget = rng.integers(0, 40, 8).astype(np.int32)
    want = np.asarray(topr_ref.gain_topr(jnp.asarray(cand), jnp.asarray(budget)))
    got = np.asarray(topr_kernel.gain_topr_pallas(
        jnp.asarray(cand), jnp.asarray(budget), interpret=True
    ))
    rows.append((
        "gain_topr_interpret_parity",
        float((want == got).all()),
        "Pallas top-R kernel == jnp oracle, interpret mode (1.0 = exact)",
    ))
    return rows


# --------------------------------------------------------------------------- #
# §18 compacted decide: tile a small diurnal-zoo static stack to fleet
# extent and pin the trigger rate by construction — the compacted decide
# triggers on exact input change, so perturbing exactly f*B lanes' lam
# rows per tick (by a factor that never repeats between consecutive
# ticks) reprices exactly those lanes plus any hot ones.
# --------------------------------------------------------------------------- #
def _tile_static(st: ctl.ControllerStatic, reps: int) -> ctl.ControllerStatic:
    from dataclasses import replace as _replace

    return _replace(
        st,
        base_routing=np.tile(st.base_routing, (reps, 1, 1)),
        group=np.tile(st.group, (reps, 1)),
        alpha=np.tile(st.alpha, (reps, 1)),
        active=np.tile(st.active, (reps, 1)),
        speed=np.tile(st.speed, (reps, 1)),
        n_ops=np.tile(st.n_ops, reps),
        names=st.names * reps,
    )


def _tile_params(pr: ctl.ControllerParams, reps: int) -> ctl.ControllerParams:
    from dataclasses import replace as _replace

    return _replace(
        pr,
        t_max=np.tile(pr.t_max, reps),
        k_max=np.tile(pr.k_max, reps),
        headroom=np.tile(pr.headroom, reps),
        scale_in_hysteresis=np.tile(pr.scale_in_hysteresis, reps),
        min_improvement=np.tile(pr.min_improvement, reps),
        horizon_seconds=np.tile(pr.horizon_seconds, reps),
        allocator=pr.allocator * reps,
    )


def _decide_tps(
    b: int, rates: tuple[float, ...], *, reps: int, gate_at: float | None
) -> list[tuple[str, float, str]]:
    """Compacted-vs-dense decide ticks/s rows at extent ``b``, one per
    trigger rate.  Asserts bitwise identity on every compacted tick and
    the >= 3x gate at ``gate_at`` (None skips the gate — smoke extents)."""
    import jax
    import jax.numpy as jnp

    from repro.streaming.scenarios import scenario_matrix

    zoo = [
        s.with_(negotiated=False)
        for s in scenario_matrix(16, seed=9, horizon=20.0, warmup=5.0, dt=0.05)
    ]
    runner = ScenarioRunner(zoo, tick_interval=5.0, backend="numpy", fused=False)
    assert b % 16 == 0, b
    st = _tile_static(runner.static, b // 16)
    pr = _tile_params(runner._params(), b // 16)
    n = st.n
    rng = np.random.default_rng(3)
    lam = np.abs(rng.normal(2.0, 0.5, (b, n)))
    mu = np.abs(rng.normal(6.0, 0.5, (b, n))) + 1.0
    drop = np.zeros((b, n))
    lam0 = np.abs(rng.normal(2.0, 0.5, b))
    k = np.where(st.active, 2, 0).astype(np.int64)

    dense = ctl.make_decide_jax(st, pr)
    comp = ctl.make_decide_jax(st, pr, compact=True)
    rows: list[tuple[str, float, str]] = []
    dense_tps = None
    for rate in rates:
        n_trig = int(round(rate * b))
        # Factor cycle length 7 is coprime with everything the loop does,
        # so consecutive ticks never present a triggered lane with the
        # same lam row (which would memoize it quiet).
        lam_ticks = []
        for t in range(reps + 1):
            lt = lam.copy()
            lt[:n_trig] *= 1.0 + 0.01 * ((t % 7) + 1)
            lam_ticks.append(jnp.asarray(lt))
        d_args = lambda lt: (lt, jnp.asarray(mu), jnp.asarray(drop),
                             jnp.asarray(lam0), jnp.asarray(k))
        dense_outs = [dense(*d_args(lt)) for lt in lam_ticks]
        dense_outs[0][1].block_until_ready()
        if dense_tps is None:
            t0 = time.perf_counter()
            for lt in lam_ticks[1:]:
                dense(*d_args(lt))[1].block_until_ready()
            dense_tps = reps / (time.perf_counter() - t0)
            rows.append((f"decide_dense_ticks_per_second_B{b}", dense_tps,
                         f"dense jit decide, B={b} diurnal-zoo tile"))
        cache = comp.init_cache()
        out, _, cache = comp(*d_args(lam_ticks[0]), cache)  # cold: dense-cost
        out[1].block_until_ready()
        t0 = time.perf_counter()
        comp_outs = []
        for lt in lam_ticks[1:]:
            out, _, cache = comp(*d_args(lt), cache)
            comp_outs.append(out)
        comp_outs[-1][1].block_until_ready()
        comp_tps = reps / (time.perf_counter() - t0)
        # Bit-identity before the number is reported: a fast wrong decide
        # is worthless.  Hard fail — smoke included.  Decisions (code,
        # k_next, applied) are bitwise; the E[T] diagnostics get the mesh
        # tests' ~1-ulp rtol (XLA reassociates lane reductions at
        # compacted widths — tests/test_compaction.py).
        for ti, (want, got) in enumerate(zip(dense_outs[1:], comp_outs)):
            for oi in (0, 1, 4):
                if not np.array_equal(np.asarray(want[oi]), np.asarray(got[oi])):
                    raise AssertionError(
                        f"compacted decide diverged from dense at B={b}, "
                        f"trigger rate {rate:.0%}, tick {ti}, out[{oi}]"
                    )
            for oi in (2, 3):
                np.testing.assert_allclose(
                    np.asarray(want[oi]), np.asarray(got[oi]), rtol=1e-6,
                    err_msg=f"B={b} rate={rate} tick={ti} out[{oi}]",
                )
        pct = int(round(rate * 100))
        rows.append((
            f"decide_compacted_ticks_per_second_B{b}_trig{pct}pct", comp_tps,
            f"§18 compacted decide, {n_trig}/{b} lanes triggered per tick "
            "(bitwise == dense, asserted)",
        ))
        speedup = comp_tps / max(dense_tps, 1e-12)
        rows.append((
            f"compacted_vs_dense_speedup_B{b}_trig{pct}pct", speedup,
            "x compacted vs dense ticks/s"
            + (" (acceptance: >= 3x)" if gate_at == rate else ""),
        ))
        if gate_at == rate and speedup < 3.0:
            raise AssertionError(
                f"compaction gate regressed: {speedup:.2f}x < 3x at "
                f"B={b}, {rate:.0%} trigger rate"
            )
    ms = jax.local_devices()[0].memory_stats() or {}
    rows.append((
        f"compacted_peak_live_bytes_B{b}",
        float(ms.get("peak_bytes_in_use", -1.0)),
        "device peak live bytes after the compacted sweep "
        "(-1.0: backend reports no allocator stats, e.g. CPU)",
    ))
    return rows


def _compaction_rows(smoke: bool) -> list[tuple[str, float, str]]:
    rates = (0.02, 0.10, 0.50)
    if smoke:
        return _decide_tps(256, rates, reps=3, gate_at=None)
    rows = _decide_tps(4096, rates, reps=8, gate_at=0.10)
    rows += _decide_tps(10_000, (0.10,), reps=4, gate_at=None)
    return rows


# --------------------------------------------------------------------------- #
# Sharded rows run in this process over every visible device: one process
# holds the chips.  Emulated CPU devices come from XLA_FLAGS=
# --xla_force_host_platform_device_count=D, set before jax is imported.
# --------------------------------------------------------------------------- #
def _sharded_rows(
    b_shard: int, b: int, base_tps: float, horizon: float
) -> list[tuple[str, float, str]]:
    info = _sharded_tps(b_shard, horizon, reps=2)
    d = info["devices"]
    sharded_tps = info["ticks_per_s_sharded"]
    pinned_tps = info["ticks_per_s_pinned"]
    return [
        (
            f"fused_loop_sharded_ticks_per_second_B{b_shard}_D{d}", sharded_tps,
            f"scenario-ticks/s, batch axis shard_map'd over {d} "
            f"{info['platform']} devices ({info['n_ticks']} ticks)",
        ),
        (
            f"fused_loop_pinned_ticks_per_second_B{b_shard}_D1", pinned_tps,
            "same shard_map program on a 1-device mesh (the pinned baseline)",
        ),
        (
            f"sharded_vs_pinned_ratio_B{b_shard}",
            sharded_tps / max(pinned_tps, 1e-12),
            f"x D={d} mesh vs 1-device mesh; host has {os.cpu_count()} core(s) "
            "backing emulated CPU devices — parallel speedup needs real cores",
        ),
        (
            f"sharded_vs_B{b}_throughput_ratio",
            sharded_tps / max(base_tps, 1e-12),
            f"x B={b_shard} sharded aggregate scenario-ticks/s vs this run's "
            f"B={b} single-device row (ROADMAP's ~4.4k ticks/s reference)",
        ),
    ]


def _sharded_tps(b: int, horizon: float, reps: int) -> dict:
    import jax

    from repro.distributed.sharding import fleet_mesh
    from repro.streaming.scenarios import scenario_matrix

    scens = [
        s.with_(negotiated=False)
        for s in scenario_matrix(b, seed=5, horizon=horizon, warmup=5.0, dt=0.05)
    ]
    runner = ScenarioRunner(scens, tick_interval=5.0, backend="jax")
    d = len(jax.devices())
    out: dict = {"b": b, "devices": d, "platform": jax.devices()[0].platform}
    for tag, nd in (("sharded", d), ("pinned", 1)):
        loop, n_ticks = ctl.make_fused_loop(
            runner.arrays, runner.static, runner._params(),
            steps_per_tick=runner._steps_per_tick, mesh=fleet_mesh(nd),
        )
        np.asarray(loop(runner.k)["k_final"])  # compile + warm
        ts = []
        for _ in range(reps):
            t0 = time.perf_counter()
            loop(runner.k)["k_final"].block_until_ready()
            ts.append(time.perf_counter() - t0)
        out[f"ticks_per_s_{tag}"] = n_ticks * b / min(ts)
        out["n_ticks"] = n_ticks
    return out


if __name__ == "__main__":
    for _name, _val, _note in run(smoke="--smoke" in sys.argv[1:]):
        print(f"{_name},{_val},{_note}")
