"""Smoke test of the fused control plane on a TPU.

Runs the main path once, through the entry points a user calls, at a
fleet size DRS users run, and checks what comes out:

1. kernels: every Pallas kernel of the control plane, compiled for the
   chip, against its jnp oracle (``kernels/*/ref.py``) run on the same
   chip, at the loop's widths;
2. loop: ``ScenarioRunner(backend="jax")`` over B = 16384 lanes (a
   64-scenario zoo tiled 256 times; 8 ticks of 100 steps at dt = 0.05 s,
   the first tick warmup) with the dense decide and with
   ``compact=True``.

With ``--mesh 4`` it runs only the sharded paths, on four chips: the
fused loop over ``fleet_mesh(4)`` at B = 65536 against the same program
on ``fleet_mesh(1)``, and ``FleetPlanner.plan_batched`` on
``fleet_mesh(4)`` against the unsharded ``plan``.

Each failed check raises.  The last line of standard output is
``{"ok": true, "device": {...}}`` only when every phase passed; without a
TPU, or without the ``src/repro`` package next to this file, the script
exits non-zero before any phase runs.

    python chip_smoke.py            # one chip
    python chip_smoke.py --mesh 4   # four chips
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

import numpy as np

REPO = pathlib.Path(__file__).resolve().parent
SRC = REPO / "src"

BASE = 64  # scenarios in the zoo; a fleet tiles it
TILES = 256  # one chip: B = 16384
MESH_TILES = 1024  # four chips: B = 65536
TICK, HORIZON, WARMUP, DT = 5.0, 40.0, 5.0, 0.05  # 8 ticks of 100 steps
ZOO_SEED = 5
K_HI = 64  # the zoo's largest k_max
# Float outputs of a compiled kernel against its oracle on the same chip.
KERNEL_RTOL = 1e-5
# Sharded against single-device E[T] diagnostics (DESIGN.md §16).
MESH_RTOL = 1e-6


def log(msg: str) -> None:
    print(msg, flush=True)


class CompileClock:
    """Seconds the backend spends compiling programs (or fetching them from
    the persistent cache), summed from JAX's monitoring events, and the
    persistent-cache hits.  Tracing is left out: its events nest."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def zoo() -> list:
    """The 64 base scenarios: random graphs of N <= 7 operators (padded to
    8) x trace kinds x overload policies, static budgets (k_max 48 or 64)."""
    from repro.streaming.scenarios import scenario_matrix

    return [
        s.with_(negotiated=False, k_max=48 if i % 2 else 64)
        for i, s in enumerate(
            scenario_matrix(BASE, seed=ZOO_SEED, horizon=HORIZON, warmup=WARMUP, dt=DT)
        )
    ]


# --------------------------------------------------------------------------- #
# Phase 1: compiled kernels against their oracles
# --------------------------------------------------------------------------- #
def _close(name, got, want, rtol) -> None:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    if got.shape != want.shape:
        raise AssertionError(f"{name}: shape {got.shape} != oracle {want.shape}")
    fin = np.isfinite(want) & (np.abs(want) > 1e-30)
    err = float(np.max(np.abs(got[fin] - want[fin]) / np.abs(want[fin]), initial=0.0))
    log(f"  {name}: shape {got.shape}, max rel err vs oracle {err!r} (rtol {rtol})")
    np.testing.assert_allclose(got, want, rtol=rtol, atol=1e-30, err_msg=name)


def _exact(name, got, want) -> None:
    got, want = np.asarray(got), np.asarray(want)
    bad = int(np.sum(got != want))
    log(f"  {name}: shape {got.shape}, {bad} entries differ from the oracle")
    if bad:
        raise AssertionError(f"{name}: {bad} entries differ from the oracle")


def check_kernels(*, b: int, n: int) -> None:
    """Each kernel at the loop's widths against its oracle, both on the
    chip."""
    import functools

    import jax
    import jax.numpy as jnp

    from repro.kernels.erlang_c import kernel as ek, ref as eref
    from repro.kernels.gain_topr import kernel as gk, ref as gref
    from repro.kernels.l2_match import kernel as lk, ref as lref
    from repro.kernels.queue_step import kernel as qk, ref as qref

    rng = np.random.default_rng(11)
    f32 = functools.partial(jnp.asarray, dtype=jnp.float32)
    s = b * n

    log(f"kernel queue_step, M = {s} queue lanes")
    q = f32(rng.gamma(2.0, 5.0, s))
    inflow = f32(rng.gamma(2.0, 3.0, s))
    cap_s = f32(rng.uniform(0.0, 20.0, s))
    cap_q = f32(np.where(rng.random(s) < 0.5, np.inf, rng.uniform(5.0, 60.0, s)))
    got = qk.queue_step_pallas(q, inflow, cap_s, cap_q)
    want = jax.jit(qref.queue_step)(q, inflow, cap_s, cap_q)
    for name, g, w in zip(("q_next", "served", "dropped"), got, want):
        _close(f"queue_step {name}", g, w, KERNEL_RTOL)

    log(f"kernel erlang_c, S = {s} lanes, k_hi = {K_HI}")
    a = f32(rng.uniform(0.0, 40.0, s))
    got = ek.erlang_b_table_pallas(a, k_hi=K_HI)
    want = jax.jit(functools.partial(eref.erlang_b_table, k_hi=K_HI))(a)
    _close("erlang_c table", got, want, KERNEL_RTOL)

    log(f"kernel gain_topr, B = {b}, N = {n}, J = {K_HI}")
    cand = -np.sort(-rng.gamma(1.5, 1.0, (b, n, K_HI)), axis=-1)
    cand = f32(np.where(rng.random((b, n, 1)) < 0.1, 0.0, cand))
    budget = jnp.asarray(rng.integers(0, n * K_HI // 2, b), dtype=jnp.int32)
    got = gk.gain_topr_pallas(cand, budget)
    _exact("gain_topr take", got, jax.jit(gref.gain_topr)(cand, budget))

    # Small-integer descriptors keep every distance an exact integer on
    # both paths, so the counts compare exactly whatever the matmul
    # precision; two library blocks exercise the accumulation.
    log("kernel l2_match.match_count, 512 frames x 256 logos x 128 dims")
    desc = f32(rng.integers(0, 3, (512, 128)))
    lib = f32(rng.integers(0, 3, (256, 128)))
    valid = jnp.asarray(rng.random(512) < 0.9)
    thresh = float(np.sqrt(130.5))
    got = lk.match_count_pallas(desc, lib, valid, thresh)
    _exact("match_count", got, jax.jit(lref.match_count)(desc, lib, thresh, valid))


# --------------------------------------------------------------------------- #
# Phase 2: the fused loop through ScenarioRunner
# --------------------------------------------------------------------------- #
def run_loop(label: str, scens: list, device: str, clock: CompileClock, **kw):
    """``ScenarioRunner(...).run()`` once (trace, compile, run, reports).
    Then the runner's loop is built again from its own inputs and run
    twice: the first run compiles it anew (or fetches it from the
    persistent cache), the second gives the steady-state time.  Returns
    the runner and the raw per-tick arrays of the second run, on the
    host; the checks hold the runner's decisions to those arrays."""
    import jax

    from repro.api.session import ScenarioRunner
    from repro.core import controller as ctl

    t0 = time.perf_counter()
    runner = ScenarioRunner(scens, tick_interval=TICK, backend="jax", **kw)
    k0 = runner.k.copy()
    t_setup = time.perf_counter() - t0
    if not runner.fused:
        raise AssertionError(f"{label}: the runner did not take the fused loop")
    c0, h0 = clock.seconds, clock.cache_hits
    t0 = time.perf_counter()
    runner.run()
    t_first = time.perf_counter() - t0
    t_compile, hits = clock.seconds - c0, clock.cache_hits - h0
    loop, ticks = ctl.make_fused_loop(
        runner.arrays, runner.static, runner._params(),
        steps_per_tick=runner._steps_per_tick, warmup_seconds=scens[0].warmup,
        mesh=runner.mesh, compact=runner.compact,
    )
    c0, h0 = clock.seconds, clock.cache_hits
    t0 = time.perf_counter()
    first = jax.block_until_ready(loop(k0))
    t_again = time.perf_counter() - t0
    t_compile2, hits2 = clock.seconds - c0, clock.cache_hits - h0
    t0 = time.perf_counter()
    out = jax.block_until_ready(loop(k0))
    t_steady = time.perf_counter() - t0
    if runner.mesh is not None and runner.mesh.size > 1:
        check_spread(label, runner)
    del loop
    out = {key: np.asarray(v) for key, v in out.items()}
    b = len(scens)
    log(
        f"loop[{label}] on {device}: B = {b}, {ticks} ticks x "
        f"{runner._steps_per_tick} steps; setup {t_setup!r} s; first run "
        f"(trace + compile + run + reports) {t_first!r} s, of which XLA "
        f"compile {t_compile!r} s ({hits} persistent-cache hits); rebuilt "
        f"loop, first run {t_again!r} s, of which XLA compile {t_compile2!r} s "
        f"({hits2} persistent-cache hits); steady run {t_steady!r} s = "
        f"{ticks / t_steady!r} ticks/s = {ticks * b / t_steady!r} lane-ticks/s"
    )
    for key in ("codes", "k", "applied", "k_final"):
        if not np.array_equal(np.asarray(first[key]), out[key]):
            raise AssertionError(f"{label}: a second run of the loop changed {key}")
    if not np.array_equal(runner.k, out["k_final"]):
        raise AssertionError(f"{label}: the runner's final allocation differs")
    codes = [
        [ctl.ACTIONS.index(d.action) for d in runner.decisions[bi]] for bi in range(b)
    ]
    if not np.array_equal(np.array(codes).T, out["codes"]):
        raise AssertionError(f"{label}: the runner's actions differ from the loop's")
    return runner, out


def check_spread(label: str, runner) -> None:
    """While a sharded loop is alive it keeps its float32 arrivals
    [steps, B, N] on the mesh: each device must hold its lane shard of
    them, not device 0 all of them."""
    import jax

    mesh = runner.mesh
    share = runner.arrays.ext.size * 4 // mesh.size
    held = dict.fromkeys(mesh.devices.flat, 0)
    for arr in jax.live_arrays():
        for shard in arr.addressable_shards:
            if shard.device in held:
                held[shard.device] += shard.data.nbytes
    held = list(held.values())
    log(f"  {label}: live bytes per device {held}; arrival shard {share}")
    if min(held) < share:
        raise AssertionError(f"{label}: a device holds less than its lane shard")


def check_invariants(label: str, runner, out: dict, tiles: int) -> None:
    """Tiled copies decide alike; budgets hold; every action is known."""
    from repro.core import controller as ctl

    for key in ("codes", "k", "applied", "k_final"):
        x = out[key]
        lanes = x.shape[1] if key != "k_final" else x.shape[0]
        if key == "k_final":
            tiled = x.reshape(tiles, lanes // tiles, *x.shape[1:])
            same = (tiled == tiled[:1]).all()
        else:
            tiled = x.reshape(x.shape[0], tiles, lanes // tiles, *x.shape[2:])
            same = (tiled == tiled[:, :1]).all()
        if not same:
            raise AssertionError(f"{label}: tiled copies of a lane disagree on {key}")
    k_max = np.array([s.k_max for s in runner.scenarios])
    if (out["k"].sum(axis=-1) > k_max).any() or (out["k_final"].sum(-1) > k_max).any():
        raise AssertionError(f"{label}: an allocation exceeds its lane's k_max")
    if (out["k"] < 0).any():
        raise AssertionError(f"{label}: a negative allocation")
    codes = out["codes"]
    if codes.min() < 0 or codes.max() >= len(ctl.ACTIONS):
        raise AssertionError(f"{label}: action code outside ctl.ACTIONS")
    acts = {a for decs in runner.decisions for a in (d.action for d in decs)}
    if not acts <= set(ctl.ACTIONS):
        raise AssertionError(f"{label}: unknown actions {acts - set(ctl.ACTIONS)}")
    hist = np.bincount(codes.ravel(), minlength=len(ctl.ACTIONS))
    log(f"  {label}: tiles agree, budgets hold; actions "
        f"{ {ctl.ACTIONS[i]: int(c) for i, c in enumerate(hist) if c} }")


def _surface(runner, lanes: int) -> list:
    """Per lane: the (action, allocation) sequence over the ticks."""
    return [
        [(d.action, tuple(int(x) for x in d.k_current)) for d in runner.decisions[bi]]
        for bi in range(lanes)
    ]


def _differing(a: list, b: list) -> int:
    return sum(x != y for x, y in zip(a, b))


def check_loop(base: list, tiles: int, device: str, clock: CompileClock) -> None:
    from repro.api.session import ScenarioRunner

    scens = base * tiles
    dense, dense_out = run_loop("dense", scens, device, clock)
    check_invariants("dense", dense, dense_out, tiles)
    compact, compact_out = run_loop("compact", scens, device, clock, compact=True)
    check_invariants("compact", compact, compact_out, tiles)
    for key in ("codes", "k", "applied", "k_final"):
        if not np.array_equal(compact_out[key], dense_out[key]):
            raise AssertionError(f"compact: {key} differs from the dense loop")
    log("  compact: decisions bitwise equal to the dense loop")

    twin = ScenarioRunner(base, tick_interval=TICK, backend="numpy")
    twin.run()
    ref = _surface(dense, len(base))
    log(f"reported: base lanes where {device} differs from the float64 numpy "
        f"twin: {_differing(_surface(twin, len(base)), ref)} of {len(base)}")


# --------------------------------------------------------------------------- #
# --mesh: the sharded paths
# --------------------------------------------------------------------------- #
def check_mesh(
    base: list, n_dev: int, tiles: int, device: str, clock: CompileClock
) -> None:
    from repro.core.planner import FleetPlanner, Tenant
    from repro.distributed.sharding import fleet_mesh

    scens = base * tiles
    mesh = fleet_mesh(n_dev)
    sharded, sharded_out = run_loop(f"mesh{n_dev}", scens, device, clock, mesh=mesh)
    check_invariants(f"mesh{n_dev}", sharded, sharded_out, tiles)
    _, single_out = run_loop("mesh1", scens, device, clock, mesh=fleet_mesh(1))
    for key in ("codes", "k", "applied", "k_final"):
        if not np.array_equal(sharded_out[key], single_out[key]):
            raise AssertionError(f"mesh{n_dev}: {key} differs from fleet_mesh(1)")
    for key in ("et_cur", "et_target"):
        np.testing.assert_allclose(
            sharded_out[key], single_out[key], rtol=MESH_RTOL,
            err_msg=f"mesh{n_dev} {key}",
        )
    log(f"  mesh{n_dev}: decisions bitwise equal to fleet_mesh(1); E[T] within "
        f"rtol {MESH_RTOL}")

    tenants = [
        Tenant(s.name, topology=s.mean_topology(), t_max=s.t_max) for s in base
    ]
    planner = FleetPlanner(tenants, k_max=24 * len(base))
    want = planner.plan()
    t0 = time.perf_counter()
    got = planner.plan_batched(mesh=mesh)
    log(f"plan_batched over fleet_mesh({n_dev}): {len(tenants)} tenants, pool "
        f"{planner.k_max}, {time.perf_counter() - t0!r} s incl. compile")
    for name in want.k:
        if not np.array_equal(want.k[name], got.k[name]):
            raise AssertionError(f"plan_batched: tenant {name} differs from plan")
    if (want.total, want.overloaded, want.unmet, want.unreachable) != (
        got.total, got.overloaded, got.unmet, got.unreachable
    ):
        raise AssertionError("plan_batched: plan summary differs from plan")
    log(f"  plan_batched: equal to plan ({want.total} processors handed out)")


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--mesh", type=int, default=None, metavar="D",
                        help="run only the sharded paths over D chips")
    args = parser.parse_args(argv)
    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: no repro package at {SRC}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))
    from repro.compile_cache import use_compile_cache

    import jax

    cache = pathlib.Path(use_compile_cache(REPO / ".jax_cache"))
    warm = len(list(cache.iterdir())) if cache.is_dir() else 0
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    log(f"device: {device}; compile cache {cache}, {warm} entries at start")
    if device["platform"] != "tpu":
        print("chip_smoke: JAX found no TPU", file=sys.stderr)
        return 1
    kind = device["kind"]
    clock = CompileClock()
    base = zoo()
    t0 = time.perf_counter()
    if args.mesh:
        if len(devs) < args.mesh:
            print(f"chip_smoke: {args.mesh} chips asked, {len(devs)} visible",
                  file=sys.stderr)
            return 1
        check_mesh(base, args.mesh, MESH_TILES, kind, clock)
    else:
        check_kernels(b=BASE * TILES, n=8)
        check_loop(base, TILES, kind, clock)
    log(f"all phases passed in {time.perf_counter() - t0!r} s")
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
