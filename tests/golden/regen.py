#!/usr/bin/env python
"""Regenerate (or verify) the committed golden control-loop traces
(DESIGN.md §13).

One command, from the repo root:

    PYTHONPATH=src python tests/golden/regen.py           # rewrite fixtures
    PYTHONPATH=src python tests/golden/regen.py --check   # drift guard (CI)

The default mode rewrites ``vld_control_trace.json``,
``fpd_control_trace.json``, and ``vld_proactive_control_trace.json``
(the forecast/MPC plane on the flash-crowd VLD — proving predictor +
planner replayability, DESIGN.md §15) next to this script.  Run it
after an *intentional* change to the scheduler / batch simulator /
forecast decision path, eyeball the diff (actions and allocations are
the contract), and commit the new fixtures together with the change.

``--check`` regenerates into a temporary directory and diffs against the
committed fixtures, exiting non-zero on any difference — CI runs it so a
silent decision-logic change can't leave stale goldens behind.
``tests/test_golden_traces.py`` replays the same scenarios and diffs.
"""

from __future__ import annotations

import json
import pathlib
import sys
import tempfile

HERE = pathlib.Path(__file__).resolve().parent


def entries():
    """(fixture name, scenario, proactive cfg | None, tick_interval) — the
    one list both this script and tests/test_golden_traces.py replay
    from."""
    import dataclasses

    import numpy as np

    from repro.forecast import MPCConfig, PredictorParams
    from repro.streaming.scenarios import ArrivalTrace, fpd_scenario, vld_scenario
    from repro.streaming.soak import SoakConfig, build_scenario

    mpc = MPCConfig(
        horizon=3, window=12, min_scored=2, headroom=1.1,
        scale_in_hysteresis=0.7,
        predictor=PredictorParams(kind="holt", alpha=0.6, beta=0.4),
    )
    # Flash crowd as a steep ramp (the benchmarks/bench_forecast.py flash
    # scenario): forecastable, so the MPC plane actually commits plans
    # ahead of the trigger instead of just holding.
    t5 = np.arange(0.0, 231.0, 5.0)
    ramp = np.interp(t5, [0, 80, 120, 140, 170, 230], [10, 10, 30, 30, 12, 12])
    flash_vld = vld_scenario(
        name="vld_proactive",
        traces={"extract": ArrivalTrace(kind="replay", samples=tuple(ramp),
                                        sample_dt=5.0)},
        t_max=1.0, queue_capacity=40, machine_size=1, horizon=230.0,
    )
    # The soak harness's smoke-capped composite day (DESIGN.md §17):
    # pins the twin's decision surface for the same scenario
    # tests/test_soak.py drives through the fused checkpointed loop.
    soak = dataclasses.replace(build_scenario(SoakConfig.smoke()), name="soak")
    return [
        ("vld", vld_scenario(), None, 10.0),
        ("fpd", fpd_scenario(), None, 10.0),
        ("vld_proactive", flash_vld, mpc, 10.0),
        # Static-budget VLD: jit-eligible (no negotiator), so this one
        # fixture is ALSO replayed through the fused jax loop
        # (tests/test_golden_traces.py) — the jit decision surface must
        # match this twin-generated trace bit-for-bit.
        ("vld_fused", vld_scenario(name="vld_fused", negotiated=False), None, 10.0),
        ("soak", soak, None, 120.0),
    ]


def generate(out_dir: pathlib.Path) -> list[pathlib.Path]:
    from repro.streaming.scenarios import control_trace

    paths = []
    for name, scenario, proactive, tick_interval in entries():
        trace = control_trace(
            [scenario], tick_interval=tick_interval, proactive=proactive
        )
        path = out_dir / f"{name}_control_trace.json"
        path.write_text(json.dumps(trace, indent=2, sort_keys=True) + "\n")
        paths.append(path)
    return paths


def main() -> int:
    check = "--check" in sys.argv[1:]
    if not check:
        for path in generate(HERE):
            ticks = len(
                next(iter(json.loads(path.read_text())["scenarios"].values()))["actions"]
            )
            print(f"wrote {path} ({ticks} ticks)")
        return 0
    drifted = []
    with tempfile.TemporaryDirectory(prefix="golden-check-") as tmp:
        for fresh in generate(pathlib.Path(tmp)):
            committed = HERE / fresh.name
            if not committed.exists():
                drifted.append(f"{committed} is missing")
            elif committed.read_text() != fresh.read_text():
                drifted.append(f"{committed} differs from a fresh regeneration")
    if drifted:
        for line in drifted:
            print(f"GOLDEN DRIFT: {line}", file=sys.stderr)
        print(
            "The committed golden traces no longer match the decision path.\n"
            "If the change is intentional, regenerate and commit them:\n"
            "    PYTHONPATH=src python tests/golden/regen.py",
            file=sys.stderr,
        )
        return 1
    print("golden traces match a fresh regeneration")
    return 0


if __name__ == "__main__":
    sys.exit(main())
