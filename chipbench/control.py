"""Readings from which a cell's limits are set, at the cell's own size:
the program over many seeds; on the first three, also the control (the
plain reference computed in bfloat16, put in the program's place) and
each planted fault of ``faults.py``.

    python chipbench/control.py --workload <cell> --seeds 1,2,3,...

One process builds and compiles the cell once.  For each seed it draws
new traffic, warms up, runs a short window (the mix's ``check_ticks``
ticks of the service, or one call of the twin: as many lane-ticks as a
run compares) and prints one JSON line with the numbers.  The
benchmark's own runs do not run this.
"""

from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent.parent
CONTROL_SEEDS = 3


def control_numbers(spec, cell, dep) -> dict:
    """The same comparison with the bfloat16 reference's outputs in the
    program's place."""
    import jax.numpy as jnp
    import numpy as np

    from chipbench import check, reference as ref

    if spec.traffic["entry"] == "twin":
        _, totals, outs = check.replay(
            dep, cell.ext, cell.k0, None, steps_per_tick=cell.steps_per_tick, dt=cell.dt,
            warmup_steps=cell.warmup_steps, xp=jnp, dtype=jnp.bfloat16, decide_own=True)
        out = dict(totals)
        out["codes"] = np.stack([o["code"] for o in outs])
        out["k"] = np.stack([o["k_next"] for o in outs])
        for key in ("et_cur", "et_target", "applied"):
            out[key] = np.stack([o[key] for o in outs])
        return check.twin_numbers(dep, cell.ext, cell.k0, out,
                                  steps_per_tick=cell.steps_per_tick, dt=cell.dt,
                                  warmup_steps=cell.warmup_steps)
    recs = []
    for r in cell.sample:
        d = ref.decide(jnp, jnp.bfloat16, dep, r["lam"], r["mu"], r["drop"], r["lam0"], r["k"])
        recs.append(dict(r, **{key: np.asarray(d[key]) for key in check.DECIDE_KEYS}))
    return check.service_numbers(dep, recs)


def short_window(spec, cell, span) -> None:
    cell.warm(span)
    if spec.traffic["entry"] == "twin":
        cell.window(span, calls=1)
    else:
        cell.window(span, ticks=int(spec.traffic["check_ticks"]))


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True, help="comma-separated")
    args = parser.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    from chipbench import faults, reference as ref, run

    run.configure()
    import jax

    spec = run.Spec(args.workload)
    seeds = [int(s) for s in args.seeds.split(",")]
    lanes = int(spec.cfg["lanes"])
    span = run.span_factory()
    dep = ref.Deployment(spec.cfg)
    cell = run.load_module(spec.driver).Cell(spec.cfg, spec.traffic, seeds[0], lanes)
    attr = "loop" if hasattr(cell, "loop") else "decide"
    print(json.dumps({"device": str(jax.devices()[0]), "lanes": lanes}), flush=True)
    for i, seed in enumerate(seeds):
        if i:
            cell.reseed(seed)
        short_window(spec, cell, span)
        t0 = time.perf_counter()
        line = {"seed": seed, "program": cell.numbers(dep)}
        seconds = {"reference": time.perf_counter() - t0}
        if i < CONTROL_SEEDS:
            t0 = time.perf_counter()
            line["control"] = control_numbers(spec, cell, dep)
            seconds["control"] = time.perf_counter() - t0
            for fault in faults.FAULTS:
                cell.reseed(seed)
                real = getattr(cell, attr)
                faults.wrap_with(fault)(cell)
                try:
                    short_window(spec, cell, span)
                    line[fault] = cell.numbers(dep)
                finally:
                    setattr(cell, attr, real)
        line["seconds"] = seconds
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
