"""Benchmark harness: one module per paper table/figure.

Prints ``name,value,derived`` CSV rows per benchmark.  Mapping:

  bench_overhead         -> paper Table II   (scheduling + measurement cost)
  bench_model_accuracy   -> paper Fig. 6 + 7 (allocation quality; est vs meas)
  bench_underestimation  -> paper Fig. 8     (out-of-model cost ratio)
  bench_rebalance        -> paper Fig. 9 + 10 (live rebalance, scale out/in)
  bench_overload         -> beyond-paper: flash-crowd overload (bounded
                            queues, drop agreement, "overloaded" decision)
  bench_scenarios        -> beyond-paper: scenario-matrix sweep (batch
                            simulator vs sequential DES, >= 20x gate)
  bench_controller       -> beyond-paper: batched control plane (fused jit
                            batch-decide vs per-scenario loop, >= 20x gate)
  bench_kernels          -> kernel layer (no paper table; TPU hot spots)
  bench_serving          -> beyond-paper: DRS-scheduled LLM serving
  bench_forecast         -> beyond-paper: proactive forecast/MPC control
                            vs the reactive trigger (miss/drop/cost gates,
                            confidence-gate fallback, twin-vs-jit parity)

Every run also persists its rows to a ``BENCH_<name>.json`` artifact at
the repo root (schema ``{bench, rows, smoke, timestamp}``); the CI
bench-smoke job uploads them, so the perf trajectory accumulates per PR
instead of evaporating with the job log.
"""

from __future__ import annotations

import inspect
import json
import pathlib
import subprocess
import sys
import time
import traceback

from . import (
    bench_controller,
    bench_forecast,
    bench_kernels,
    bench_model_accuracy,
    bench_overhead,
    bench_overload,
    bench_rebalance,
    bench_scenarios,
    bench_serving,
    bench_underestimation,
)

SUITES = [
    ("overhead", bench_overhead),
    ("model_accuracy", bench_model_accuracy),
    ("underestimation", bench_underestimation),
    ("rebalance", bench_rebalance),
    ("overload", bench_overload),
    ("scenarios", bench_scenarios),
    ("controller", bench_controller),
    ("kernels", bench_kernels),
    ("serving", bench_serving),
    ("forecast", bench_forecast),
]

REPO_ROOT = pathlib.Path(__file__).resolve().parent.parent


def provenance() -> dict:
    """Attribution fields stamped into every artifact: without the commit
    and runtime that produced a number, the per-PR perf trajectory the
    bench-smoke job accumulates is not comparable across uploads."""
    try:
        git_sha = subprocess.run(
            ["git", "rev-parse", "HEAD"], cwd=REPO_ROOT, text=True,
            capture_output=True, timeout=10, check=True,
        ).stdout.strip()
    except Exception:  # noqa: BLE001 — detached tarballs, missing git
        git_sha = "unknown"
    try:
        import jax

        jax_version, backend = jax.__version__, jax.default_backend()
    except Exception:  # noqa: BLE001 — numpy-only environments
        jax_version, backend = "unavailable", "none"
    return {"git_sha": git_sha, "jax_version": jax_version, "backend": backend}


def persist(name: str, rows: list, smoke: bool) -> pathlib.Path:
    """Write one suite's rows to ``BENCH_<name>.json`` at the repo root."""
    path = REPO_ROOT / f"BENCH_{name}.json"
    path.write_text(json.dumps(
        {
            "bench": name,
            "rows": [
                {"name": rn, "value": val, "note": note} for rn, val, note in rows
            ],
            "smoke": smoke,
            "timestamp": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
            **provenance(),
        },
        indent=2,
    ) + "\n")
    return path


def main() -> None:
    # ``python -m benchmarks.run [suite] [--smoke]`` — smoke caps every
    # bench to seconds (CI drift gate); a suite name runs just that one.
    from repro.compile_cache import use_compile_cache

    use_compile_cache(REPO_ROOT / ".jax_cache")
    args = [a for a in sys.argv[1:] if a != "--smoke"]
    smoke = "--smoke" in sys.argv[1:]
    only = args[0] if args else None
    failures = 0
    for name, mod in SUITES:
        if only and only != name:
            continue
        print(f"# --- {name} ({mod.__name__}) ---", flush=True)
        t0 = time.time()
        try:
            kwargs = (
                {"smoke": smoke}
                if "smoke" in inspect.signature(mod.run).parameters
                else {}
            )
            rows = list(mod.run(**kwargs))
            for row_name, val, note in rows:
                print(f"{row_name},{val},{note}", flush=True)
            persist(name, rows, smoke)
        except Exception:  # noqa: BLE001
            failures += 1
            print(f"{name},ERROR,{traceback.format_exc().splitlines()[-1]}")
        print(f"# {name} took {time.time()-t0:.1f}s", flush=True)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
