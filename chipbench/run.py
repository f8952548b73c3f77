"""DRS chip benchmark: one run of one cell.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (an entry of ``BENCHMARK.json``'s ``workloads``) names a
configuration (``configs/<config>.json``) and a traffic mix
(``traffic/<traffic>.json``); the mix names its entry driver
(``drivers/<entry>.py``); ``workloads/<cell>.json`` holds the limits of
the comparison that decides ``correct``; each metric is read by
``metrics/<metric>.py``.  A run builds the fleet, compiles and warms the
cell's own shapes (set-up), measures for ``--seconds`` (or, with
``--trace 1``, traces the mix's short window), compares what the timed
path produced with the plain reference, and prints one JSON object as
the last line of standard output.  It exits non-zero, printing no
result, where JAX finds no TPU or fewer chips than the cell asks for.
"""

from __future__ import annotations

import argparse
import contextlib
import importlib.util
import json
import os
import pathlib
import shutil
import sys
import tempfile
import time

T_START = time.perf_counter()
# Load from one process with few threads: the host's math (the traffic
# generator, the reference) runs on one thread, so no BLAS or OpenMP
# pool can spin beside the ticks.  Set before anything imports numpy.
for _var in ("OPENBLAS_NUM_THREADS", "OMP_NUM_THREADS", "MKL_NUM_THREADS"):
    os.environ[_var] = "1"
ROOT = pathlib.Path(__file__).resolve().parent.parent
HERE = ROOT / "chipbench"


def log(msg: str) -> None:
    print(msg, flush=True)


def load_module(path: pathlib.Path):
    spec = importlib.util.spec_from_file_location("chipbench_" + path.stem.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(path: pathlib.Path) -> dict:
    return json.loads(path.read_text())


class Spec:
    """Everything a cell's name leads to, read from files."""

    def __init__(self, name: str):
        self.bench = load_json(ROOT / "BENCHMARK.json")
        cells = {w["name"]: w for w in self.bench["workloads"]}
        if name not in cells:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json")
        self.name = name
        self.cell = cells[name]
        self.cfg = load_json(HERE / "configs" / f"{self.cell['config']}.json")
        self.traffic = load_json(HERE / "traffic" / f"{self.cell['traffic']}.json")
        self.limits = load_json(HERE / "workloads" / f"{name}.json")["limits"]
        self.driver = HERE / "drivers" / f"{self.traffic['entry']}.py"
        self.metric_dir = HERE / "metrics"

    def metrics(self, traced: bool) -> list:
        group = self.bench["per_layer"] if traced else self.bench["end_to_end"]
        return [m for m in group if self.name in m.get("workloads", [self.name])]


def span_factory():
    import jax

    return lambda name: jax.profiler.TraceAnnotation(name)


def configure() -> pathlib.Path:
    """The compile cache and the precision every run uses; returns the
    cache directory."""
    from repro.compile_cache import use_compile_cache

    cache = pathlib.Path(use_compile_cache(ROOT / ".jax_cache"))
    import jax

    # Every program, however quick to compile, goes into the cache, so a
    # second run of a cell compiles nothing.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # The configurations state float32.  On a TPU a float32 matrix product
    # or solve (the decide's inflow and Jackson solve, the window's
    # routing) runs in one bfloat16 pass unless asked for more.
    jax.config.update("jax_default_matmul_precision", "highest")
    return cache


def run_cell(spec: Spec, seed: int, seconds: float, traced: bool, *, lanes: int | None = None,
             device_check: bool = True, wrap=None) -> dict:
    """One run; returns the result object.  ``lanes`` (default: the
    configuration's) and ``device_check=False`` let a CPU test drive the
    same path at a small size; ``wrap(cell)`` may replace what the window
    calls (a planted fault)."""
    cache = configure()
    import jax

    from chipbench import bench, check, reference

    t_imports = time.perf_counter() - T_START
    devs = jax.devices()
    t_device = time.perf_counter() - T_START
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)}
    entries = len(list(cache.iterdir())) if cache.is_dir() else 0
    log(f"device: {device}; compile cache {cache}, {entries} entries at start")
    if device_check:
        if device["platform"] != "tpu":
            raise SystemExit("chipbench: JAX found no TPU")
        if len(devs) < int(spec.cell["chips"]):
            raise SystemExit(f"chipbench: {spec.cell['chips']} chips asked, {len(devs)} visible")
        if device["kind"] not in bench.PEAKS:
            raise SystemExit(f"chipbench: no published peaks for {device['kind']!r}")
    clock = bench.CompileClock()
    lanes = int(spec.cfg["lanes"] if lanes is None else lanes)
    span = span_factory()
    cell = load_module(spec.driver).Cell(spec.cfg, spec.traffic, seed, lanes)
    if wrap is not None:
        wrap(cell)
    t_built = time.perf_counter() - T_START
    cell.warm(span)
    setup_s = time.perf_counter() - T_START
    log(f"set-up {setup_s!r} s (imports {t_imports!r} s, device {t_device - t_imports!r} s, "
        f"fleet and traffic built "
        f"{t_built - t_device!r} s, compile and warm-up {setup_s - t_built!r} s): XLA compile "
        f"{clock.seconds!r} s over {clock.compiles} programs, {clock.cache_hits} "
        f"persistent-cache hits")

    compiles0 = clock.compiles
    summary, trace_dir = None, None
    if traced:
        trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        try:
            result = cell.window(span, **spec.traffic["trace_window"])
        finally:
            jax.profiler.stop_trace()
    else:
        result = cell.window(span, seconds=seconds)
    log(f"window: {result['attempted']} calls; {clock.compiles - compiles0} programs "
        f"compiled inside it; " + ", ".join(
            f"{k} {v!r}" for k, v in result.items() if isinstance(v, (int, float))))
    if traced:
        summary = bench.read_trace(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        log(f"trace: window {summary.window_s!r} s, device busy {summary.busy_s!r} s on "
            f"{len(summary.chips)} chips, {len(summary.ops)} device ops")

    stats = devs[0].memory_stats() or {}
    device["memory_peak_bytes"] = int(stats.get("peak_bytes_in_use", 0))
    if summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
    cell.release()

    t0 = time.perf_counter()
    numbers = cell.numbers(reference.Deployment(spec.cfg))
    log(f"reference comparison took {time.perf_counter() - t0!r} s; "
        f"{numbers.get('_differing')} of {numbers.get('_rows')} lane-decisions differ")
    correct, shown = check.judge(numbers, spec.limits)

    ctx = {"result": result, "setup_s": setup_s, "trace": summary, "traffic": spec.traffic}
    metrics = {}
    for m in spec.metrics(traced):
        value = load_module(spec.metric_dir / f"{m['name']}.py").read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    out = {"correct": correct, "attempted": result["attempted"], "failed": 0,
           "metrics": metrics, "device": device}
    if summary is not None:
        out["breakdown"] = summary.breakdown()
    out["checks"] = shown
    return out


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"chipbench: no repro package under {ROOT / 'src'}", file=sys.stderr)
        return 2
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    # The compile cache lives at a fixed path inside the checkout.
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    spec = Spec(args.workload)
    try:
        out = run_cell(spec, args.seed, args.seconds, bool(args.trace))
    except SystemExit as e:
        print(e, file=sys.stderr)
        return 1
    with contextlib.redirect_stdout(sys.stderr):
        for name, c in out["checks"].items():
            print(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
