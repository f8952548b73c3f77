"""Device time by program stage (``chipbench/stage_time.py``): on a
hand-built trace whose answers are known, with a program that has no
stage scopes, and on a short trace of the compacted decide service
recorded on a TPU v5 lite together with the program's own map of its
instruction names to stages (``data/``)."""

from __future__ import annotations

import json
import pathlib
import sys
from types import SimpleNamespace as NS

import pytest

from chipbench import bench, stage_time
from chipbench.run import load_module

DATA = pathlib.Path(__file__).resolve().parent / "data"
TRACE = DATA / "stale_service_trace.xplane.pb"
MAP = DATA / "stale_service_op_stages.json"
METRICS = pathlib.Path(__file__).resolve().parents[1] / "metrics"
SERVICE = ("trigger", "solve", "table", "candidates", "topr", "price", "gates")
STAGE_METRICS = [f"{s}_ms.service" for s in SERVICE + ("compact",)] + [
    "window_ms.twin", "measure_ms.twin", "decide_ms.twin", "unstaged_share.service",
    "unstaged_share.twin"]


def ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur)


def known_trace():
    # window 0..1000 ns; a window loop 100..400 holding the top-R kernel
    # (150..200) and a copy of no stage (250..300), which the loop's stage
    # takes; a copy of no stage outside any op (600..700)
    dev = [ev("%while.1 = loop", 100, 300), ev("%gain_topr_pallas.2 = k", 150, 50),
           ev("%copy.3 = c", 250, 50), ev("%copy.4 = c", 600, 100)]
    return bench.summarize(NS(planes=[
        NS(name="/host:CPU", lines=[NS(name="python3", events=[ev("window", 0, 1000)])]),
        NS(name="/device:TPU:0", lines=[NS(name="XLA Ops", events=dev)]),
    ]))


KNOWN = {"while.1": "window", "gain_topr_pallas.2": "topr", "copy.3": None}


def read(name, ctx):
    return load_module(METRICS / f"{name}.py").read(ctx)


def test_known_trace_by_stage(monkeypatch):
    monkeypatch.setattr(stage_time, "op_stages", lambda: KNOWN)
    s = known_trace()
    split = stage_time.split(s, KNOWN)
    assert split == {"window": pytest.approx(250e-9), "topr": pytest.approx(50e-9),
                     "unstaged": pytest.approx(100e-9)}
    assert sum(split.values()) == pytest.approx(s.busy_s)
    ctx = {"trace": s, "result": {"attempted": 2}, "traffic": {"ticks": 5}}
    assert read("topr_ms.service", ctx) == pytest.approx(2.5e-5)
    assert read("window_ms.twin", ctx) == pytest.approx(2.5e-5)
    assert read("decide_ms.twin", ctx) == pytest.approx(5e-6)
    assert read("unstaged_share.service", ctx) == pytest.approx(25.0)
    assert read("unstaged_share.twin", ctx) == pytest.approx(25.0)
    # stages the program never names read nothing, not zero
    assert read("compact_ms.service", ctx) is None
    assert read("measure_ms.twin", ctx) is None


@pytest.mark.parametrize("names", [None, {}], ids=["ambiguous", "no-programs"])
def test_no_map_reads_nothing(monkeypatch, names):
    monkeypatch.setattr(stage_time, "op_stages", lambda: names)
    ctx = {"trace": known_trace(), "result": {"attempted": 2}, "traffic": {"ticks": 5}}
    for name in STAGE_METRICS:
        assert read(name, ctx) is None


def test_program_without_stages_reads_nothing(monkeypatch):
    # A program that predates the stage scopes has no repro.core.stages.
    import repro.core

    monkeypatch.delattr(repro.core, "stages", raising=False)
    monkeypatch.setitem(sys.modules, "repro.core.stages", None)
    assert stage_time.op_stages() is None
    ctx = {"trace": known_trace(), "result": {"attempted": 2}, "traffic": {"ticks": 5}}
    assert read("gates_ms.service", ctx) is None
    assert read("unstaged_share.service", ctx) is None


def test_no_trace_reads_nothing():
    ctx = {"trace": None, "result": {"attempted": 2}, "traffic": {"ticks": 5}}
    assert read("solve_ms.service", ctx) is None
    assert read("unstaged_share.twin", ctx) is None


@pytest.fixture(scope="module")
def recorded():
    """The recorded stale-service window and the program's own map."""
    from jax.profiler import ProfileData

    trace = bench.summarize(ProfileData.from_file(str(TRACE)))
    return trace, json.loads(MAP.read_text())


def test_recorded_stages_partition_the_busy_time(recorded):
    trace, names = recorded
    split = stage_time.split(trace, names)
    assert sum(split.values()) == pytest.approx(trace.busy_s, abs=1e-9)
    assert set(split) == {"trigger", "solve", "table", "candidates", "topr", "price", "gates",
                          "compact", "unstaged"}


def test_recorded_topr_kernel_lands_in_topr(recorded):
    trace, names = recorded
    kernels = {n.split(" = ")[0].lstrip("%") for n, _, _ in trace.matching("gain_topr_pallas")}
    assert kernels and all(names[k] == "topr" for k in kernels)
    split = stage_time.split(trace, names)
    assert split["topr"] >= trace.op_seconds("gain_topr_pallas")


def test_recorded_unstaged_share_is_small(recorded, monkeypatch):
    trace, names = recorded
    monkeypatch.setattr(stage_time, "op_stages", lambda: names)
    ctx = {"trace": trace, "result": {"attempted": 3}, "traffic": {"ticks": 1}}
    assert read("unstaged_share.service", ctx) < 5.0
    for stage in SERVICE + ("compact",):
        assert read(f"{stage}_ms.service", ctx) > 0
