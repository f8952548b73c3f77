"""The reduction from a profiler trace to the per-layer numbers: on a
hand-built trace whose answers are known, and on a short trace of the
decide service recorded on a TPU v5 lite (``data/``)."""

from __future__ import annotations

import pathlib
from types import SimpleNamespace as NS

import pytest

from chipbench import bench
from chipbench.run import load_module

DATA = pathlib.Path(__file__).resolve().parent / "data" / "service_trace.xplane.pb"
METRICS = pathlib.Path(__file__).resolve().parents[1] / "metrics"


def ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur)


def profile(device_events, host_events):
    return NS(planes=[
        NS(name="/host:CPU", lines=[NS(name="python3", events=host_events)]),
        NS(name="/device:TPU:0", lines=[
            NS(name="XLA Modules", events=[ev("jit_decide", 0, 10_000)]),
            NS(name="XLA Ops", events=device_events),
        ]),
    ])


def test_known_trace():
    # window 0..1000 ns; a loop 100..400 holding two kernels; an op 600..700
    dev = [ev("%while.1 = loop", 100, 300), ev("%queue_step_pallas.2 = k", 150, 50),
           ev("%queue_step_pallas.2 = k", 250, 50), ev("%fusion.3 = f", 600, 100),
           ev("%fusion.9 = outside", 2000, 100)]
    host = [ev("window", 0, 1000), ev("generate", 0, 100), ev("call", 100, 600),
            ev("fetch", 700, 300)]
    s = bench.summarize(profile(dev, host))
    assert s.window_s == pytest.approx(1e-6)
    assert s.busy_s == pytest.approx(400e-9)
    assert s.idle_share() == pytest.approx(0.6)
    assert s.op_seconds("queue_step") == pytest.approx(100e-9)
    assert s.enclosing("queue_step") == [("%while.1 = loop", 100, 400)]
    gaps = sorted(s.idle_gaps())
    assert gaps == [("call", pytest.approx(200e-9)), ("fetch", pytest.approx(300e-9)),
                    ("generate", pytest.approx(100e-9))]
    ops = dict(s.breakdown()["device_ops"])
    assert ops == {"while.1": pytest.approx(200e-9), "queue_step_pallas.2": pytest.approx(100e-9),
                   "fusion.3": pytest.approx(100e-9)}
    ctx = {"trace": s, "result": {"attempted": 2}, "traffic": {"ticks": 1}}
    assert load_module(METRICS / "window_sim_ms.twin.py").read(ctx) == pytest.approx(1.5e-4)
    assert load_module(METRICS / "decide_device_ms.service.py").read(ctx) == pytest.approx(2e-4)
    assert load_module(METRICS / "device_idle_share.twin.py").read(ctx) == pytest.approx(60.0)


def test_empty_device_reads_nothing():
    s = bench.summarize(profile([], [ev("window", 0, 1000)]))
    ctx = {"trace": s, "result": {"attempted": 3}, "traffic": {"ticks": 1}}
    for name in ("device_idle_share.service", "decide_device_ms.service", "window_sim_ms.twin"):
        assert load_module(METRICS / f"{name}.py").read(ctx) is None


def test_missing_window_is_an_error():
    with pytest.raises(ValueError):
        bench.summarize(profile([], [ev("call", 0, 10)]))


def test_recorded_service_trace():
    from jax.profiler import ProfileData

    s = bench.summarize(ProfileData.from_file(str(DATA)))
    assert s.chips == ["/device:TPU:0"]
    # Independent sums over the raw events: every op lies inside the
    # window, the busy time is at most the op time, and self times add up
    # to the busy time of one line of nested events.
    assert all(s.window[0] <= a <= b <= s.window[1] for _, a, b in s.ops)
    total = sum(b - a for _, a, b in s.ops) * 1e-9
    assert 0 < s.busy_s <= total
    assert sum(s.self_ns) * 1e-9 == pytest.approx(s.busy_s, rel=1e-9)
    assert 0.0 < s.idle_share() < 1.0
    gaps = s.idle_gaps()
    assert sum(g for _, g in gaps) == pytest.approx(s.window_s - s.busy_s, rel=1e-9)
    assert {name for name, _ in gaps} <= set(bench.HOST_SPANS) | {"other"}
    b = s.breakdown()
    assert len(b["device_ops"]) == 10 and len(b["idle_gaps"]) <= 10
    assert any("gain_topr" in name for name, _ in b["device_ops"])
