"""Percentile and rate arithmetic on synthetic tick times: a stall moves
the 95th percentile and the rate, not the median."""

from __future__ import annotations

import pathlib

import numpy as np
import pytest

from chipbench import bench
from chipbench.run import load_module

METRICS = pathlib.Path(__file__).resolve().parents[1] / "metrics"


def reader(name):
    return load_module(METRICS / f"{name}.py").read


def ticks(stall_every=None, stall_s=0.0):
    t = [0.100 + 0.001 * (i % 7) for i in range(400)]
    if stall_every:
        t = [x + (stall_s if i % stall_every == 0 else 0.0) for i, x in enumerate(t)]
    return t


def test_percentiles_match_numpy():
    xs = ticks()
    for q in (50, 95):
        assert bench.percentile(xs, q) == pytest.approx(np.percentile(xs, q), rel=1e-12)


def test_stall_moves_p95_not_p50():
    p50, p95 = reader("tick_p50_ms"), reader("tick_p95_ms")
    calm = {"result": {"tick_s": ticks()}}
    stalled = {"result": {"tick_s": ticks(stall_every=10, stall_s=0.25)}}
    assert p50(stalled) == pytest.approx(p50(calm), rel=0.02)
    assert p95(stalled) > p95(calm) + 200.0
    assert p95(calm) == pytest.approx(106.0, abs=0.01)


def test_rate_counts_every_second_of_the_window():
    rate = reader("lane_ticks_per_s")
    calls = ticks()[:20]
    calm = {"result": {"lane_ticks": 20 * 16 * 16384, "elapsed_s": sum(calls)}}
    stalled = {"result": {"lane_ticks": 20 * 16 * 16384, "elapsed_s": sum(calls) + 1.0}}
    assert rate(calm) == pytest.approx(20 * 16 * 16384 / sum(calls))
    assert rate(stalled) < 0.7 * rate(calm)
    assert rate({"result": {"tick_s": calls}}) is None


def test_empty_windows_read_nothing():
    assert reader("tick_p50_ms")({"result": {"lane_ticks": 5}}) is None
    with pytest.raises(ValueError):
        bench.rate(10, 0.0)
