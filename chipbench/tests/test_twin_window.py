"""The twin's window with calls dispatched ahead: every call sent is
fetched and counted, the clock stops after the last fetch, and the
number in flight follows the warm call's time."""

from __future__ import annotations

import contextlib
import time

import numpy as np
import pytest

from chipbench.drivers import twin


class Pending:
    """Stands in for a dispatched call: its outputs are ready at ``due``."""

    def __init__(self, seconds, log):
        self.due, self.log = time.perf_counter() + seconds, log

    def __array__(self, dtype=None, copy=None):
        time.sleep(max(self.due - time.perf_counter(), 0.0))
        self.log.append(time.perf_counter())
        return np.zeros(1)


def cell(call_s):
    c = object.__new__(twin.Cell)
    c.ticks, c.lanes, c.k0, c.fetched = 16, 4, np.zeros((4, 3)), []
    c.loop = lambda k0: Pending(call_s, c.fetched)
    return c


def span(_name):
    return contextlib.nullcontext()


@pytest.mark.parametrize("call_s, ahead", [(0.05, twin.AHEAD_MAX), (3.0, 2), (5.0, 1)])
def test_calls_in_flight_follow_the_warm_call(monkeypatch, call_s, ahead):
    c = cell(0.0)
    c.loop = lambda k0: np.zeros(1)
    clock = iter([0.0, call_s])
    monkeypatch.setattr(twin.time, "perf_counter", lambda: next(clock))
    c.warm(span)
    assert c.ahead == ahead


def test_every_call_sent_is_fetched_and_counted():
    c = cell(0.02)
    c.ahead = 3
    r = c.window(span, seconds=0.1)
    assert r["attempted"] == len(c.fetched) >= 4
    assert r["lane_ticks"] == r["attempted"] * 16 * 4
    # The window's clock is read after the last fetch, which comes after
    # the time is up: the work sent counts over all of its time.
    assert r["elapsed_s"] >= 0.1


def test_a_fixed_number_of_calls():
    c = cell(0.0)
    c.ahead = 2
    assert c.window(span, calls=5)["attempted"] == 5
    assert len(c.fetched) == 5
