"""The twin: the program's fused simulate -> measure -> decide -> apply
loop (``make_fused_loop``) over the mix's horizon, called back to back.

Arrivals for every lane and step are drawn once from the seed and held on
the device as the loop's argument.  Each call starts the fleet from the
configuration's ``k0`` and runs every tick of the horizon in one
program; it counts once its per-tick outputs and run aggregates are in
host memory.  Calls do not depend on each other, so the window keeps
about ``AHEAD_S`` seconds of them dispatched ahead of the one it waits
for: a host that stands still for less than that leaves the chip busy.
"""

from __future__ import annotations

import collections
import math
import time

import numpy as np

from chipbench import check, fleet, traffic as tr

# Seconds of calls dispatched ahead of the one being fetched, and the
# most calls that may be in flight behind it.
AHEAD_S, AHEAD_MAX = 4.0, 8


class Cell:
    def __init__(self, cfg: dict, traffic: dict, seed: int, lanes: int):
        self.cfg, self.traffic, self.lanes = cfg, traffic, lanes
        self.static, self.params = fleet.controller(cfg, lanes)
        self.dt = float(traffic["dt"])
        self.steps_per_tick = int(round(traffic["tick_seconds"] / self.dt))
        self.warmup_steps = int(traffic["warmup_ticks"]) * self.steps_per_tick
        self.k0 = fleet.k0(cfg, lanes)
        self.reseed(seed)

    def reseed(self, seed: int) -> None:
        """New arrivals from ``seed``; the loop is built again around them
        (same shapes, so the compiled program is reused)."""
        from repro.core.controller import make_fused_loop
        from repro.streaming.batchsim import BatchArrays

        self.loop = None
        b, n = self.k0.shape
        self.ext = tr.twin_arrivals(self.cfg, self.traffic, b, seed)
        st = self.static
        arrays = BatchArrays(
            ext=self.ext, routing=st.base_routing, mu=np.repeat(
                [[float(op["mu"]) for op in self.cfg["operators"]]], b, axis=0),
            group=st.group, alpha=st.alpha, cap_queue=np.full((b, n), np.inf),
            dt=self.dt, warmup_steps=self.warmup_steps, active=st.active,
        )
        self.loop, self.ticks = make_fused_loop(
            arrays, st, self.params, steps_per_tick=self.steps_per_tick,
            warmup_seconds=self.warmup_steps * self.dt,
        )
        self.last = None

    def dispatch(self, span):
        with span("call"):
            return self.loop(self.k0)

    def fetch(self, span, out) -> None:
        import jax

        with span("fetch"):
            self.last = jax.device_get(out)

    def warm(self, span) -> None:
        """Compile and run one call; the seconds from its dispatch (compiled)
        to its outputs on the host set how many calls the window keeps
        ahead."""
        out = self.dispatch(span)
        t0 = time.perf_counter()
        self.fetch(span, out)
        call_s = time.perf_counter() - t0
        self.ahead = min(AHEAD_MAX, max(1, math.ceil(AHEAD_S / max(call_s, 1e-9))))

    def window(self, span, *, seconds: float | None = None, calls: int | None = None) -> dict:
        """Calls dispatched back to back, ``self.ahead`` in flight behind
        the one being fetched, until ``seconds`` have passed (or ``calls``
        are sent).  Then nothing more is sent, every call sent is fetched,
        and the clock is read after the last: all of them count, over all
        of that time."""
        sent, pending, t0 = 0, collections.deque(), time.perf_counter()
        t_end = None if seconds is None else t0 + seconds
        with span("window"):
            while (calls is None or sent < calls) and (
                t_end is None or time.perf_counter() < t_end
            ):
                pending.append(self.dispatch(span))
                sent += 1
                if len(pending) > self.ahead:
                    self.fetch(span, pending.popleft())
            while pending:
                self.fetch(span, pending.popleft())
        elapsed = time.perf_counter() - t0
        return {"lane_ticks": sent * self.ticks * self.lanes, "elapsed_s": elapsed,
                "attempted": sent, "ahead": self.ahead}

    def numbers(self, dep) -> dict:
        return check.twin_numbers(
            dep, self.ext, self.k0, self.last, steps_per_tick=self.steps_per_tick,
            dt=self.dt, warmup_steps=self.warmup_steps)

    def release(self) -> None:
        del self.loop
