"""The decide service: one closed-loop caller ticks the fleet's decide.

Each tick the host makes the fleet's ``[B, N]`` measurement batch, hands
it to the program's ``make_decide_jax`` decide and waits for the action
codes, the next allocations and the applied flags on the host.  The next
tick's ``k_current`` is the allocation this tick returned.  A tick is
timed from the hand-off to the three outputs in host memory; E[T] (and,
when compacting, the repriced mask) is fetched after the clock stops.
"""

from __future__ import annotations

import time

import numpy as np

from chipbench import bench, check, fleet, traffic as tr


class Cell:
    compact = False

    def __init__(self, cfg: dict, traffic: dict, seed: int, lanes: int):
        from repro.core.controller import make_decide_jax

        self.cfg, self.traffic, self.lanes = cfg, traffic, lanes
        static, params = fleet.controller(cfg, lanes)
        self.decide = make_decide_jax(
            static, params, pause_seconds=float(cfg["scheduler"]["pause_seconds"]),
            compact=True if self.compact else None,
        )
        self.reseed(seed)

    def reseed(self, seed: int) -> None:
        """New traffic from ``seed`` on the same compiled decide."""
        self.gen = tr.ServiceTraffic(self.cfg, self.traffic, self.lanes, seed)
        self.k = fleet.k0(self.cfg, self.lanes)
        self.t = 0
        self.cache = self.decide.init_cache() if self.compact else None
        self.sample_rng = np.random.default_rng(np.random.SeedSequence([seed, 0xC4EC]))
        self.generate_s = 0.0
        self.parts = []

    def tick(self, span) -> tuple[float, dict]:
        """One tick; returns its seconds and its record (inputs, outputs)."""
        import jax

        t_gen = time.perf_counter()
        with span("generate"):
            lam, mu, drop, lam0 = self.gen.batch(self.t, self.k)
        t0 = time.perf_counter()
        self.generate_s += t0 - t_gen
        with span("handoff"):
            args = jax.device_put((lam, mu, drop, lam0, self.k))
        t1 = time.perf_counter()
        with span("call"):
            if self.compact:
                out, repriced, self.cache = self.decide(*args, self.cache)
            else:
                out, repriced = self.decide(*args), None
        t2 = time.perf_counter()
        with span("fetch"):
            code, k_next, applied = jax.device_get((out[0], out[1], out[4]))
        t3 = time.perf_counter()
        seconds = t3 - t0
        self.parts.append((t1 - t0, t2 - t1, t3 - t2, t0 - t_gen))
        rec = {"lam": lam, "mu": mu, "drop": drop, "lam0": lam0, "k": self.k, "code": code,
               "k_next": k_next, "applied": applied, "et_cur": np.asarray(out[2]),
               "et_target": np.asarray(out[3])}
        if repriced is not None:
            rec["repriced_share"] = float(np.asarray(repriced).mean())
        self.k = k_next
        self.t += 1
        return seconds, rec

    def warm(self, span) -> None:
        """Compile, then run the mix's warm-up ticks."""
        for _ in range(int(self.traffic["warmup_ticks"])):
            self.tick(span)

    def window(self, span, *, seconds: float | None = None, ticks: int | None = None) -> dict:
        """Ticks back to back for ``seconds`` (or ``ticks`` ticks).  Keeps a
        uniform sample of ``check_ticks`` records (reservoir, drawn from
        the seed) for the comparison."""
        keep = int(self.traffic["check_ticks"])
        sample, tick_s, repriced = [], [], []
        self.generate_s = 0.0
        self.parts = []
        t_end = None if seconds is None else time.perf_counter() + seconds
        with span("window"):
            while (ticks is None or len(tick_s) < ticks) and (
                t_end is None or time.perf_counter() < t_end
            ):
                s, rec = self.tick(span)
                tick_s.append(s)
                if "repriced_share" in rec:
                    repriced.append(rec["repriced_share"])
                i = len(tick_s) - 1
                if i < keep:
                    sample.append(rec)
                else:
                    j = int(self.sample_rng.integers(0, i + 1))
                    if j < keep:
                        sample[j] = rec
        self.sample = sample
        out = {"tick_s": tick_s, "repriced_share": repriced, "attempted": len(tick_s),
               "generate_s": self.generate_s}
        # Diagnostics: each part of a tick (and the generator between
        # ticks), median and 95th percentile in ms over the window.
        for name, xs in zip(("handoff", "call", "fetch", "generate"), zip(*self.parts)):
            out[f"{name}_p50_ms"] = 1e3 * bench.percentile(xs, 50)
            out[f"{name}_p95_ms"] = 1e3 * bench.percentile(xs, 95)
        return out

    def numbers(self, dep) -> dict:
        return check.service_numbers(dep, self.sample)

    def release(self) -> None:
        del self.decide, self.cache
