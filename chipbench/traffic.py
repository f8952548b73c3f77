"""The one traffic generator: every mix is a data file under ``traffic/``.

A mix gives the spread of the lanes' mean external rates
(``lane_rate: [low, high]``, tuples/s), how that rate moves in time
(``trace``), the control period (``tick_seconds``), how long a
measurement counts arrivals (``measure_seconds``) and how often a lane
reports (``report_every`` ticks).  The twin mix adds the simulated step
(``dt``) and the horizon (``ticks``).

Each lane b of a fleet of B carries its own mean rate and its own phase
within the trace's period.  Both are midpoint grids over B (the rates
uniform on ``[low, high]``), permuted by the seed, so every seed offers
the same multiset of rates and phases (the same work) to different
lanes.  The seed also drives the Poisson counts; it never changes a
lane's graph, budget or deadline.

Traces: ``flat`` holds each lane at its mean rate; ``diurnal`` is
``max(mean + amplitude * sin(2 pi t / period), 0)``, copied from
``ArrivalTrace`` (the repo's ``streaming/scenarios.py``).
"""

from __future__ import annotations

import math

import numpy as np

# Offered-load fixed point: a chain settles in N rounds, a leaking
# self-loop geometrically (FPD's p = 0.3 leaves 0.3**64 after 64).
_FIXED_POINT_ROUNDS = 64


def rate_at(trace: dict, mean: np.ndarray, t: np.ndarray) -> np.ndarray:
    """Each lane's rate at times ``t`` (seconds), given its ``mean``;
    broadcasts like ``mean + t``."""
    kind = trace["kind"]
    if kind == "flat":
        return np.broadcast_to(mean, np.broadcast_shapes(np.shape(mean), np.shape(t)))
    if kind == "diurnal":
        wave = np.sin(2.0 * math.pi * t / float(trace["period"]))
        return np.maximum(mean + float(trace["amplitude"]) * wave, 0.0)
    raise ValueError(f"unknown trace kind {kind!r}")


def lane_profile(traffic: dict, b: int, seed: int) -> tuple[np.ndarray, np.ndarray]:
    """Per-lane ``(mean rate, phase)``: midpoint grids over ``lane_rate``
    and over the trace's period (none for ``flat``), each permuted by the
    seed."""
    rng = np.random.default_rng(np.random.SeedSequence([seed, 0x10AD]))
    lo, hi = traffic["lane_rate"]
    grid = (np.arange(b) + 0.5) / b
    mean = lo + (hi - lo) * grid[rng.permutation(b)]
    period = float(traffic["trace"].get("period", 0.0))
    phase = period * grid[rng.permutation(b)]
    return mean, phase


def external_rates(cfg: dict, traffic: dict, mean, phase, t: float) -> np.ndarray:
    """``[B, N]`` external arrival rates at time ``t``: each lane's rate
    split over the sources by their declared shares."""
    r = rate_at(traffic["trace"], mean, t + phase)  # [B]
    return r[:, None] * source_shares(cfg)


def source_shares(cfg: dict) -> np.ndarray:
    """``[N]`` share of the schedule each operator receives from outside."""
    names = [op["name"] for op in cfg["operators"]]
    share = np.zeros(len(names))
    total = sum(cfg["sources"].values())
    for name, rate in cfg["sources"].items():
        share[names.index(name)] = rate / total
    return share


def routing_matrix(cfg: dict) -> np.ndarray:
    """``[N, N]`` expected multiplicities ``P[i, j]`` of edge i -> j."""
    names = [op["name"] for op in cfg["operators"]]
    p = np.zeros((len(names), len(names)))
    for src, dst, mult in cfg["edges"]:
        p[names.index(src), names.index(dst)] += mult
    return p


def offered_rates(ext: np.ndarray, routing: np.ndarray, cap: np.ndarray) -> np.ndarray:
    """``[B, N]`` rates offered at each operator when each serves at most
    ``cap``: ``x = ext + P^T min(x, cap)``, iterated to its fixed point."""
    x = ext
    for _ in range(_FIXED_POINT_ROUNDS):
        nxt = ext + np.minimum(x, cap) @ routing
        if np.array_equal(nxt, x):
            break
        x = nxt
    return x


class ServiceTraffic:
    """Measurement batches for the decide service, one per tick.

    A lane that reports this tick sends Poisson counts over
    ``measure_seconds`` at the rates its graph offers under its current
    allocation; drops appear where the counted rate exceeds capacity.  A
    lane that does not report resends its last batch bitwise.  Lane b
    reports on ticks t with ``(t + b) % report_every == 0``.
    """

    def __init__(self, cfg: dict, traffic: dict, b: int, seed: int):
        self.cfg, self.traffic, self.b = cfg, traffic, b
        self.routing = routing_matrix(cfg)
        self.mu = np.array([op["mu"] for op in cfg["operators"]])
        self.mean, self.phase = lane_profile(traffic, b, seed)
        self.rng = np.random.default_rng(np.random.SeedSequence([seed, 0x5E41]))
        self.every = int(traffic["report_every"])
        self.lanes = np.arange(b)
        n = len(self.mu)
        self.mu_hat = np.broadcast_to(self.mu.astype(np.float32), (b, n)).copy()
        self.src = source_shares(cfg) > 0
        self.last = None

    def batch(self, tick: int, k: np.ndarray) -> tuple:
        """``(lam_hat, mu_hat, drop_hat, lam0_hat)`` float32 for ``tick``
        given the allocation ``k [B, N]`` in force."""
        period = float(self.traffic["measure_seconds"])
        t = tick * float(self.traffic["tick_seconds"])
        ext = external_rates(self.cfg, self.traffic, self.mean, self.phase, t)
        cap = self.mu * np.maximum(k, 1)
        x = offered_rates(ext, self.routing, cap)
        lam = self.rng.poisson(x * period) / period
        drop = np.maximum(lam - cap, 0.0)
        lam0 = (lam - drop)[:, self.src].sum(axis=-1)
        fresh = (lam.astype(np.float32), drop.astype(np.float32), lam0.astype(np.float32))
        if self.last is None or self.every == 1:
            self.last = fresh
        else:
            due = (tick + self.lanes) % self.every == 0
            self.last = tuple(
                np.where(due.reshape((-1,) + (1,) * (f.ndim - 1)), f, old)
                for f, old in zip(fresh, self.last)
            )
        lam, drop, lam0 = self.last
        return lam, self.mu_hat, drop, lam0


def twin_arrivals(cfg: dict, traffic: dict, b: int, seed: int) -> np.ndarray:
    """``[steps, B, N]`` external arrival counts per simulated step for the
    whole horizon: Poisson at the schedule's rate at each step's midpoint."""
    dt = float(traffic["dt"])
    steps = int(round(traffic["ticks"] * traffic["tick_seconds"] / dt))
    mean, phase = lane_profile(traffic, b, seed)
    rng = np.random.default_rng(np.random.SeedSequence([seed, 0xA881]))
    t = ((np.arange(steps) + 0.5) * dt)[:, None]
    share = source_shares(cfg)
    rates = rate_at(traffic["trace"], mean, t + phase)  # [steps, B]
    ext = np.zeros((steps, b, len(share)), dtype=np.float32)
    for i in np.flatnonzero(share):
        ext[:, :, i] = rng.poisson(rates * share[i] * dt)
    return ext
