"""A configuration file -> the program's entry-point objects for B lanes.

Every lane runs the configuration's one graph with its one budget and
deadline, so the statics are one lane's, repeated: no Python loop over
the fleet, and programs that embed them are the same for every seed.
"""

from __future__ import annotations

import numpy as np


def graph(cfg: dict):
    """The configuration's ``AppGraph``."""
    from repro.api import AppGraph, Edge, OpDef

    return AppGraph(
        [OpDef(op["name"], mu=float(op["mu"]), scaling=op["scaling"]) for op in cfg["operators"]],
        [Edge(src, dst, multiplicity=float(m)) for src, dst, m in cfg["edges"]],
        dict(cfg["sources"]),
    )


def controller(cfg: dict, lanes: int):
    """``(ControllerStatic, ControllerParams)`` for ``lanes`` copies."""
    from repro.core.controller import ControllerParams, ControllerStatic

    one = ControllerStatic.from_graphs([graph(cfg)])
    rep = lambda x: np.repeat(x, lanes, axis=0)
    static = ControllerStatic(
        base_routing=rep(one.base_routing), group=rep(one.group), alpha=rep(one.alpha),
        active=rep(one.active), speed=rep(one.speed), n_ops=rep(one.n_ops),
        names=one.names * lanes,
    )
    s = cfg["scheduler"]
    full = lambda v, dtype=np.float64: np.full(lanes, v, dtype=dtype)
    params = ControllerParams(
        t_max=full(cfg["t_max"]),
        k_max=full(cfg["k_max"], np.int64),
        headroom=full(s["headroom"]),
        scale_in_hysteresis=full(s["scale_in_hysteresis"]),
        min_improvement=full(s["min_improvement"]),
        horizon_seconds=full(s["horizon_seconds"]),
        allocator=(s["allocator"],) * lanes,
        fused_decide=False,
    )
    return static, params


def k0(cfg: dict, lanes: int) -> np.ndarray:
    """``[B, N]`` int32 starting allocation."""
    return np.repeat(np.asarray([cfg["k0"]], dtype=np.int32), lanes, axis=0)
