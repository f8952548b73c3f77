"""The decide service over a graph with keyed operators (NEXmark).

``service.py``'s closed-loop caller, with the configuration's graph built
here: an operator may be keyed (``scaling: "keyed"`` with a
``hot_share``), its k processors k hash partitions of one keyed stream
with the hot key on one of them (DESIGN.md §20).  The measurements count
drops where an operator's rate passes its capacity, and a keyed
operator's capacity is where its hot partition saturates,
``mu / (h + (1 - h)/k)``, not ``mu * k``.  The program's decide then
returns one more output, the keyed operators per lane whose least stable
allocation the hot partition set (``hot_floor``); it is fetched after the
tick's clock stops, as ``repriced`` is, and the window reports its share.
The comparison is against ``reference_keyed.py``.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from chipbench import fleet, reference_keyed, traffic as tr
from chipbench.drivers import service


def graph(cfg: dict):
    """The configuration's ``AppGraph``, keyed operators included."""
    from repro.api import AppGraph, Edge, OpDef

    return AppGraph(
        [OpDef(op["name"], mu=float(op["mu"]), scaling=op["scaling"],
               hot_share=op.get("hot_share")) for op in cfg["operators"]],
        [Edge(src, dst, multiplicity=float(m)) for src, dst, m in cfg["edges"]],
        dict(cfg["sources"]),
    )


def controller(cfg: dict, lanes: int):
    """``(ControllerStatic, ControllerParams)`` for ``lanes`` copies:
    ``fleet.controller``'s, with the keyed operators' hot shares."""
    pooled = dict(cfg, operators=[{"name": op["name"], "mu": op["mu"], "scaling": "replica"}
                                  for op in cfg["operators"]])
    static, params = fleet.controller(pooled, lanes)
    hot = graph(cfg).hot_shares()
    return dataclasses.replace(static, hot=np.repeat(hot[None], lanes, axis=0)), params


class KeyedTraffic(tr.ServiceTraffic):
    """``traffic.ServiceTraffic`` with a keyed operator's capacity at its
    hot partition."""

    def __init__(self, cfg: dict, traffic: dict, b: int, seed: int, hot: np.ndarray):
        super().__init__(cfg, traffic, b, seed)
        self.hot = hot

    def batch(self, tick: int, k: np.ndarray) -> tuple:
        """``ServiceTraffic.batch``, which counts drops past ``mu * k``, given
        for a keyed operator the multiple of mu its hot partition passes,
        ``1 / (h + (1 - h)/k)``."""
        h = np.nan_to_num(self.hot)
        k1 = np.maximum(k, 1)
        return super().batch(tick, np.where(np.isnan(self.hot), k1, 1.0 / (h + (1.0 - h) / k1)))


class Cell(service.Cell):
    def __init__(self, cfg: dict, traffic: dict, seed: int, lanes: int):
        from repro.core.controller import make_decide_jax

        self.cfg, self.traffic, self.lanes = cfg, traffic, lanes
        self.dep = reference_keyed.Deployment(cfg)
        static, params = controller(cfg, lanes)
        program = make_decide_jax(
            static, params, pause_seconds=float(cfg["scheduler"]["pause_seconds"]),
        )

        def decide(*args):
            # The five outputs every service caller reads; the counter waits
            # on the device until the tick's clock has stopped.
            *out, self.hot_floor = program(*args)
            return tuple(out)

        self.decide = decide
        self.reseed(seed)

    def reseed(self, seed: int) -> None:
        super().reseed(seed)
        self.gen = KeyedTraffic(self.cfg, self.traffic, self.lanes, seed, self.dep.hot)
        self.hot_floor_share = []

    def tick(self, span) -> tuple[float, dict]:
        seconds, rec = super().tick(span)
        keyed = int(self.dep.keyed.sum()) * self.lanes
        self.hot_floor_share.append(100.0 * int(np.asarray(self.hot_floor).sum()) / keyed)
        return seconds, rec

    def window(self, span, **kw) -> dict:
        self.hot_floor_share = []
        out = super().window(span, **kw)
        out["hot_floor_share"] = self.hot_floor_share
        return out

    def numbers(self, dep=None) -> dict:
        """The comparison against the keyed reference (``dep``, the harness's
        plain deployment, has no keyed operators)."""
        return reference_keyed.service_numbers(self.dep, self.sample)

    def control_numbers(self) -> dict:
        """The same comparison with the keyed reference's bfloat16 outputs in
        the program's place (the control a sound comparison must refuse)."""
        import jax.numpy as jnp

        recs = []
        for r in self.sample:
            d = reference_keyed.decide(jnp, jnp.bfloat16, self.dep, r["lam"], r["mu"],
                                       r["drop"], r["lam0"], r["k"])
            recs.append(dict(r, **{key: np.asarray(d[key]) for key in
                                   ("code", "k_next", "et_cur", "et_target", "applied")}))
        return reference_keyed.service_numbers(self.dep, recs)
