"""FleetPlanner — cross-tenant Programs (4)/(6) on one shared pool.

The paper schedules ONE application against one cluster.  The fleet
setting (DESIGN.md §12) schedules M tenant graphs — each its own Jackson
network with its own arrival process and optionally its own real-time
constraint T_max — against one shared processor pool K_max:

    min   sum_m w_m * sum_i lam_{m,i} * E[T_{m,i}](k_{m,i})
    s.t.  sum_m sum_i k_{m,i} <= K_max,
          E[T_m](k_m) <= T_max_m             for tenants that declare one.

Because each tenant's objective is separable and convex in its own k
(paper Ineq. 5), the cross-tenant optimum is the same marginal-benefit
greedy as Algorithm 1 run over the *merged* gain tables: first every
tenant gets its Program-(6) minimum (its T_max floor, or the stability
floor when no T_max is declared), then the remaining budget goes one
processor at a time to the globally largest *weighted* gain ``w_m *
lam_i * (E[T_i](k) - E[T_i](k+1))`` — which the batched core collapses
to a top-R selection over the stacked ``[sum_m N_m, K]`` table
(core/batched.py, allocator.greedy_increments).

Weighting selects the fleet objective:

* ``objective="fair"`` (default) — ``w_m = 1 / lam0_m``: minimizes
  ``sum_m E[T_m]``, every tenant's mean sojourn counts equally regardless
  of its traffic volume.
* ``objective="throughput"`` — ``w_m = 1``: minimizes total tuple-seconds
  ``sum_m lam0_m * E[T_m]``; exactly Program (4) on the block-diagonal
  union of the tenant networks (tests exploit this equivalence).

``Tenant.weight`` multiplies on top (paying tenants, SLO tiers).

Overload semantics reuse PR 2's: when the per-tenant T_max floors alone
exceed the pool, the plan is flagged ``overloaded`` — the caller
(api.session.FleetSession) reacts like the single-tenant scheduler's
``"overloaded"`` action: ask the negotiator for ``needed_total``
immediately, no scale-in hysteresis, no cost/benefit gate — and the
planner still hands out the whole pool best-effort (weighted Program (4))
so queues drain as fast as the lease allows while capacity arrives.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field

import numpy as np

from .allocator import (
    AllocationResult,
    InsufficientResourcesError,
    greedy_increments,
    min_processors_table,
)
from .batched import gain_table
from .jackson import Topology

__all__ = ["Tenant", "FleetPlan", "FleetPlanner"]


@dataclass(frozen=True)
class Tenant:
    """One tenant: a declared graph (or a prebuilt/measured Topology), an
    optional per-tenant real-time constraint, and an optional objective
    weight multiplier (> 0; default 1)."""

    name: str
    graph: object | None = None  # repro.api.AppGraph (kept untyped: core < api)
    topology: Topology | None = None
    t_max: float | None = None
    weight: float = 1.0

    def __post_init__(self) -> None:
        if self.graph is None and self.topology is None:
            raise ValueError(f"tenant {self.name!r}: need a graph or a topology")
        if self.weight <= 0:
            raise ValueError(f"tenant {self.name!r}: weight must be > 0, got {self.weight}")

    def resolve(self, override: Topology | None = None) -> Topology:
        if override is not None:
            return override
        if self.topology is not None:
            return self.topology
        return self.graph.topology()


@dataclass(frozen=True)
class FleetPlan:
    """One cross-tenant allocation decision."""

    k: dict[str, np.ndarray]  # tenant -> per-operator allocation
    per_tenant: dict[str, AllocationResult]
    total: int  # processors handed out
    k_max: int  # pool size planned against
    needed_total: int  # sum of per-tenant Program-(6) floors
    overloaded: bool  # floors alone exceed the pool (PR-2 overload semantics)
    unmet: tuple[str, ...] = ()  # declared T_max not satisfied by this plan
    unreachable: tuple[str, ...] = ()  # T_max below the tenant's service floor
    objective: float = math.inf  # sum_m w_m * lam0_m * E[T_m]
    evaluations: int = 0  # table entries materialised

    def as_dict(self) -> dict:
        return {
            "k": {t: k.tolist() for t, k in self.k.items()},
            "expected_sojourn": {
                t: r.expected_sojourn for t, r in self.per_tenant.items()
            },
            "total": self.total,
            "k_max": self.k_max,
            "needed_total": self.needed_total,
            "overloaded": self.overloaded,
            "unmet": list(self.unmet),
            "unreachable": list(self.unreachable),
            "objective": self.objective,
        }


@dataclass
class FleetPlanner:
    """Solves the cross-tenant program on merged per-tenant gain tables."""

    tenants: list[Tenant]
    k_max: int
    objective: str = "fair"  # "fair" | "throughput"
    _names: list[str] = field(init=False, repr=False)

    def __post_init__(self) -> None:
        self.tenants = list(self.tenants)
        if not self.tenants:
            raise ValueError("fleet needs at least one tenant")
        self._names = [t.name for t in self.tenants]
        if len(set(self._names)) != len(self._names):
            dupes = sorted({n for n in self._names if self._names.count(n) > 1})
            raise ValueError(f"duplicate tenant names: {dupes}")
        if self.objective not in ("fair", "throughput"):
            raise ValueError(
                f"unknown objective {self.objective!r}; expected 'fair' or 'throughput'"
            )

    # ------------------------------------------------------------------ #
    def weight(self, tenant: Tenant, top: Topology) -> float:
        """Gain multiplier w_m for this tenant under the fleet objective
        (the FleetSession improvement gate reuses this so the two sides
        always score with the same formula).  A zero-traffic tenant gets
        the visit-count guard, not a division crash — an idle measurement
        window must not kill the fleet control loop."""
        base = (
            1.0 / max(top.lam0_total, 1e-300) if self.objective == "fair" else 1.0
        )
        return tenant.weight * base

    def plan(
        self,
        topologies: dict[str, Topology] | None = None,
        *,
        k_max: int | None = None,
    ) -> FleetPlan:
        """Solve the fleet program.  ``topologies`` overrides tenants'
        declared graphs with measured models (the FleetSession control
        loop passes the offered-load-clamped rebuilds here).

        Raises :class:`InsufficientResourcesError` when even the stability
        minima don't fit the pool (no finite-E[T] allocation exists).
        """
        resolved, ctx = self._floors(topologies, k_max)
        take = np.zeros(sum(top.n for _, top in resolved), dtype=np.int64)
        if ctx["budget"] > 0:
            rows, k_start, evals = self._gain_rows(
                resolved, ctx["starts"], ctx["budget"]
            )
            ctx["evals"] += evals
            take = greedy_increments(rows, k_start, ctx["budget"])
        return self._assemble(resolved, take, ctx)

    def plan_batched(
        self,
        topologies: dict[str, Topology] | None = None,
        *,
        k_max: int | None = None,
        mesh=None,
    ) -> FleetPlan:
        """:meth:`plan` with the merged greedy as ONE batched top-R
        selection (``kernels/gain_topr``) over the stacked tenant rows —
        the jit fleet solve of DESIGN.md §16.

        The Program-(6) floors and the gain tables are built by the same
        float64 numpy code as :meth:`plan`, and ``gain_topr`` implements
        exactly ``greedy_increments``'s threshold + row-major tie rule,
        so under ``jax.config.enable_x64`` the plan is bit-identical to
        the scalar path (tests/test_planner.py asserts equality; without
        x64 the float32 cast can resolve near-ties differently).

        ``mesh`` (1-D) runs the selection as a cross-device fleet
        reduction: the stacked rows are sharded over devices, each shard
        ``all_gather``s the merged gain table, solves the SAME global
        top-R (replicated, so every device agrees bitwise), and keeps its
        own rows' take — Programs (4)/(6) over the merged gain tables of
        a sharded tenant stack.
        """
        resolved, ctx = self._floors(topologies, k_max)
        take = np.zeros(sum(top.n for _, top in resolved), dtype=np.int64)
        if ctx["budget"] > 0:
            rows, k_start, evals = self._gain_rows(
                resolved, ctx["starts"], ctx["budget"]
            )
            ctx["evals"] += evals
            take = _merged_topr(rows, k_start, ctx["budget"], mesh=mesh)
        return self._assemble(resolved, take, ctx)

    # ------------------------------------------------------------------ #
    # Shared plan stages (scalar + batched solvers)
    # ------------------------------------------------------------------ #
    def _floors(
        self, topologies: dict[str, Topology] | None, k_max: int | None
    ) -> tuple[list, dict]:
        """Resolve tenants, compute Program-(6) floors, classify overload,
        and derive the residual budget — everything before the greedy."""
        k_max = self.k_max if k_max is None else k_max
        tops = topologies or {}
        resolved = [(t, t.resolve(tops.get(t.name))) for t in self.tenants]
        k_min = [top.min_feasible_allocation() for _, top in resolved]
        min_total = int(sum(int(k.sum()) for k in k_min))
        if min_total > k_max:
            raise InsufficientResourcesError(
                min_total, k_max, np.concatenate(k_min)
            )
        evals = 0

        # --- Program (6) floors: what each tenant needs for its T_max --- #
        floors: list[np.ndarray] = []
        unreachable: list[str] = []
        for (tenant, top), km in zip(resolved, k_min):
            if tenant.t_max is None:
                floors.append(km.astype(np.int64))
                continue
            try:
                need = min_processors_table(top, tenant.t_max)
                evals += need.evaluations
                floors.append(need.k.astype(np.int64))
            except InsufficientResourcesError:
                unreachable.append(tenant.name)
                floors.append(km.astype(np.int64))
        needed_total = int(sum(int(f.sum()) for f in floors))

        # --- Overload fast path: floors don't fit the pool -------------- #
        overloaded = needed_total > k_max
        starts = k_min if overloaded else floors  # best-effort vs floors-granted
        granted = int(sum(int(s.sum()) for s in starts))
        return resolved, {
            "k_max": k_max,
            "needed_total": needed_total,
            "overloaded": overloaded,
            "unreachable": unreachable,
            "starts": starts,
            "budget": k_max - granted,
            "evals": evals,
        }

    def _gain_rows(
        self, resolved: list, starts: list, budget: int
    ) -> tuple[np.ndarray, np.ndarray, int]:
        """Stacked weighted gain rows ``[sum_m N_m, width]`` + start
        columns — the merged table both solvers select from."""
        evals = 0
        k_start = np.concatenate([s.astype(np.int64) for s in starts])
        width = int(max(int(s.max()) for s in starts)) + budget
        rows = []
        for (tenant, top), s in zip(resolved, starts):
            k_hi = int(s.max()) + budget
            T, G = gain_table(top, k_hi)
            evals += T.size
            w = self.weight(tenant, top)
            Gw = np.full((top.n, width), -np.inf)
            Gw[:, :k_hi] = w * G
            rows.append(Gw)
        return np.vstack(rows), k_start, evals

    def _assemble(self, resolved: list, take: np.ndarray, ctx: dict) -> FleetPlan:
        k_out: dict[str, np.ndarray] = {}
        per_tenant: dict[str, AllocationResult] = {}
        unmet: list[str] = []
        objective = 0.0
        off = 0
        for (tenant, top), s in zip(resolved, ctx["starts"]):
            n = top.n
            k = np.asarray(s, dtype=np.int64) + take[off : off + n]
            off += n
            et = top.expected_sojourn(k)
            k_out[tenant.name] = k
            per_tenant[tenant.name] = AllocationResult(k, et, int(k.sum()), 0)
            if tenant.t_max is not None and not et <= tenant.t_max:
                unmet.append(tenant.name)
            w = self.weight(tenant, top)
            objective += w * top.lam0_total * et if math.isfinite(et) else math.inf
        return FleetPlan(
            k=k_out,
            per_tenant=per_tenant,
            total=int(sum(int(k.sum()) for k in k_out.values())),
            k_max=ctx["k_max"],
            needed_total=ctx["needed_total"],
            overloaded=ctx["overloaded"],
            unmet=tuple(unmet),
            unreachable=tuple(ctx["unreachable"]),
            objective=objective,
            evaluations=ctx["evals"],
        )


def _merged_topr(
    G: np.ndarray, k_start: np.ndarray, budget: int, *, mesh=None
) -> np.ndarray:
    """``greedy_increments``'s selection as one batched ``gain_topr`` call
    over the merged fleet rows (optionally as a cross-device reduction).

    Gathers the same ``[R, budget]`` candidate window the scalar greedy
    walks (rows start at each operator's floor; entries are finite there
    because floors sit at/above every stability minimum), then hands the
    whole fleet's budget to the globally largest positive gains in one
    top-R selection.  With ``mesh``, rows are sharded across devices and
    each shard ``all_gather``s the full table before solving — every
    device computes the identical global selection, then keeps its own
    rows (DESIGN.md §16 fleet reduction).
    """
    import jax.numpy as jnp

    from .controller import _topr_ops

    topr_ops = _topr_ops()
    r = G.shape[0]
    if budget <= 0:
        return np.zeros(r, dtype=np.int64)
    idx = k_start[:, None] + np.arange(budget)[None, :]
    if idx.max() >= G.shape[1]:
        raise ValueError(
            f"gain table too narrow: need column {int(idx.max())}, have {G.shape[1]}"
        )
    cand = G[np.arange(r)[:, None], idx]  # [R, budget]
    budget_arr = jnp.asarray([budget], dtype=jnp.int32)
    if mesh is None:
        take = topr_ops.gain_topr(jnp.asarray(cand[None]), budget_arr)[0]
        return np.asarray(take, dtype=np.int64)

    import jax
    from jax import lax
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    if len(mesh.axis_names) != 1:
        raise ValueError(f"fleet mesh must be 1-D; got axes {mesh.axis_names}")
    axis = mesh.axis_names[0]
    d = int(mesh.size)
    r_pad = -(-r // d) * d
    if r_pad > r:  # zero-gain rows are never selected
        cand = np.concatenate([cand, np.zeros((r_pad - r, budget))])

    def solve(local_rows):
        merged = lax.all_gather(local_rows, axis, axis=0, tiled=True)
        take_all = topr_ops.gain_topr(merged[None], budget_arr)[0]
        i0 = lax.axis_index(axis) * local_rows.shape[0]
        return lax.dynamic_slice_in_dim(take_all, i0, local_rows.shape[0])

    rows = P(axis, None)
    take = jax.shard_map(
        solve, mesh=mesh, in_specs=rows, out_specs=P(axis), check_vma=False,
    )(jax.device_put(cand, NamedSharding(mesh, rows)))
    return np.asarray(take[:r], dtype=np.int64)
