"""DRSSession — one AppGraph bound to one backend (DESIGN.md §3).

A session owns the whole measure -> model -> rebalance loop that every
call site used to assemble by hand: scheduler construction (names, routing
matrix, scaling lists all derived from the graph), measurer wiring,
negotiator hookup, tick driving, and decision application.  The same
``AppGraph`` binds unmodified to:

* :class:`EngineBackend` — the live micro-batch ``StreamEngine`` (worker
  threads, real wall-clock measurements);
* :class:`DESBackend` — the discrete-event ``NetworkSimulator`` (simulated
  time, statistically tight model validation), including the group-scaled
  chip-gang conversion the serving router used to hand-roll.

Typical use::

    session = graph.bind("engine", config=SchedulerConfig(k_max=6))
    session.start({"extract": 1, "match": 2, "aggregate": 1})
    ...inject tuples...
    session.tick()          # pulls measurements, decides, applies rescale
    session.drain(); session.stop()

    report = graph.bind("des", seed=3, horizon=2000.0).simulate(k)
"""

from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Any, Mapping, Sequence

import numpy as np

from ..core import controller as ctl
from ..core.allocator import AllocationResult, InsufficientResourcesError, allocate
from ..core.jackson import Topology
from ..core.measurer import Measurer, MeasurementBatch, stack_snapshots
from ..core.negotiator import Negotiator
from ..core.planner import FleetPlan, FleetPlanner, Tenant
from ..core.rebalance import ExecutableCache, RebalanceCostModel
from ..core.scheduler import DRSScheduler, SchedulerConfig, SchedulerDecision
from .graph import AppGraph, GraphValidationError

__all__ = [
    "DRSSession",
    "EngineBackend",
    "DESBackend",
    "FleetSession",
    "FleetDecision",
    "ScenarioRunner",
    "ScenarioReport",
]


def _group_effective_services(top: Topology, k_vec: np.ndarray):
    """Convert group-scaled operators for the DES: one fast server at
    ``mu * k * eff(k)`` instead of k parallel servers (mirrors
    ``OperatorSpec.scaling == "group"``; DESIGN.md §2)."""
    from ..streaming.des import ServiceProcess

    services, k_eff = [], []
    for i, op in enumerate(top.operators):
        k_i = int(k_vec[i])
        if op.scaling == "group":
            eff = 1.0 / (1.0 + op.group_alpha * (k_i - 1))
            services.append(ServiceProcess(rate=op.mu * k_i * eff))
            k_eff.append(1)
        else:
            services.append(ServiceProcess(rate=op.mu))
            k_eff.append(k_i)
    return services, np.asarray(k_eff, dtype=np.int64)


class EngineBackend:
    """Live StreamEngine behind the backend protocol.

    ``queue_capacity`` bounds every operator queue (``None`` = unbounded)
    and ``overload_policy`` (``"block"`` | ``"shed-newest"`` |
    ``"shed-oldest"``, or an :class:`~repro.streaming.overload.OverloadPolicy`)
    decides what happens when one fills — DESIGN.md §11.
    """

    kind = "engine"

    def __init__(
        self,
        graph: AppGraph,
        *,
        queue_capacity: int | None = 10_000,
        overload_policy: Any = "block",
    ):
        from ..streaming.engine import Operator, StreamEngine

        missing = [op.name for op in graph.ops if op.fn is None]
        if missing:
            raise GraphValidationError(
                f"engine backend needs a compute fn on every operator; "
                f"missing: {missing} (attach with AppGraph.with_fns)"
            )
        self.graph = graph
        self.engine = StreamEngine(
            [Operator(op.name, op.fn) for op in graph.ops],
            queue_capacity=queue_capacity,
            overload_policy=overload_policy,
        )
        self.measurer: Measurer = self.engine.measurer

    def start(self, k: Mapping[str, int]) -> None:
        self.engine.start(dict(k))

    def apply_allocation(self, k: Mapping[str, int]) -> None:
        self.engine.scale_to(dict(k))

    def allocation(self) -> dict[str, int]:
        return self.engine.k()

    def inject(
        self, payload: Any, source: str | None = None, *, timeout: float | None = None
    ) -> int | None:
        if source is None:
            srcs = self.graph.source_names
            if len(srcs) != 1:
                raise GraphValidationError(
                    f"graph has {len(srcs)} sources {srcs}; pass source= explicitly"
                )
            source = srcs[0]
        return self.engine.inject(source, payload, timeout=timeout)

    def drain(self, timeout: float = 10.0) -> bool:
        return self.engine.drain(timeout=timeout)

    def stop(self) -> None:
        self.engine.stop()

    @property
    def completed_sojourns(self) -> list[float]:
        return self.engine.completed_sojourns

    def drop_counts(self) -> dict[str, int]:
        """Cumulative tuples shed per operator (overload policy drops)."""
        return self.engine.drop_counts()


class DESBackend:
    """NetworkSimulator behind the backend protocol (simulated time)."""

    kind = "des"

    def __init__(
        self,
        graph: AppGraph,
        *,
        seed: int = 0,
        horizon: float = 120.0,
        warmup: float = 10.0,
        network_delay: float = 0.0,
        arrival_kind: str | None = None,
        arrival_kw: Mapping[str, float] | None = None,
        measurer: Measurer | None = None,
        queue_capacity: int | None = None,
        overload_policy: Any = "shed-newest",
    ):
        self.graph = graph
        self.seed = seed
        self.horizon = horizon
        self.warmup = warmup
        self.network_delay = network_delay
        self.arrival_kind = arrival_kind or graph.arrival_kind
        # Extra ArrivalProcess parameters for every source — required for
        # the modulated kinds, e.g. bind("des", arrival_kind="mmpp",
        # arrival_kw={"rate2": 50.0, "switch01": 0.2, "switch10": 0.8}) or
        # arrival_kind="burst" with rate2/burst_every/burst_length.
        self.arrival_kw = dict(arrival_kw or {})
        self.measurer = measurer
        self.queue_capacity = queue_capacity
        self.overload_policy = overload_policy

    # The DES is batch-simulated, not tick-driven: the live control-loop
    # protocol fails with a pointer to simulate() instead of AttributeError.
    def _not_live(self, method: str):
        raise GraphValidationError(
            f"DES backend is batch-simulated; {method}() is only available on "
            "the engine backend — use simulate(k, rebalance_to=, rebalance_at=) "
            "to run allocation changes in simulated time"
        )

    def start(self, k):
        self._not_live("start")

    def apply_allocation(self, k):
        self._not_live("apply_allocation")

    def allocation(self):
        self._not_live("allocation")

    def inject(self, payload, source=None):
        self._not_live("inject")

    def drain(self, timeout: float = 10.0):
        self._not_live("drain")

    def stop(self):
        self._not_live("stop")

    @property
    def completed_sojourns(self):
        self._not_live("completed_sojourns")

    def simulator(
        self,
        k: Mapping[str, int] | Sequence[int] | np.ndarray,
        *,
        seed: int | None = None,
        horizon: float | None = None,
        warmup: float | None = None,
    ):
        """Build a NetworkSimulator for allocation ``k`` (group ops are
        collapsed to single effective servers)."""
        from ..streaming.des import ArrivalProcess, NetworkSimulator, ServiceProcess, SimConfig

        graph = self.graph
        top = graph.topology()
        k_vec = graph.k_vector(k)
        services, k_eff = _group_effective_services(top, k_vec)
        # apply each op's declared DES service distribution, keeping the
        # (possibly group-effective) rate the helper computed
        for i, op in enumerate(graph.ops):
            if op.service_kind != "exponential" or op.service_cv != 1.0:
                services[i] = ServiceProcess(
                    rate=services[i].rate, kind=op.service_kind, cv=op.service_cv
                )
        arrivals = [
            ArrivalProcess(rate=float(top.lam0[i]), kind=self.arrival_kind,
                           **self.arrival_kw)
            for i in range(top.n)
        ]
        cfg = SimConfig(
            seed=self.seed if seed is None else seed,
            horizon=self.horizon if horizon is None else horizon,
            warmup=self.warmup if warmup is None else warmup,
            network_delay=self.network_delay,
            queue_capacity=self.queue_capacity,
            overload_policy=self.overload_policy,
        )
        return NetworkSimulator(
            top, k_eff, config=cfg, arrivals=arrivals, services=services,
            measurer=self.measurer,
        )

    def simulate(
        self,
        k: Mapping[str, int] | Sequence[int] | np.ndarray,
        *,
        rebalance_to: Mapping[str, int] | Sequence[int] | np.ndarray | None = None,
        rebalance_at: float | None = None,
        pause: float = 1.0,
        seed: int | None = None,
        horizon: float | None = None,
        warmup: float | None = None,
    ):
        """Run the DES under ``k``; optionally switch to ``rebalance_to``
        at ``rebalance_at`` (with a processing pause) mid-run."""
        graph = self.graph
        sim = self.simulator(k, seed=seed, horizon=horizon, warmup=warmup)
        if rebalance_to is not None and rebalance_at is not None:
            top = sim.top
            k2 = graph.k_vector(rebalance_to)
            services2, k2_eff = _group_effective_services(top, k2)
            for i, op in enumerate(top.operators):
                if op.scaling == "group":
                    sim.schedule_rate_change(rebalance_at, i, services2[i].rate)
            sim.rebalance_at(rebalance_at, k2_eff, pause=pause)
        return sim.run()


_BACKENDS = {"engine": EngineBackend, "des": DESBackend}


class DRSSession:
    """One AppGraph + one backend + the DRS control loop.

    Construction wires the scheduler from the graph (names, routing matrix,
    scaling modes — no positional hand-syncing) and the backend's measurer.
    ``tick()`` pulls, models, decides, and *applies* the decision to the
    backend; ``plan()``/``topology()`` expose the model side directly.
    """

    def __init__(
        self,
        graph: AppGraph,
        backend: EngineBackend | DESBackend,
        *,
        config: SchedulerConfig | None = None,
        negotiator: Negotiator | None = None,
        cost_model: RebalanceCostModel | None = None,
        executable_cache: ExecutableCache | None = None,
        on_decision=None,
        proactive=None,
    ):
        self.graph = graph
        self.backend = backend
        self.config = config or SchedulerConfig()
        self.negotiator = negotiator
        self.cost_model = cost_model
        self.executable_cache = executable_cache
        self.on_decision = on_decision
        self.proactive = proactive  # forecast/MPC mode (MPCConfig | True)
        self.scheduler: DRSScheduler | None = None

    # Construction ------------------------------------------------------ #
    @classmethod
    def bind(cls, graph: AppGraph, backend: Any = "des", **kwargs) -> "DRSSession":
        session_keys = ("config", "negotiator", "cost_model", "executable_cache", "on_decision", "proactive")
        session_kw = {k: kwargs.pop(k) for k in session_keys if k in kwargs}
        if isinstance(backend, str):
            try:
                backend_cls = _BACKENDS[backend]
            except KeyError:
                raise GraphValidationError(
                    f"unknown backend {backend!r}; expected one of {sorted(_BACKENDS)} "
                    "or a backend instance"
                ) from None
            backend = backend_cls(graph, **kwargs)
        elif kwargs:
            raise GraphValidationError(
                f"unexpected options for pre-built backend: {sorted(kwargs)}"
            )
        return cls(graph, backend, **session_kw)

    # Model side --------------------------------------------------------- #
    def topology(self, mu: Mapping[str, float] | None = None) -> Topology:
        return self.graph.topology(mu)

    def plan(
        self, *, k_max: int | None = None, t_max: float | None = None
    ) -> AllocationResult:
        """Program (4)/(6) on the declared graph (priors, not measurements)."""
        k_max = k_max if k_max is not None else self.config.k_max
        t_max = t_max if t_max is not None else self.config.t_max
        if k_max is None and t_max is None:
            raise GraphValidationError(
                "plan() needs a budget: pass k_max= or t_max=, or bind with "
                "config=SchedulerConfig(k_max=..., t_max=...)"
            )
        return allocate(self.topology(), k_max=k_max, t_max=t_max)

    def split(self, alloc: AllocationResult | Sequence[int] | np.ndarray) -> dict[str, int]:
        k = alloc.k if isinstance(alloc, AllocationResult) else alloc
        return self.graph.k_dict(k)

    # Control loop ------------------------------------------------------- #
    def _build_scheduler(self, k0: np.ndarray) -> DRSScheduler:
        scaling, group_alpha = self.graph.scaling_lists()
        return DRSScheduler(
            self.graph.names,
            self.graph.routing_matrix(),
            k0,
            self.config,
            measurer=self.backend.measurer,
            negotiator=self.negotiator,
            cost_model=self.cost_model,
            executable_cache=self.executable_cache,
            scaling=scaling,
            group_alpha=group_alpha,
            on_decision=self.on_decision,
            proactive=self.proactive,
        )

    def start(
        self, k0: Mapping[str, int] | Sequence[int] | np.ndarray | None = None
    ) -> dict[str, int]:
        """Start the backend under ``k0`` (default: the planned optimum)
        and arm the scheduler.  Returns the starting allocation."""
        if k0 is None:
            k0_vec = self.plan().k
        else:
            k0_vec = self.graph.k_vector(k0)
        self.scheduler = self._build_scheduler(k0_vec.copy())
        self.backend.start(self.graph.k_dict(k0_vec))
        # Anchor the measurer's pull clock so the first tick has a window.
        self.backend.measurer.pull(time.time())
        return self.graph.k_dict(k0_vec)

    def tick(self, now: float | None = None) -> SchedulerDecision:
        """One scheduler tick: pull -> model -> decide -> apply."""
        if self.scheduler is None:
            raise RuntimeError("session not started; call start() first")
        decision = self.scheduler.tick(now)
        if decision.action in (
            "rebalance", "scale_out", "scale_in", "overloaded", "proactive"
        ):
            # "overloaded" with no feasible target keeps the current k.
            if decision.k_target is not None:
                self.backend.apply_allocation(self.graph.k_dict(decision.k_target))
        return decision

    @property
    def allocation(self) -> dict[str, int]:
        if self.scheduler is not None:
            return self.graph.k_dict(self.scheduler.k_current)
        return self.backend.allocation()

    @property
    def history(self) -> list[SchedulerDecision]:
        return [] if self.scheduler is None else self.scheduler.history

    # Backend pass-throughs ---------------------------------------------- #
    def inject(
        self, payload: Any, source: str | None = None, *, timeout: float | None = None
    ) -> int | None:
        """Inject an external tuple.  Under a bounded queue with the
        ``block`` policy this backpressures the caller; returns ``None``
        when the tuple was shed at admission (DESIGN.md §11)."""
        if isinstance(self.backend, EngineBackend):
            return self.backend.inject(payload, source=source, timeout=timeout)
        return self.backend.inject(payload, source=source)

    def drain(self, timeout: float = 10.0) -> bool:
        return self.backend.drain(timeout=timeout)

    def stop(self) -> None:
        self.backend.stop()

    @property
    def completed_sojourns(self) -> list[float]:
        return self.backend.completed_sojourns

    def drop_counts(self) -> dict[str, int]:
        """Cumulative tuples shed per operator (engine backend)."""
        if not isinstance(self.backend, EngineBackend):
            raise GraphValidationError(
                "drop_counts() needs the engine backend; the DES reports "
                "drops on its SimResult (per_op_dropped / per_op_drop_rate)"
            )
        return self.backend.drop_counts()

    def simulate(self, k=None, **kwargs):
        """DES-mode: simulate allocation ``k`` (default: planned optimum)."""
        if not isinstance(self.backend, DESBackend):
            raise GraphValidationError(
                f"simulate() needs a DES backend, have {self.backend.kind!r}"
            )
        if k is None:
            k = self.plan().k
        return self.backend.simulate(k, **kwargs)

    def run(self, k=None, **kwargs):
        """One-call entry point: DES -> :meth:`simulate`; engine ->
        :meth:`start` (then inject/tick/drain at your own pace)."""
        if isinstance(self.backend, DESBackend):
            return self.simulate(k, **kwargs)
        return self.start(k)


# --------------------------------------------------------------------------- #
# Fleet: several sessions against one shared pool
# --------------------------------------------------------------------------- #
@dataclass(frozen=True)
class FleetDecision:
    """One fleet control tick's outcome."""

    t: float
    # "none" | "rebalance" | "scale_in" | "overloaded" | "infeasible"
    action: str
    k_max: int
    plan: FleetPlan | None
    # tenant -> name-keyed allocation actually in force after the tick
    k: dict
    overloaded_tenants: tuple = ()
    objective_current: float = float("inf")
    reason: str = ""


class FleetSession:
    """Several :class:`DRSSession` tenants scheduled against ONE pool.

    Where a ``DRSSession`` runs the paper's control loop for one graph, a
    ``FleetSession`` owns the cross-tenant loop (DESIGN.md §12): every
    tick it pulls each tenant's measurements, rebuilds each tenant's model
    (reusing the per-tenant scheduler's offered-load clamping when a
    tenant is overloaded), and solves the merged Program (4)/(6) with
    :class:`~repro.core.planner.FleetPlanner` — per-tenant ``T_max`` come
    from each session's ``SchedulerConfig.t_max``.

    Overload reuses PR 2's semantics fleet-wide: any tenant with measured
    ``rho >= 1``, or Program-(6) floors exceeding the pool, makes the tick
    ``"overloaded"`` — the negotiator is asked for capacity immediately
    and the replan is applied with no improvement gate.

    Tenants may be model-only (never started): their declared priors feed
    the planner and allocations are tracked but not applied to a backend.

    Typical use::

        fleet = FleetSession(
            {"vld": vld_graph.bind("engine", config=SchedulerConfig(t_max=0.5)),
             "fpd": fpd_graph.bind("engine", config=SchedulerConfig(t_max=2.0))},
            k_max=64,
        )
        fleet.start()          # plans the pool split and starts each backend
        ...inject per tenant...
        fleet.tick()           # merged measure -> model -> replan -> apply
    """

    def __init__(
        self,
        sessions: Mapping[str, DRSSession],
        *,
        k_max: int | None = None,
        negotiator: Negotiator | None = None,
        objective: str = "fair",
        min_improvement: float = 0.05,
        headroom: float = 1.1,
        scale_in_hysteresis: float = 0.8,
        on_decision=None,
        solver: str = "scalar",
        mesh=None,
    ):
        if not sessions:
            raise GraphValidationError("fleet needs at least one session")
        if k_max is None and negotiator is None:
            raise GraphValidationError("fleet needs k_max= and/or negotiator=")
        if solver not in ("scalar", "batched"):
            raise GraphValidationError(
                f"unknown solver {solver!r}; expected 'scalar' or 'batched'"
            )
        if mesh is not None and solver != "batched":
            raise GraphValidationError("mesh= requires solver='batched'")
        self.sessions: dict[str, DRSSession] = dict(sessions)
        self._static_k_max = k_max
        self.negotiator = negotiator
        self.objective = objective
        self.min_improvement = min_improvement
        self.headroom = headroom
        self.scale_in_hysteresis = scale_in_hysteresis
        self.on_decision = on_decision
        # "batched" solves the merged greedy as one gain_topr selection
        # (FleetPlanner.plan_batched); mesh= additionally runs it as the
        # cross-device fleet reduction of DESIGN.md §16.
        self.solver = solver
        self.mesh = mesh
        self.history: list[FleetDecision] = []
        # tenant -> index-ordered allocation currently in force
        self._k: dict[str, np.ndarray] = {}

    # ------------------------------------------------------------------ #
    @property
    def k_max(self) -> int:
        if self.negotiator is not None:
            k = self.negotiator.k_max
            return max(k, self._static_k_max or 0)
        return self._static_k_max

    def tenants(self) -> list[Tenant]:
        return [
            Tenant(name=name, graph=s.graph, t_max=s.config.t_max)
            for name, s in self.sessions.items()
        ]

    def planner(self) -> FleetPlanner:
        return FleetPlanner(self.tenants(), self.k_max, objective=self.objective)

    def plan(self, *, k_max: int | None = None) -> FleetPlan:
        """Cross-tenant Programs (4)/(6) on the declared priors."""
        return self._plan_with(self.planner(), k_max=k_max)

    def _plan_with(
        self, planner: FleetPlanner, tops: dict | None = None,
        *, k_max: int | None = None,
    ) -> FleetPlan:
        """Every plan call routes here so the solver choice (scalar greedy
        vs batched/sharded top-R) applies uniformly across start/tick."""
        if self.solver == "batched":
            return planner.plan_batched(tops, k_max=k_max, mesh=self.mesh)
        return planner.plan(tops, k_max=k_max)

    def allocations(self) -> dict[str, dict[str, int]]:
        """tenant -> name-keyed allocation currently in force."""
        return {
            name: self.sessions[name].graph.k_dict(k) for name, k in self._k.items()
        }

    # ------------------------------------------------------------------ #
    def start(self) -> dict[str, dict[str, int]]:
        """Plan the pool split on priors and start every engine-backed
        tenant under its share (model-only/DES tenants are planned but not
        started).  With a negotiator, the initial lease is acquired here —
        stability minima first, then the Program-(6) floors."""
        try:
            plan = self.plan()
        except InsufficientResourcesError as e:
            if self.negotiator is None:
                raise
            self.negotiator.ensure(int(np.ceil(e.needed * self.headroom)))
            plan = self.plan()
        if self.negotiator is not None and plan.needed_total > self.k_max:
            self.negotiator.ensure(int(np.ceil(plan.needed_total * self.headroom)))
            plan = self.plan()
        for name, session in self.sessions.items():
            k = plan.k[name]
            self._k[name] = k.copy()
            if isinstance(session.backend, EngineBackend):
                session.start(k)
            else:
                # Arm the model side so tick() can track without a backend.
                session.scheduler = session._build_scheduler(k.copy())
        return self.allocations()

    def stop(self) -> None:
        for session in self.sessions.values():
            if isinstance(session.backend, EngineBackend):
                session.stop()

    # ------------------------------------------------------------------ #
    def _measured_topologies(self, now: float) -> tuple[dict, list[str]]:
        """Per-tenant measured model rebuilds + overloaded tenant names.

        The per-tenant measurer pulls stay in Python (live probes), but
        the model plane is batched: the snapshots are stacked into one
        :class:`~repro.core.measurer.MeasurementBatch` and the §11
        overload trigger + throughput-capped propagation run vectorized
        across the whole fleet (core/controller.py) before the per-tenant
        offered-load clamp.  Tenants without a complete snapshot (or
        never started) fall back to their declared priors by omission —
        the planner resolves those from the graph."""
        tops: dict[str, Topology] = {}
        hot: list[str] = []
        pulled: list[tuple[str, DRSScheduler]] = []
        snaps = []
        for name, session in self.sessions.items():
            sched = session.scheduler
            if sched is None:
                continue
            snap = sched.measurer.pull(now)
            sched._observe_instances()
            if not snap.complete():
                continue
            pulled.append((name, sched))
            snaps.append(snap)
        if not pulled:
            return tops, hot
        batch = stack_snapshots(snaps)
        b, n = batch.batch, batch.n
        routing = np.zeros((b, n, n))
        group = np.zeros((b, n), dtype=bool)
        alpha = np.zeros((b, n))
        active = np.zeros((b, n), dtype=bool)
        k_cur = np.zeros((b, n), dtype=np.int64)
        mu_eff = batch.mu_hat.copy()
        for bi, (_, sched) in enumerate(pulled):
            ni = len(sched.names)
            routing[bi, :ni, :ni] = sched.base_routing
            group[bi, :ni] = sched._group
            alpha[bi, :ni] = sched._alpha
            active[bi, :ni] = True
            k_cur[bi, :ni] = sched.k_current
            if sched.speed_factors is not None:
                mu_eff[bi, :ni] = mu_eff[bi, :ni] * sched.speed_factors
        over = ctl.overloaded_mask_batch(
            batch.lam_hat, mu_eff, batch.drop_hat, k_cur, group, alpha
        ) & active
        capped = ctl.capped_mask_batch(over, routing, active)
        for bi, (name, sched) in enumerate(pulled):
            ni = len(sched.names)
            mask = over[bi, :ni]
            if mask.any():
                hot.append(name)
            tops[name] = ctl.clamp_row(
                sched.names,
                sched.base_routing,
                batch.lam_hat[bi, :ni],
                batch.mu_hat[bi, :ni],
                float(batch.lam0_hat[bi]),
                mask,
                capped[bi, :ni],
                sched.scaling,
                sched.group_alpha,
                speed=sched.speed_factors,
            )
        return tops, hot

    def _objective_of(self, planner: FleetPlanner, tops: dict) -> float:
        """Fleet objective of the allocations currently in force — scored
        with the planner's own weighting so the improvement gate compares
        like with like."""
        if not self._k:
            return float("inf")
        total = 0.0
        for tenant in planner.tenants:
            k = self._k.get(tenant.name)
            if k is None:
                return float("inf")
            top = tenant.resolve(tops.get(tenant.name))
            et = top.expected_sojourn(k)
            w = planner.weight(tenant, top)
            total += w * top.lam0_total * et if np.isfinite(et) else float("inf")
        return total

    def _apply(self, plan: FleetPlan) -> dict:
        for name, session in self.sessions.items():
            k = plan.k[name]
            self._k[name] = k.copy()
            if session.scheduler is not None:
                session.scheduler.k_current = k.copy()
            if isinstance(session.backend, EngineBackend):
                session.backend.apply_allocation(session.graph.k_dict(k))
        return self.allocations()

    def tick(self, now: float | None = None) -> FleetDecision:
        """One fleet tick: pull every tenant, replan the pool, apply.

        Mirrors ``DRSScheduler.decide``'s gates at fleet level: an
        improvement below ``min_improvement`` keeps the current split;
        overload (any tenant's measured rho >= 1, or Program-(6) floors
        exceeding the pool) bypasses the gate and leases immediately."""
        now = time.time() if now is None else now
        tops, hot = self._measured_topologies(now)
        k_max = self.k_max
        planner = FleetPlanner(self.tenants(), k_max, objective=self.objective)
        try:
            plan = self._plan_with(planner, tops, k_max=k_max)
        except InsufficientResourcesError as e:
            if self.negotiator is not None:
                self.negotiator.ensure(int(np.ceil(e.needed * self.headroom)))
                k_max = self.k_max
                try:
                    plan = self._plan_with(planner, tops, k_max=k_max)
                except InsufficientResourcesError as e2:
                    return self._emit(FleetDecision(
                        now, "infeasible", k_max, None, self.allocations(),
                        tuple(hot), reason=str(e2),
                    ))
            else:
                return self._emit(FleetDecision(
                    now, "infeasible", k_max, None, self.allocations(),
                    tuple(hot), reason=str(e),
                ))

        overloaded = bool(hot) or plan.overloaded
        if overloaded and self.negotiator is not None and plan.needed_total > k_max:
            # PR-2 overload semantics: lease now, no hysteresis, no gate.
            self.negotiator.ensure(int(np.ceil(plan.needed_total * self.headroom)))
            if self.k_max > k_max:
                k_max = self.k_max
                plan = self._plan_with(planner, tops, k_max=k_max)
        elif (
            self.negotiator is not None
            and self._static_k_max is None
            # Mirror DRSScheduler: only scale in when the floors are real
            # latency targets — every tenant must declare a T_max, or the
            # "need" is just the stability minimum and releasing to it
            # would degrade tenants that never asked for a budget cut.
            and all(t.t_max is not None for t in planner.tenants)
            and plan.needed_total > 0
            and np.ceil(plan.needed_total * self.headroom)
            < self.scale_in_hysteresis * k_max
        ):
            # Shrink the lease and the allocation together: replan at the
            # smaller pool and apply in the same tick, so the machines we
            # hand back are never still part of the split in force.
            target = int(np.ceil(plan.needed_total * self.headroom))
            self.negotiator.ensure(target)
            if self.k_max < k_max:
                cur_obj = self._objective_of(planner, tops)
                k_max = self.k_max
                plan = self._plan_with(planner, tops, k_max=k_max)
                self._apply(plan)
                return self._emit(FleetDecision(
                    now, "scale_in", k_max, plan, self.allocations(), tuple(hot),
                    cur_obj,
                    reason=f"floors need {plan.needed_total} (headroom {target}) "
                    f"<< leased; released to k_max={k_max}",
                ))

        cur_obj = self._objective_of(planner, tops)
        if overloaded:
            self._apply(plan)
            return self._emit(FleetDecision(
                now, "overloaded", k_max, plan, self.allocations(), tuple(hot),
                cur_obj,
                reason=f"overloaded tenants {hot}; floors need "
                f"{plan.needed_total} of {k_max}",
            ))
        improvement = (
            (cur_obj - plan.objective) / cur_obj
            if np.isfinite(cur_obj) and cur_obj > 0
            else float("inf")
        )
        unchanged = all(
            np.array_equal(self._k.get(n), plan.k[n]) for n in self.sessions
        )
        if unchanged or improvement < self.min_improvement:
            return self._emit(FleetDecision(
                now, "none", k_max, plan, self.allocations(), tuple(hot), cur_obj,
                reason=f"improvement {improvement:.1%} < {self.min_improvement:.0%}",
            ))
        self._apply(plan)
        return self._emit(FleetDecision(
            now, "rebalance", k_max, plan, self.allocations(), tuple(hot), cur_obj,
            reason=f"fleet objective {cur_obj:.4g} -> {plan.objective:.4g}",
        ))

    def _emit(self, d: FleetDecision) -> FleetDecision:
        self.history.append(d)
        if self.on_decision:
            self.on_decision(d)
        return d


# --------------------------------------------------------------------------- #
# Scenario matrix sweeps (DESIGN.md §13)
# --------------------------------------------------------------------------- #
@dataclass(frozen=True)
class ScenarioReport:
    """One scenario's outcome after a controlled (or fixed-k) sweep."""

    name: str
    actions: tuple  # scheduler action per tick, in order
    allocations: tuple  # name-keyed allocation in force after each tick
    k_final: dict
    provisioned_total: int  # sum of the final allocation
    optimal_total: int | None  # Program (4)/(6) total at the mean true topology
    deadline_miss_rate: float  # post-warmup windows with est. E[T] > t_max
    drop_rate: float  # post-warmup shed fraction of offered load
    mean_sojourn: float  # batchsim visit-sum E[T] estimate at k_final
    saturated: tuple  # operator names at/above capacity post-warmup
    # Per-tick time series (dict of equal-length lists): "t", "k_total"
    # (allocation in force after the tick = the per-tick provisioned
    # cost), "miss" (post-warmup deadline-miss mask), "sojourn", "warm",
    # and — in proactive mode — "mpc_used" / "confident".  None for an
    # uncontrolled sweep.
    trajectory: dict | None = None

    def as_dict(self) -> dict:
        return {
            "name": self.name,
            "actions": list(self.actions),
            "allocations": [dict(a) for a in self.allocations],
            "k_final": dict(self.k_final),
            "provisioned_total": self.provisioned_total,
            "optimal_total": self.optimal_total,
            "deadline_miss_rate": self.deadline_miss_rate,
            "drop_rate": self.drop_rate,
            "mean_sojourn": self.mean_sojourn,
            "saturated": list(self.saturated),
            "trajectory": self.trajectory,
        }


class ScenarioRunner:
    """Sweep a scenario matrix through the full measure -> model ->
    rebalance loop on the vectorized batch simulator (DESIGN.md §13/§14).

    Every ``tick_interval`` of simulated time the whole batch advances one
    window; the window aggregates become ONE stacked
    :class:`~repro.core.measurer.MeasurementBatch` fed to the batched
    controller (``core/controller.py``) — the *identical* decide math the
    live ``DRSScheduler`` shell runs, including the §11 overload
    semantics — and applied decisions change each scenario's allocation
    for the next window.  Per-scenario ``Negotiator`` leases are invoked
    as hooks at the batch boundary between windows.

    When every scenario has a static budget (``negotiated=False``) and
    ``backend="jax"``, the whole sweep — simulate, measure, decide,
    apply, for every tick — compiles to ONE jit program
    (:func:`repro.core.controller.make_fused_loop`); ``fused=False``
    forces the window-at-a-time float64 twin instead.
    ``controlled=False`` freezes ``k`` (pure simulation sweep).

    Reports per scenario: deadline-miss rate, drop rate, and provisioned
    vs Program-(4)/(6)-optimal resources at the trace's mean rate.
    """

    def __init__(
        self,
        scenarios: Sequence,
        *,
        tick_interval: float = 10.0,
        controlled: bool = True,
        backend: str = "numpy",
        interpret: bool = False,
        force_kernel: bool = False,
        fused: bool | None = None,
        fused_decide: bool = False,
        proactive=None,
        mesh=None,
        compact=None,
    ):
        from ..streaming.batchsim import BatchQueueSim
        from ..streaming.scenarios import map_distinct, pack_allocations, pack_scenarios

        self.scenarios = list(scenarios)
        self.tick_interval = tick_interval
        self.controlled = controlled
        self.backend = backend
        self.interpret = interpret
        self.force_kernel = force_kernel
        # The decide-dispatch knob (SchedulerConfig.fused_decide): route
        # the jit decide through kernels/decide_fused — note this is
        # orthogonal to `fused` below, which fuses the *loop* over ticks.
        self.fused_decide = bool(fused_decide)
        # Device mesh for the fused loop (DESIGN.md §16): shard the batch
        # axis across devices.  Only the fused path consumes it — the
        # window-at-a-time twin is a numpy debugging surface.
        self.mesh = mesh
        # Trigger-gated lane compaction (DESIGN.md §18): True or a
        # CompactionConfig turns on the sparse decide — exact memoization
        # on the fused path, the per-lane replay cache on the twin.
        # Output-invisible by construction: decisions stay bitwise equal
        # to the dense run, only the `repriced` diagnostic reveals it.
        self.compact = compact if compact not in (False,) else None
        # Forecast/MPC mode (DESIGN.md §15): True -> default MPCConfig;
        # an MPCConfig customizes predictor/horizon/gate knobs.
        if proactive is True:
            from ..forecast.mpc import MPCConfig

            proactive = MPCConfig()
        self.proactive_cfg = proactive
        self._proactive_ctl = None
        self.arrays = pack_scenarios(self.scenarios)
        self.sim = BatchQueueSim(
            self.arrays, backend=backend, interpret=interpret, force_kernel=force_kernel
        )
        self.k = pack_allocations(
            self.scenarios, map_distinct(self.scenarios, lambda s: s.plan_k0())
        )
        self.static = ctl.ControllerStatic.from_graphs(
            [s.graph for s in self.scenarios],
            speed=[s.speed_vector() for s in self.scenarios],
        )
        self.negotiators = [
            self._negotiator_for(s, self.k[bi, : s.graph.n])
            for bi, s in enumerate(self.scenarios)
        ]
        self._steps_per_tick = max(int(round(self.tick_interval / self.arrays.dt)), 1)
        can_fuse = (
            controlled
            and backend == "jax"
            and all(neg is None for neg in self.negotiators)
            and self.arrays.steps % self._steps_per_tick == 0
        )
        if fused is None:
            fused = can_fuse
        elif fused and not can_fuse:
            # Forcing the fused path past its preconditions would silently
            # change semantics (leases need Python hooks, controlled=False
            # must freeze k, a partial final window would be dropped).
            raise GraphValidationError(
                "fused=True requires controlled=True, backend='jax', no "
                "negotiated scenarios, and a horizon divisible by the tick "
                "interval; use fused=None for the automatic gate"
            )
        self.fused = fused
        if mesh is not None and not fused:
            raise GraphValidationError(
                "mesh= shards the fused loop's batch axis; it has no effect "
                "on the window-at-a-time path (pass fused=True or drop mesh)"
            )
        # Per-scenario decision parameters are static except the budgets,
        # which negotiator leases move between ticks — stack once here,
        # refresh only k_max in _params() (the tick hot loop).
        self._base_params = ctl.ControllerParams.stack(
            [
                SchedulerConfig(
                    k_max=None if neg is not None else s.k_max,
                    t_max=s.t_max,
                    tick_interval=self.tick_interval,
                    allocator=s.allocator,
                    fused_decide=self.fused_decide,
                )
                for s, neg in zip(self.scenarios, self.negotiators)
            ],
            [
                neg.k_max if neg is not None else s.k_max
                for s, neg in zip(self.scenarios, self.negotiators)
            ],
        )
        self.decisions: list[list[SchedulerDecision]] = [[] for _ in self.scenarios]
        self._miss = np.zeros(len(self.scenarios), dtype=np.int64)
        self._windows_warm = 0
        self._fused_result = None
        self._traj: list[dict[str, list]] = [
            {"t": [], "k_total": [], "miss": [], "sojourn": [], "warm": []}
            for _ in self.scenarios
        ]

    def _negotiator_for(self, s, k0: np.ndarray):
        """The scenario zoo's optional machine lease: ``negotiated``
        scenarios draw ``machine_size``-processor machines from a finite
        pool (speed-tagged when the scenario declares machine-class
        factors) instead of holding a static budget."""
        if not s.negotiated:
            return None
        from ..core.negotiator import Machine, Negotiator as _Neg, ResourcePool

        size = max(int(s.machine_size), 1)
        speed = s.speed_vector()
        mean_speed = 1.0 if speed is None else float(np.mean(speed))
        pool = ResourcePool(
            [
                Machine(f"m{i}", size, speed=mean_speed)
                for i in range(-(-s.k_max // size))
            ]
        )
        negotiator = _Neg(pool)
        negotiator.ensure(int(k0.sum()))
        return negotiator

    def _params(self) -> ctl.ControllerParams:
        """Per-scenario decision parameters with the budget re-resolved
        from each negotiator's current lease (the scalar ``_k_max`` rule)."""
        if all(neg is None for neg in self.negotiators):
            return self._base_params
        from dataclasses import replace

        return replace(self._base_params, k_max=np.array(
            [
                neg.k_max if neg is not None else s.k_max
                for s, neg in zip(self.scenarios, self.negotiators)
            ],
            dtype=np.int64,
        ))

    # ------------------------------------------------------------------ #
    def _window_measurement(self, w: dict) -> tuple[MeasurementBatch, np.ndarray]:
        """One stacked synthetic measurement from a window's aggregates.

        The sojourn estimate is NaN for a scenario that admitted no
        external tuples this window (no sojourn is defined; ``NaN >
        t_max`` is False, so idle trace troughs never register deadline
        misses).  ``mu_hat`` carries the reference-class priors — the
        controller applies the machine-class ``speed`` factors on the
        model side, mirroring the sim's scaled service capacity."""
        from ..streaming.batchsim import composed_wait, per_op_service_time, visit_sum_sojourn

        a = self.arrays
        span = w["span"]
        lam_hat = w["offered"] / span
        drop_hat = w["dropped"] / span
        mu_eff = a.mu if a.speed is None else a.mu * a.speed
        admitted = np.maximum(lam_hat - drop_hat, 0.0)
        wait = composed_wait(
            w["q_mean"], admitted, a.dt, span, self.k, a.mu, a.group, a.alpha,
            a.speed, a.ca2, a.cs2,
        )
        svc = per_op_service_time(w["capacity"], mu_eff, a.group)
        lam0 = np.maximum(w["ext_admitted"] / span, 0.0)
        sojourn = visit_sum_sojourn(admitted, wait, svc, lam0)
        return MeasurementBatch.from_rates(
            lam_hat, a.mu, lam0, sojourn, self.sim.now, drop_hat=drop_hat
        ), sojourn

    def _ensure_hooks(self):
        hooks = []
        for neg in self.negotiators:
            if neg is None:
                hooks.append(None)
            else:
                def hook(target: int, _neg=neg) -> int:
                    _neg.ensure(target)
                    return _neg.k_max
                hooks.append(hook)
        return hooks

    def _to_decision(self, bi: int, row: ctl.RowDecision, meas, error) -> SchedulerDecision:
        s = self.scenarios[bi]
        return SchedulerDecision(
            self.sim.now,
            row.action,
            row.k_next.copy(),
            row.k_target,
            s.k_max if error is not None else row.k_max,
            row.et_cur,
            row.et_target,
            float(meas.sojourn_hat[bi]),
            row.plan,
            row.reason,
        )

    def run(self) -> list[ScenarioReport]:
        if self.fused:
            return self._run_fused()
        a = self.arrays
        t_max = np.array(
            [np.nan if s.t_max is None else s.t_max for s in self.scenarios]
        )
        hooks = self._ensure_hooks()
        cstate = None
        if self.controlled and self.compact is not None:
            cstate = ctl.TwinCompactionState.create(
                len(self.scenarios), self.static.n
            )
        pc = None
        if self.controlled and self.proactive_cfg is not None:
            from ..forecast.mpc import ProactiveController

            pc = ProactiveController.create(
                len(self.scenarios), self.static.n, self.proactive_cfg,
                cap_queue=a.cap_queue, span=self._steps_per_tick * a.dt,
            )
            self._proactive_ctl = pc
            for tr in self._traj:
                tr["mpc_used"] = []
                tr["confident"] = []
        while self.sim.step_index < a.steps:
            w = self.sim.step_window(self.k, self._steps_per_tick)
            warm = w["t0"] >= self.scenarios[0].warmup
            if warm:
                self._windows_warm += 1
            meas, sojourn = self._window_measurement(w)
            with np.errstate(invalid="ignore"):
                miss_mask = (sojourn > t_max) & warm
            if warm:
                self._miss += miss_mask.astype(np.int64)
            if self.controlled:
                batch = ctl.tick_batch(
                    meas, self.k, self.static, self._params(), ensure=hooks,
                    proactive=pc, q_backlog=w["q_final"],
                    compact_state=cstate,
                )
                for bi, row in enumerate(batch.rows):
                    s = self.scenarios[bi]
                    self.decisions[bi].append(
                        self._to_decision(bi, row, meas, batch.errors[bi])
                    )
                    if row.applied:
                        self.k[bi, : s.graph.n] = row.k_next
            for bi, s in enumerate(self.scenarios):
                tr = self._traj[bi]
                tr["t"].append(float(self.sim.now))
                tr["k_total"].append(int(self.k[bi, : s.graph.n].sum()))
                tr["miss"].append(bool(miss_mask[bi]))
                tr["sojourn"].append(float(sojourn[bi]))
                tr["warm"].append(bool(warm))
                if pc is not None:
                    tr["mpc_used"].append(bool(pc.mpc_used[bi]))
                    tr["confident"].append(bool(pc.confident[bi]))
        return self.reports()

    def _run_fused(self) -> list[ScenarioReport]:
        """The one-program path: lax.scan over every control window, the
        decide compiled inline (negotiator-free scenarios only)."""
        from ..streaming.batchsim import BatchSimResult

        a = self.arrays
        run, n_ticks = ctl.make_fused_loop(
            a, self.static, self._params(),
            steps_per_tick=self._steps_per_tick,
            warmup_seconds=self.scenarios[0].warmup,
            interpret=self.interpret, force_kernel=self.force_kernel,
            proactive=self.proactive_cfg, mesh=self.mesh,
            compact=self.compact,
        )
        out = {key: np.asarray(v) for key, v in run(self.k).items()}
        self.k = out["k_final"].astype(np.int64)
        self._windows_warm = int(out["warm_windows"])
        self._miss = np.where(
            [s.t_max is not None for s in self.scenarios], out["miss"], 0
        ).astype(np.int64)
        if self.proactive_cfg is not None:
            for tr in self._traj:
                tr["mpc_used"] = []
                tr["confident"] = []
        t_max_arr = np.array(
            [np.nan if s.t_max is None else s.t_max for s in self.scenarios]
        )
        for ti in range(n_ticks):
            now = (ti + 1) * self._steps_per_tick * a.dt
            warm = (ti * self._steps_per_tick * a.dt) >= self.scenarios[0].warmup
            for bi, s in enumerate(self.scenarios):
                action = ctl.ACTIONS[int(out["codes"][ti, bi])]
                k_row = out["k"][ti, bi, : s.graph.n].astype(np.int64)
                # k_target only when the jit decide actually applied an
                # allocation (the twin's rule: an infeasible "overloaded"
                # row proposes nothing).
                applied = bool(out["applied"][ti, bi])
                self.decisions[bi].append(SchedulerDecision(
                    now, action, k_row, k_row if applied else None, s.k_max,
                    float(out["et_cur"][ti, bi]), float(out["et_target"][ti, bi]),
                    float(out["sojourn"][ti, bi]),
                    reason="fused jit decide",
                ))
                tr = self._traj[bi]
                soj = float(out["sojourn"][ti, bi])
                with np.errstate(invalid="ignore"):
                    missed = bool((soj > t_max_arr[bi]) and warm)
                tr["t"].append(now)
                tr["k_total"].append(int(k_row.sum()))
                tr["miss"].append(missed)
                tr["sojourn"].append(soj)
                tr["warm"].append(bool(warm))
                if self.proactive_cfg is not None:
                    tr["mpc_used"].append(bool(out["mpc_used"][ti, bi]))
                    tr["confident"].append(bool(out["confident"][ti, bi]))
        warm_steps = max(a.steps - a.warmup_steps, 0)
        self._fused_result = BatchSimResult(
            offered=out["offered"], served=out["served"], dropped=out["dropped"],
            ext_admitted=out["ext_admitted"], ext_offered=out["ext_offered"],
            q_final=out["q_final"], q_mean=out["q_int"] / max(warm_steps, 1),
            max_backlog=out["q_max"], span=warm_steps * a.dt, dt=a.dt,
        )
        return self.reports()

    def reports(self) -> list[ScenarioReport]:
        from ..core.allocator import InsufficientResourcesError, allocate
        from ..core.jackson import UnstableTopologyError
        from ..streaming.scenarios import map_distinct

        res = self._fused_result if self._fused_result is not None else self.sim.result()
        a = self.arrays
        sojourns = res.sojourn(self.k, a.mu, a.group, a.alpha, a.speed,
                               ca2=a.ca2, cs2=a.cs2)
        sat = res.saturated(self.k, a.mu, a.group, a.alpha, a.speed)

        def optimal_total(s):
            try:
                return allocate(s.mean_topology(), k_max=s.k_max, t_max=s.t_max).total
            except (InsufficientResourcesError, UnstableTopologyError):
                return None

        optimals = map_distinct(self.scenarios, optimal_total)
        out = []
        for bi, (s, optimal) in enumerate(zip(self.scenarios, optimals)):
            n = s.graph.n
            offered = float(res.offered[bi, :n].sum())
            dropped = float(res.dropped[bi, :n].sum())
            decs = self.decisions[bi]
            out.append(
                ScenarioReport(
                    name=s.name,
                    actions=tuple(d.action for d in decs),
                    allocations=tuple(
                        dict(zip(s.graph.names, map(int, d.k_current))) for d in decs
                    ),
                    k_final=dict(zip(s.graph.names, map(int, self.k[bi, :n]))),
                    provisioned_total=int(self.k[bi, :n].sum()),
                    optimal_total=None if optimal is None else int(optimal),
                    deadline_miss_rate=(
                        float(self._miss[bi] / self._windows_warm)
                        if (self._windows_warm and s.t_max is not None)
                        else float("nan")
                    ),
                    drop_rate=dropped / max(offered, 1e-300),
                    mean_sojourn=float(sojourns[bi]),
                    saturated=tuple(
                        nm for i, nm in enumerate(s.graph.names) if sat[bi, i]
                    ),
                    trajectory=self._traj[bi] if self._traj[bi]["t"] else None,
                )
            )
        return out
