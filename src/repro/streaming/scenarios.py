"""Scenario matrix — declarative, seed-deterministic workload scenarios
(DESIGN.md §13).

A :class:`Scenario` pins everything one simulated experiment needs —
AppGraph x arrival trace x service distribution x
:class:`~repro.streaming.overload.OverloadPolicy` x allocator choice x
seed — and compiles to either backend:

* :meth:`Scenario.simulator` -> the event DES (``NetworkSimulator``,
  high fidelity, scalar);
* :func:`pack_scenarios` -> :class:`~repro.streaming.batchsim.BatchArrays`
  for the vectorized batch simulator (hundreds of scenarios per second).

Two generator zoos make the matrix: **arrival traces** (:class:`ArrivalTrace`
— constant, diurnal sinusoid, flash-crowd step, 2-state MMPP, trace
replay) and the **random-topology zoo** (:func:`random_appgraph` — valid
``AppGraph``s with chains, splits, joins, and stability-respecting leaking
loops).  Everything is deterministic given the seed: the same
``Scenario`` produces bit-identical pre-sampled arrival arrays and DES
runs across processes, which is what lets the test suite enforce
DES-vs-model agreement as a regression surface and commit golden decision
traces (tests/golden/).
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field, replace
from typing import Mapping, Sequence

import numpy as np

from ..api.graph import AppGraph, Edge, OpDef
from .batchsim import BatchArrays, refuse_keyed
from .overload import OverloadPolicy

__all__ = [
    "ArrivalTrace",
    "Scenario",
    "random_appgraph",
    "scenario_matrix",
    "pack_scenarios",
    "pack_allocations",
    "control_trace",
    "vld_scenario",
    "fpd_scenario",
]


# --------------------------------------------------------------------------- #
# Arrival-trace zoo
# --------------------------------------------------------------------------- #
@dataclass(frozen=True)
class ArrivalTrace:
    """A deterministic rate schedule lambda_0(t) for one source operator.

    Kinds:

    * ``constant`` — ``rate`` throughout;
    * ``diurnal``  — sinusoid ``rate + amplitude * sin(2 pi t / period)``
      (clamped at 0), the day/night load curve;
    * ``flash``    — ``rate``, stepping to ``peak`` on ``[t_on, t_off)``
      (the Fig. 9/10 flash crowd);
    * ``mmpp``     — 2-state Markov-modulated rate: ``rate`` in state 0,
      ``peak`` in state 1, exponential switching at ``switch01`` /
      ``switch10`` per second.  The state path is sampled once from the
      scenario seed, so the *trace itself* is deterministic;
    * ``replay``   — an explicit measured-rate array ``samples`` covering
      the horizon at ``sample_dt`` spacing (held piecewise-constant,
      clipped at the ends).
    """

    kind: str = "constant"
    rate: float = 10.0
    peak: float | None = None
    amplitude: float = 0.0
    period: float = 60.0
    t_on: float = 0.0
    t_off: float = 0.0
    switch01: float = 0.1
    switch10: float = 0.1
    samples: tuple = ()
    sample_dt: float = 1.0

    _KINDS = ("constant", "diurnal", "flash", "mmpp", "replay")

    def __post_init__(self):
        if self.kind not in self._KINDS:
            raise ValueError(f"unknown trace kind {self.kind!r}; expected {self._KINDS}")
        if self.rate < 0:
            raise ValueError(f"trace rate must be >= 0, got {self.rate}")
        if self.kind in ("flash", "mmpp") and self.peak is None:
            raise ValueError(f"trace kind {self.kind!r} needs peak=")
        if self.kind == "replay" and not self.samples:
            raise ValueError("replay trace needs samples=")

    def rates(self, t_grid: np.ndarray, seed: int = 0) -> np.ndarray:
        """lambda_0 at each grid time — [T] float64, deterministic given
        (trace, seed)."""
        t = np.asarray(t_grid, dtype=np.float64)
        if self.kind == "constant":
            return np.full(t.shape, self.rate)
        if self.kind == "diurnal":
            return np.maximum(
                self.rate + self.amplitude * np.sin(2.0 * math.pi * t / self.period), 0.0
            )
        if self.kind == "flash":
            return np.where((t >= self.t_on) & (t < self.t_off), self.peak, self.rate)
        if self.kind == "replay":
            idx = np.clip((t / self.sample_dt).astype(np.int64), 0, len(self.samples) - 1)
            return np.asarray(self.samples, dtype=np.float64)[idx]
        # mmpp: sample the modulating state path once, from its own stream.
        rng = np.random.default_rng(np.random.SeedSequence([seed, 0x3A7E]))
        rates = np.empty(t.shape)
        state, t_next, now = 0, 0.0, float(t[0]) if t.size else 0.0
        sw = (self.switch01, self.switch10)
        t_next = now + (rng.exponential(1.0 / sw[0]) if sw[0] > 0 else math.inf)
        for i, ti in enumerate(t):
            while ti >= t_next:
                state = 1 - state
                s = sw[state]
                t_next += rng.exponential(1.0 / s) if s > 0 else math.inf
            rates[i] = self.rate if state == 0 else self.peak
        return rates

    def mean_rate(self, horizon: float, seed: int = 0, dt: float = 0.5) -> float:
        """Time-averaged rate over [0, horizon] (model-side lam0): the
        trapezoid integral of :meth:`rates` on a ``dt`` grid divided by
        the covered span — the contract the forecast predictors train
        against (tests/test_scenarios.py locks the <= 1e-9 agreement)."""
        span = max(horizon, dt)
        grid = np.arange(0.0, span + dt / 2.0, dt)
        r = self.rates(grid, seed)
        integral = 0.5 * (r[1:] + r[:-1]).sum() * dt
        return float(integral / (grid[-1] - grid[0]))

    def des_schedule(self, horizon: float, seed: int = 0, dt: float = 1.0):
        """(initial ArrivalProcess kwargs, [(t, rate), ...] mid-run changes)
        — how the event DES reproduces this trace.  ``flash`` and ``mmpp``
        map onto the DES's native ``burst``/``mmpp`` processes only when
        exact (single cycle / matching switch rates); every kind also has
        the generic piecewise-constant fallback used here: the rate grid
        at ``dt`` spacing becomes ``schedule_arrival_change`` calls."""
        if self.kind == "constant":
            return {"rate": self.rate}, []
        grid = np.arange(0.0, horizon + dt, dt)
        rates = self.rates(grid, seed)
        changes = []
        last = rates[0]
        for t, r in zip(grid[1:], rates[1:]):
            if r != last:
                changes.append((float(t), float(r)))
                last = r
        return {"rate": float(rates[0])}, changes


# --------------------------------------------------------------------------- #
# Random-topology zoo
# --------------------------------------------------------------------------- #
def random_appgraph(
    seed: int,
    *,
    n_ops: tuple[int, int] = (3, 7),
    p_split: float = 0.35,
    p_join: float = 0.35,
    p_loop: float = 0.3,
    target_rho: tuple[float, float] = (0.3, 0.8),
    lam0: float = 10.0,
    n_sources: int = 1,
) -> AppGraph:
    """A valid random :class:`AppGraph` with splits, joins, and leaking loops.

    Construction: a random topological spine guarantees every operator is
    reachable from a source; extra forward edges create joins (several
    in-edges) and splits (several out-edges, multiplicities summing to
    ~1); self-loops and back-edges are added with multiplicity small
    enough to keep the routing spectral radius below 0.9 (stability is
    then asserted by ``AppGraph`` itself at construction).  Service rates
    are set from the *solved* per-operator arrival rates so utilisation
    at a handful of processors lands inside ``target_rho`` — the zoo
    yields feasible Programs (4)/(6) by construction, not by rejection.
    """
    rng = np.random.default_rng(np.random.SeedSequence([seed, 0x70B0]))
    n = int(rng.integers(n_ops[0], n_ops[1] + 1))
    names = [f"op{i}" for i in range(n)]
    routing = np.zeros((n, n))
    n_src = min(n_sources, n)
    # Spine: op i (i >= n_src) receives from a random earlier operator.
    for j in range(n_src, n):
        i = int(rng.integers(0, j))
        routing[i, j] = 1.0
    # Splits: give a random earlier op a second forward edge and split its
    # outflow (multiplicities ~ sum to the original mass, or > 1 fan-out).
    for i in range(n - 1):
        if rng.random() < p_split:
            choices = [j for j in range(i + 1, n) if routing[i, j] == 0.0]
            if choices:
                j = int(rng.choice(choices))
                routing[i, j] = float(rng.uniform(0.2, 1.2))
    # Joins arise from splits/spine overlap; force one more in-edge
    # sometimes so multi-in-degree joins are common.
    for j in range(n_src + 1, n):
        if rng.random() < p_join:
            choices = [i for i in range(j) if routing[i, j] == 0.0]
            if choices:
                i = int(rng.choice(choices))
                routing[i, j] = float(rng.uniform(0.2, 0.9))
    # Loops: a self-loop or back-edge that leaks (kept well under radius 1).
    # Every cycle goes through this one edge (spine/splits/joins are all
    # forward), so damping just it shrinks every cycle's gain while forward
    # fan-out keeps its mass.
    if rng.random() < p_loop:
        i = int(rng.integers(0, n))
        if rng.random() < 0.5 or i == 0:
            li, lj = i, i
            routing[i, i] = float(rng.uniform(0.1, 0.5))
        else:
            li, lj = i, int(rng.integers(0, i))
            routing[li, lj] = float(rng.uniform(0.1, 0.4))
        for _ in range(60):
            radius = float(max(abs(np.linalg.eigvals(routing))))
            if radius < 0.9:
                break
            routing[li, lj] *= 0.7
    lam0_vec = np.zeros(n)
    for s in range(n_src):
        lam0_vec[s] = lam0 / n_src
    # Solve traffic on the final routing, then pick mu so that a small
    # processor count sits inside target_rho.
    lam = np.linalg.solve(np.eye(n) - routing.T, lam0_vec)
    lam = np.maximum(lam, 0.0)
    mus = np.empty(n)
    for i in range(n):
        rho = float(rng.uniform(*target_rho))
        k_nom = int(rng.integers(1, 5))
        mus[i] = max(lam[i] / (rho * k_nom), 1e-3) if lam[i] > 0 else float(rng.uniform(1.0, 10.0))
    ops = [OpDef(name=names[i], mu=float(mus[i])) for i in range(n)]
    edges = [
        Edge(names[i], names[j], multiplicity=float(routing[i, j]))
        for i in range(n)
        for j in range(n)
        if routing[i, j] > 0.0
    ]
    sources = {names[s]: float(lam0_vec[s]) for s in range(n_src) if lam0_vec[s] > 0}
    return AppGraph(ops, edges, sources)


# --------------------------------------------------------------------------- #
# Scenario spec
# --------------------------------------------------------------------------- #
@dataclass(frozen=True)
class Scenario:
    """One fully-pinned experiment: everything both simulators need.

    ``traces`` maps source-operator names to :class:`ArrivalTrace`s
    (sources without a trace run constant at the graph's declared rate).
    ``arrival_kind`` picks the *micro* inter-arrival law around the trace
    rate (``exponential``/``uniform`` sample Poisson step counts in the
    batch sim; ``deterministic`` uses exact fluid mass).  ``k0`` is the
    starting allocation (None = plan Program (4)/(6) on the declared
    priors).  ``allocator`` selects the scheduler's Program solver
    ("table" | "heap") when the scenario runs under control.
    """

    name: str
    graph: AppGraph
    traces: Mapping[str, ArrivalTrace] = field(default_factory=dict)
    arrival_kind: str = "exponential"
    service_kind: str = "exponential"
    overload_policy: OverloadPolicy | str = "shed-newest"
    allocator: str = "table"
    seed: int = 0
    horizon: float = 120.0
    warmup: float = 10.0
    dt: float = 0.05
    queue_capacity: int | None = None
    k_max: int = 64
    t_max: float | None = None
    k0: Mapping[str, int] | None = None
    # Elastic mode: lease machines of ``machine_size`` processors from a
    # pool of ``k_max`` total through a Negotiator instead of holding a
    # static budget — the controller then scales out/in (paper Fig. 10).
    negotiated: bool = False
    machine_size: int = 4
    # Heterogeneous machine classes (paper §III-A): per-operator speed
    # factor of the machine class serving that operator (1.0 = reference).
    # Scales the simulator's service capacity, the model's effective mu
    # (core/controller.py), and — for ``negotiated`` scenarios — tags the
    # leased machines' ``speed``.
    speed_factors: Mapping[str, float] | None = None

    _ARRIVAL_KINDS = ("exponential", "uniform", "deterministic")
    _SERVICE_KINDS = ("exponential", "uniform", "deterministic", "lognormal")
    _ALLOCATORS = ("table", "heap")
    # Squared coefficients of variation of the micro inter-arrival /
    # service laws (DESIGN.md §17): exponential cv^2 = 1, uniform on
    # [0, 2m] = 1/3, deterministic = 0, lognormal = cv^2 (the DES's
    # ServiceProcess default cv is 1.0).  These feed the batch
    # simulator's Allen-Cunneen stationary-wait term.
    _ARRIVAL_SCV = {"exponential": 1.0, "uniform": 1.0 / 3.0, "deterministic": 0.0}
    _SERVICE_SCV = {
        "exponential": 1.0, "uniform": 1.0 / 3.0, "deterministic": 0.0,
        "lognormal": 1.0,
    }

    def __post_init__(self):
        OverloadPolicy.coerce(self.overload_policy)  # validate early
        unknown = set(self.traces) - set(self.graph.names)
        if unknown:
            raise ValueError(f"traces for unknown operators: {sorted(unknown)}")
        if self.speed_factors is not None:
            unknown = set(self.speed_factors) - set(self.graph.names)
            if unknown:
                raise ValueError(
                    f"speed_factors for unknown operators: {sorted(unknown)}"
                )
            bad = {k: v for k, v in self.speed_factors.items() if not v > 0}
            if bad:
                raise ValueError(f"speed_factors must be > 0, got {bad}")
        if self.arrival_kind not in self._ARRIVAL_KINDS:
            raise ValueError(
                f"unknown arrival_kind {self.arrival_kind!r}; expected one of "
                f"{self._ARRIVAL_KINDS} (rate modulation goes in traces=)"
            )
        if self.service_kind not in self._SERVICE_KINDS:
            raise ValueError(
                f"unknown service_kind {self.service_kind!r}; expected one of "
                f"{self._SERVICE_KINDS}"
            )
        if self.allocator not in self._ALLOCATORS:
            raise ValueError(
                f"unknown allocator {self.allocator!r}; expected one of "
                f"{self._ALLOCATORS}"
            )
        if self.dt <= 0 or self.horizon <= 0 or not 0 <= self.warmup < self.horizon:
            raise ValueError(
                f"need dt > 0, horizon > 0, 0 <= warmup < horizon; got "
                f"dt={self.dt}, horizon={self.horizon}, warmup={self.warmup}"
            )

    @property
    def policy(self) -> OverloadPolicy:
        return OverloadPolicy.coerce(self.overload_policy)

    @property
    def arrival_scv(self) -> float:
        """cv^2 of the micro inter-arrival law (§17 ``ca2`` input)."""
        return self._ARRIVAL_SCV[self.arrival_kind]

    @property
    def service_scv(self) -> float:
        """cv^2 of the service law (§17 ``cs2`` input)."""
        return self._SERVICE_SCV[self.service_kind]

    @property
    def steps(self) -> int:
        return int(round(self.horizon / self.dt))

    def with_(self, **kw) -> "Scenario":
        return replace(self, **kw)

    # -- trace compilation ------------------------------------------------ #
    def rate_grid(self) -> np.ndarray:
        """[T, N] external arrival rate per step for every operator."""
        t_grid = (np.arange(self.steps) + 0.5) * self.dt
        rates = np.zeros((self.steps, self.graph.n))
        lam0 = self.graph.lam0_vector()
        for i, name in enumerate(self.graph.names):
            trace = self.traces.get(name)
            if trace is not None:
                rates[:, i] = trace.rates(t_grid, self.seed)
            elif lam0[i] > 0:
                rates[:, i] = lam0[i]
        return rates

    def sample_arrivals(self) -> np.ndarray:
        """[T, N] pre-sampled external arrival *counts* per step — Poisson
        around the trace rate for stochastic arrival kinds, exact fluid
        mass for ``deterministic``.  Seed-deterministic."""
        rates = self.rate_grid()
        if self.arrival_kind == "deterministic":
            return rates * self.dt
        rng = np.random.default_rng(np.random.SeedSequence([self.seed, 0xA881]))
        return rng.poisson(rates * self.dt).astype(np.float64)

    def speed_vector(self) -> np.ndarray | None:
        """[N] machine-class speed factors in graph order (None when the
        scenario is homogeneous)."""
        if self.speed_factors is None:
            return None
        return np.array(
            [float(self.speed_factors.get(n, 1.0)) for n in self.graph.names]
        )

    def mean_topology(self):
        """Model Topology at the traces' time-averaged rates (the "true"
        model a controller should converge to), with machine-class speed
        factors applied to the per-processor service rates."""
        sources = {}
        lam0 = self.graph.lam0_vector()
        for i, name in enumerate(self.graph.names):
            trace = self.traces.get(name)
            if trace is not None:
                sources[name] = trace.mean_rate(self.horizon, self.seed)
            elif lam0[i] > 0:
                sources[name] = float(lam0[i])
        g = self.graph.with_sources(sources)
        if self.speed_factors is None:
            return g.topology()
        return g.topology(
            {op.name: op.mu * float(self.speed_factors.get(op.name, 1.0))
             for op in g.ops}
        )

    # -- DES compilation -------------------------------------------------- #
    def simulator(self, k, *, measurer=None, seed: int | None = None):
        """The event-DES twin of this scenario (same topology, same rate
        schedule, same overload policy; its own exact-process randomness).

        ``seed`` overrides the DES *process* randomness only — the trace
        realization (mmpp state path etc.) stays pinned to the scenario
        seed, so conformance checks can average several independent DES
        runs of the same schedule (DESIGN.md §17)."""
        from ..api.session import _group_effective_services
        from .des import ArrivalProcess, NetworkSimulator, ServiceProcess, SimConfig

        # Machine-class speed factors scale the DES per-processor rates,
        # matching the batch sim's capacity rule and the controller model.
        if self.speed_factors is None:
            top = self.graph.topology()
        else:
            top = self.graph.topology(
                {op.name: op.mu * float(self.speed_factors.get(op.name, 1.0))
                 for op in self.graph.ops}
            )
        k_vec = self.graph.k_vector(k)
        arrivals = []
        changes: list[tuple[float, int, float]] = []
        lam0 = self.graph.lam0_vector()
        for i, name in enumerate(self.graph.names):
            trace = self.traces.get(name)
            if trace is None:
                arrivals.append(
                    ArrivalProcess(rate=float(lam0[i]), kind=self.arrival_kind)
                )
                continue
            kw, sched = trace.des_schedule(self.horizon, self.seed)
            arrivals.append(ArrivalProcess(rate=kw["rate"], kind=self.arrival_kind))
            changes.extend((t, i, r) for t, r in sched)
        # Chip-gang operators collapse to one effective server (DESIGN.md §2),
        # mirroring both the DES backend and the batch sim's capacity rule.
        services, k_eff = _group_effective_services(top, k_vec)
        services = [
            ServiceProcess(rate=svc.rate, kind=self.service_kind)
            for svc in services
        ]
        sim = NetworkSimulator(
            top,
            k_eff,
            config=SimConfig(
                seed=self.seed if seed is None else int(seed),
                horizon=self.horizon,
                warmup=self.warmup,
                queue_capacity=self.queue_capacity,
                overload_policy=self.overload_policy,
            ),
            arrivals=arrivals,
            services=services,
            measurer=measurer,
        )
        for t, i, r in changes:
            sim.schedule_arrival_change(t, i, r)
        return sim

    def plan_k0(self) -> np.ndarray:
        """Starting allocation: declared ``k0`` or Program (4)/(6) on priors."""
        from ..core.allocator import allocate

        if self.k0 is not None:
            return self.graph.k_vector(self.k0)
        res = allocate(self.mean_topology(), k_max=self.k_max, t_max=self.t_max)
        return res.k


# --------------------------------------------------------------------------- #
# Packing: scenarios -> BatchArrays
# --------------------------------------------------------------------------- #
def map_distinct(items: Sequence, fn) -> list:
    """``[fn(x) for x in items]``, calling ``fn`` once per distinct object.

    A fleet tiled from a base zoo repeats the same (seed-pinned, frozen)
    Scenario objects, and their per-scenario host work (arrival sampling,
    Program (4)/(6) plans) is a pure function of the object.
    """
    memo: dict[int, object] = {}
    out = []
    for x in items:
        if id(x) not in memo:
            memo[id(x)] = fn(x)
        out.append(memo[id(x)])
    return out


def pack_scenarios(
    scenarios: Sequence[Scenario], *, pad_to: int | None = None
) -> BatchArrays:
    """Pack B scenarios (shared dt/horizon/warmup) into one batch.

    Scenarios with fewer operators than the batch maximum are padded with
    inactive zero-traffic lanes (mu = 1, no routing) that never see mass.

    ``pad_to`` additionally pads the *batch* axis to that extent with
    fully inert scenario lanes (``active`` all-False, zero arrivals) —
    the device-mesh case where B must be a multiple of the device count
    (DESIGN.md §16).  Masked lanes provably decide ``"none"`` in both
    the numpy twin and the jit decide (tests/test_mesh_control.py
    asserts this bit-for-bit); mixed-width stacks no longer assume the
    packed B is exact.
    """
    if not scenarios:
        raise ValueError("need at least one scenario")
    refuse_keyed([op.name for s in scenarios for op in s.graph.ops if op.scaling == "keyed"])
    dts = {s.dt for s in scenarios}
    horizons = {s.horizon for s in scenarios}
    warmups = {s.warmup for s in scenarios}
    if len(dts) > 1 or len(horizons) > 1 or len(warmups) > 1:
        raise ValueError(
            "batch scenarios must share dt/horizon/warmup; got "
            f"dt={sorted(dts)}, horizon={sorted(horizons)}, warmup={sorted(warmups)}"
        )
    b = len(scenarios)
    n = max(s.graph.n for s in scenarios)
    steps = scenarios[0].steps
    dt = scenarios[0].dt
    ext = np.zeros((steps, b, n))
    routing = np.zeros((b, n, n))
    mu = np.ones((b, n))
    group = np.zeros((b, n), dtype=bool)
    alpha = np.zeros((b, n))
    cap_queue = np.full((b, n), np.inf)
    active = np.zeros((b, n), dtype=bool)
    speed = np.ones((b, n))
    ca2 = np.ones((b, n))
    cs2 = np.ones((b, n))
    heterogeneous = False
    sampled = map_distinct(
        scenarios, lambda s: (s.sample_arrivals(), s.graph.routing_matrix())
    )
    for bi, (s, (arrivals, p)) in enumerate(zip(scenarios, sampled)):
        ni = s.graph.n
        ext[:, bi, :ni] = arrivals
        routing[bi, :ni, :ni] = p
        ca2[bi, :ni] = s.arrival_scv
        cs2[bi, :ni] = s.service_scv
        for i, op in enumerate(s.graph.ops):
            mu[bi, i] = op.mu
            group[bi, i] = op.scaling == "group"
            alpha[bi, i] = op.group_alpha
        active[bi, :ni] = True
        if s.queue_capacity is not None and s.policy.sheds:
            cap_queue[bi, :ni] = float(s.queue_capacity)
        sv = s.speed_vector()
        if sv is not None:
            speed[bi, :ni] = sv
            heterogeneous = True
    arrays = BatchArrays(
        ext=ext,
        routing=routing,
        mu=mu,
        group=group,
        alpha=alpha,
        cap_queue=cap_queue,
        dt=dt,
        warmup_steps=int(round(scenarios[0].warmup / dt)),
        active=active,
        speed=speed if heterogeneous else None,
        ca2=ca2,
        cs2=cs2,
    )
    if pad_to is not None:
        arrays = arrays.pad_batch(int(pad_to))
    return arrays


def pack_allocations(scenarios: Sequence[Scenario], ks) -> np.ndarray:
    """[B, N_max] allocation matrix from per-scenario k vectors/dicts
    (padding lanes get 0 processors)."""
    n = max(s.graph.n for s in scenarios)
    out = np.zeros((len(scenarios), n), dtype=np.int64)
    for bi, (s, k) in enumerate(zip(scenarios, ks)):
        out[bi, : s.graph.n] = s.graph.k_vector(k)
    return out


# --------------------------------------------------------------------------- #
# Canonical scenarios + the matrix generator
# --------------------------------------------------------------------------- #
def vld_scenario(**kw) -> Scenario:
    """The paper's VLD chain (extract -> match -> aggregate) as a model-only
    scenario: same shape and service-rate priors as
    ``streaming.apps.vld.build_vld_graph``, no compute fns."""
    graph = AppGraph(
        [OpDef("extract", mu=2.0), OpDef("match", mu=5.0), OpDef("aggregate", mu=50.0)],
        [Edge("extract", "match"), Edge("match", "aggregate")],
        {"extract": 13.0},
        arrival_kind="uniform",
    )
    defaults = dict(
        name="vld",
        graph=graph,
        traces={"extract": ArrivalTrace(kind="flash", rate=10.0, peak=20.0,
                                        t_on=60.0, t_off=90.0)},
        arrival_kind="uniform",  # the paper's uniform fps (graph + DES twin)
        seed=7,
        horizon=150.0,
        warmup=10.0,
        k_max=48,
        t_max=2.5,
        negotiated=True,
    )
    defaults.update(kw)
    return Scenario(**defaults)


def fpd_scenario(**kw) -> Scenario:
    """The paper's FPD graph (generate -> detect[self-loop] -> report) as a
    model-only scenario mirroring ``streaming.apps.fpd.build_fpd_graph``."""
    loop_p = 0.3
    graph = AppGraph(
        [OpDef("generate", mu=4.0), OpDef("detect", mu=3.0), OpDef("report", mu=12.0)],
        [
            Edge("generate", "detect"),
            Edge("detect", "detect", multiplicity=loop_p),
            Edge("detect", "report", multiplicity=1.0 - loop_p),
        ],
        {"generate": 16.0},
    )
    defaults = dict(
        name="fpd",
        graph=graph,
        traces={"generate": ArrivalTrace(kind="diurnal", rate=14.0, amplitude=8.0,
                                         period=80.0)},
        seed=11,
        horizon=160.0,
        warmup=10.0,
        k_max=64,
        t_max=3.0,
        negotiated=True,
    )
    defaults.update(kw)
    return Scenario(**defaults)


def control_trace(
    scenarios: Sequence[Scenario],
    *,
    tick_interval: float = 10.0,
    proactive=None,
    backend: str = "numpy",
    interpret: bool = False,
    compact=None,
) -> dict:
    """JSON-able decision trace of the full control loop over ``scenarios``
    (the golden-trace surface, DESIGN.md §13).

    Runs the scenarios through :class:`~repro.api.session.ScenarioRunner`
    on the numpy float64 twin — fully deterministic given the scenario
    seeds — and records, per scenario, the scheduler's action sequence,
    the allocation in force after every tick, and the per-tick trajectory
    (provisioned k, miss mask — the reactive-vs-proactive lead-time
    surface).  ``proactive`` (True or an
    :class:`~repro.forecast.mpc.MPCConfig`) switches on the forecast/MPC
    plane, which is just as deterministic — the proactive golden fixture
    proves predictor + planner replayability.  Regenerate the committed
    fixtures with ``PYTHONPATH=src python tests/golden/regen.py``.

    ``backend="jax"`` replays the same trace through the fused jit loop
    under enable_x64 (bit-identical to the twin for non-negotiated
    scenarios); ``interpret`` runs any Pallas dispatch in interpret mode
    (tests/test_golden_traces.py).
    ``compact`` (True or a :class:`~repro.core.controller.CompactionConfig`)
    turns on the trigger-gated sparse decide (DESIGN.md §18); compaction
    is output-invisible, so every golden must replay bit-identically with
    it on — that replay is part of the compaction test surface.
    """
    from ..api.session import ScenarioRunner

    def _run():
        runner = ScenarioRunner(
            scenarios, tick_interval=tick_interval, backend=backend,
            proactive=proactive, interpret=interpret, compact=compact,
        )
        return runner.run()

    if backend == "numpy":
        reports = _run()
    else:
        import jax

        with jax.enable_x64(True):
            reports = _run()

    def _traj(tr):
        if tr is None:
            return None
        out = {
            "t": [round(float(t), 9) for t in tr["t"]],
            "k_total": list(tr["k_total"]),
            "miss": [int(m) for m in tr["miss"]],
            "warm": [int(w) for w in tr["warm"]],
        }
        if "mpc_used" in tr:
            out["mpc_used"] = [int(u) for u in tr["mpc_used"]]
            out["confident"] = [int(c) for c in tr["confident"]]
        return out

    return {
        "tick_interval": tick_interval,
        "proactive": proactive is not None,
        "scenarios": {
            r.name: {
                "actions": list(r.actions),
                "allocations": [dict(a) for a in r.allocations],
                "provisioned_total": r.provisioned_total,
                "optimal_total": r.optimal_total,
                "drop_rate": round(r.drop_rate, 9),
                "mean_sojourn": round(r.mean_sojourn, 9),
                "deadline_miss_rate": round(r.deadline_miss_rate, 9),
                "trajectory": _traj(r.trajectory),
            }
            for r in reports
        },
    }


def scenario_matrix(
    n_scenarios: int,
    *,
    seed: int = 0,
    horizon: float = 60.0,
    warmup: float = 5.0,
    dt: float = 0.05,
    k_max: int = 48,
) -> list[Scenario]:
    """A seeded sweep over (random topology x trace kind x overload policy
    x allocator) — the CI matrix.  Deterministic: scenario ``i`` of seed
    ``s`` is always the same spec."""
    rng = np.random.default_rng(np.random.SeedSequence([seed, 0x5CE0]))
    policies = ("shed-newest", "shed-oldest", "block")
    out = []
    for i in range(n_scenarios):
        g_seed = int(rng.integers(0, 1 << 30))
        graph = random_appgraph(g_seed, lam0=float(rng.uniform(5.0, 20.0)))
        src = graph.source_names[0]
        base = float(graph.lam0_vector().sum())
        kind = ("constant", "diurnal", "flash", "mmpp")[i % 4]
        if kind == "constant":
            trace = ArrivalTrace(kind="constant", rate=base)
        elif kind == "diurnal":
            trace = ArrivalTrace(kind="diurnal", rate=base, amplitude=0.5 * base,
                                 period=float(rng.uniform(0.4 * horizon, horizon)))
        elif kind == "flash":
            trace = ArrivalTrace(kind="flash", rate=base, peak=2.0 * base,
                                 t_on=horizon * 0.4, t_off=horizon * 0.6)
        else:
            trace = ArrivalTrace(kind="mmpp", rate=0.7 * base, peak=1.8 * base,
                                 switch01=0.05, switch10=0.1)
        # Coprime cycle lengths (4 for kind, 3 for policy, 5 and 7 below)
        # so the axes decorrelate: every (kind x policy x bound x allocator
        # x t_max x negotiated) combination appears once the matrix is a
        # few dozen scenarios deep — no axis is a function of another.
        bounded = i % 5 < 2
        allocator = "heap" if i % 7 < 3 else "table"
        negotiated = i % 7 >= 5
        # ~3/5 of the matrix gets a real-time constraint (Program 6 active):
        # 1.5x the best E[T] reachable within the budget, so it is feasible
        # at the mean rate but stressed at the peaks.
        t_max = None
        if i % 5 < 3:
            from ..core.allocator import InsufficientResourcesError, allocate
            from ..core.jackson import UnstableTopologyError

            try:
                sources = {src: trace.mean_rate(horizon, g_seed ^ 0x1234)}
                top = graph.with_sources(sources).topology()
                t_max = 1.5 * allocate(top, k_max=k_max).expected_sojourn
            except (InsufficientResourcesError, UnstableTopologyError):
                t_max = None
        out.append(
            Scenario(
                name=f"m{seed}-{i:03d}-{kind}",
                graph=graph,
                traces={src: trace},
                seed=g_seed ^ 0x1234,
                horizon=horizon,
                warmup=warmup,
                dt=dt,
                overload_policy=policies[i % 3],
                allocator=allocator,
                queue_capacity=int(rng.integers(50, 400)) if bounded else None,
                k_max=k_max,
                t_max=t_max,
                negotiated=negotiated,
            )
        )
    return out
