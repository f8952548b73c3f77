"""Declarative application graphs — the single topology surface (DESIGN.md §1).

Every DRS consumer used to declare its operator network a different way: a
hand-built numpy routing matrix for :class:`~repro.core.jackson.Topology`,
an ``Operator`` list for the live :class:`~repro.streaming.engine.StreamEngine`,
a ``SimConfig`` + parallel arrival/service lists for the DES, and bespoke
wiring inside the serving model — with the scheduler constructed from
positionally hand-synced name/routing/k lists at every call site.

:class:`AppGraph` collapses those surfaces into one typed declaration:

* :class:`OpDef` — one operator: name, service-rate prior, optional compute
  fn (for the live engine), scaling mode (``replica`` M/M/k or ``group``
  chip-gang, see DESIGN.md §2), and DES service-time distribution.
* :class:`Edge` — one directed edge with an expected multiplicity.  ``> 1``
  models fan-out (a feature extractor emitting many features per frame);
  ``src == dst`` with multiplicity ``< 1`` models a leaking self-loop (the
  FPD detector, autoregressive decode).

The graph validates at construction — unknown endpoints, duplicate names,
negative rates, and non-leaking loops (spectral radius >= 1) all fail
immediately with a precise error — and compiles to the core primitives:
routing matrix, external-arrival vector, name/index maps, and a
:class:`~repro.core.jackson.Topology` for the performance model.  Binding
a backend (:meth:`AppGraph.bind`) yields a
:class:`~repro.api.session.DRSSession` that owns the whole
measure -> model -> rebalance loop.
"""

from __future__ import annotations

from dataclasses import dataclass, replace
from typing import Any, Callable, Mapping, Sequence

import numpy as np

from ..core.jackson import OperatorSpec, Topology, UnstableTopologyError

__all__ = ["OpDef", "Edge", "AppGraph", "GraphValidationError"]


class GraphValidationError(ValueError):
    """The graph declaration is malformed (bad names, edges, or rates)."""


@dataclass(frozen=True)
class OpDef:
    """One operator in an application graph.

    ``mu`` is the per-processor service-rate *prior* (tuples/sec); the
    measurer corrects it online.  ``fn`` is the live-engine compute:
    ``fn(payload) -> list[(downstream_name, payload)]`` (may be ``None``
    for model-only / DES graphs).  ``scaling`` selects how k processors
    compose — ``"replica"`` (k independent servers, exact M/M/k),
    ``"group"`` (one gang of k chips at ``mu * k * eff(k)``, DESIGN.md §2)
    or ``"keyed"`` (k hash partitions of a keyed stream, each M/M/1, the
    hot key carrying ``hot_share`` of the input, DESIGN.md §20).
    ``service_kind``/``service_cv`` choose the DES service-time
    distribution used when the graph is bound to the simulator.
    """

    name: str
    mu: float
    fn: Callable[[Any], list[tuple[str, Any]]] | None = None
    scaling: str = "replica"
    group_alpha: float = 0.0
    min_k: int = 1
    max_k: int = 1 << 30
    service_kind: str = "exponential"
    service_cv: float = 1.0
    hot_share: float | None = None

    def spec(self, mu: float | None = None) -> OperatorSpec:
        """Compile to the core model's operator description."""
        return OperatorSpec(
            name=self.name,
            mu=self.mu if mu is None else mu,
            scaling=self.scaling,
            group_alpha=self.group_alpha,
            min_k=self.min_k,
            max_k=self.max_k,
            hot_share=0.0 if self.hot_share is None else self.hot_share,
        )


@dataclass(frozen=True)
class Edge:
    """Directed edge ``src -> dst`` with expected multiplicity.

    ``multiplicity`` is the expected number of tuples delivered to ``dst``
    per tuple completed at ``src`` — a probability for routing splits, or
    > 1 for fan-out.  A self-loop (``src == dst``) must keep the routing
    matrix's spectral radius below 1 (it has to leak).
    """

    src: str
    dst: str
    multiplicity: float = 1.0


class AppGraph:
    """A validated operator network: ops + edges + external sources.

    One ``AppGraph`` is the single source of truth for every backend: the
    performance model (:meth:`topology`), the live engine, the DES, and
    the scheduler all derive their wiring from it — no more parallel
    name/routing/k lists.

    Parameters
    ----------
    ops:      operator definitions (order fixes the model's index space).
    edges:    typed edge declarations.
    sources:  mapping ``op name -> external arrival rate`` (lam0).
    arrival_kind: DES inter-arrival distribution for the sources
              (``exponential`` | ``uniform`` | ``deterministic``).
    validate_stability: check spectral radius < 1 at construction
              (disable only for deliberately-unstable experiments).
    """

    def __init__(
        self,
        ops: Sequence[OpDef],
        edges: Sequence[Edge] = (),
        sources: Mapping[str, float] | None = None,
        *,
        arrival_kind: str = "exponential",
        validate_stability: bool = True,
    ):
        self.ops: tuple[OpDef, ...] = tuple(ops)
        self.edges: tuple[Edge, ...] = tuple(edges)
        self.arrival_kind = arrival_kind
        self.validate_stability = validate_stability
        if not self.ops:
            raise GraphValidationError("graph needs at least one operator")
        self.names: list[str] = [op.name for op in self.ops]
        if len(set(self.names)) != len(self.names):
            dupes = sorted({n for n in self.names if self.names.count(n) > 1})
            raise GraphValidationError(f"duplicate operator names: {dupes}")
        self.index: dict[str, int] = {n: i for i, n in enumerate(self.names)}
        for op in self.ops:
            if op.mu <= 0:
                raise GraphValidationError(
                    f"operator {op.name!r}: service rate mu must be > 0, got {op.mu}"
                )
            if op.scaling not in ("replica", "group", "keyed"):
                raise GraphValidationError(
                    f"operator {op.name!r}: unknown scaling {op.scaling!r}"
                )
            if op.scaling == "keyed":
                h = op.hot_share
                if h is None or not 0.0 <= h <= 1.0:
                    raise GraphValidationError(
                        f"operator {op.name!r}: keyed scaling needs a hot_share "
                        f"in [0, 1], got {h}"
                    )
            elif op.hot_share is not None:
                raise GraphValidationError(
                    f"operator {op.name!r}: hot_share applies to keyed scaling "
                    f"only, not {op.scaling!r}"
                )

        n = len(self.ops)
        self._routing = np.zeros((n, n), dtype=np.float64)
        for e in self.edges:
            for endpoint in (e.src, e.dst):
                if endpoint not in self.index:
                    raise GraphValidationError(
                        f"edge {e.src!r} -> {e.dst!r}: unknown operator {endpoint!r}"
                    )
            if e.multiplicity <= 0:
                raise GraphValidationError(
                    f"edge {e.src!r} -> {e.dst!r}: multiplicity must be > 0, "
                    f"got {e.multiplicity}"
                )
            i, j = self.index[e.src], self.index[e.dst]
            if self._routing[i, j] != 0.0:
                raise GraphValidationError(
                    f"duplicate edge {e.src!r} -> {e.dst!r}"
                )
            self._routing[i, j] = e.multiplicity

        self._lam0 = np.zeros(n, dtype=np.float64)
        for name, rate in (sources or {}).items():
            if name not in self.index:
                raise GraphValidationError(f"unknown source operator {name!r}")
            if rate < 0:
                raise GraphValidationError(
                    f"source {name!r}: arrival rate must be >= 0, got {rate}"
                )
            self._lam0[self.index[name]] = rate

        if validate_stability:
            radius = self.spectral_radius
            if radius >= 1.0 - 1e-12:
                loops = [e for e in self.edges if e.src == e.dst]
                hint = (
                    f" (self-loops: {[(e.src, e.multiplicity) for e in loops]})"
                    if loops
                    else ""
                )
                raise UnstableTopologyError(
                    f"routing spectral radius {radius:.6f} >= 1; every cycle "
                    f"must leak probability for the open network to be stable"
                    + hint
                )

    # Introspection ----------------------------------------------------- #
    @property
    def n(self) -> int:
        return len(self.ops)

    @property
    def spectral_radius(self) -> float:
        try:
            return float(max(abs(np.linalg.eigvals(self._routing))))
        except np.linalg.LinAlgError:  # pragma: no cover - defensive
            return float("inf")

    @property
    def source_names(self) -> list[str]:
        return [n for n, r in zip(self.names, self._lam0) if r > 0]

    def op(self, name: str) -> OpDef:
        return self.ops[self.index[name]]

    def routing_matrix(self) -> np.ndarray:
        """The derived routing matrix P (``P[i][j]`` = multiplicity i->j)."""
        return self._routing.copy()

    def lam0_vector(self) -> np.ndarray:
        """External arrival rates in operator-index order."""
        return self._lam0.copy()

    # Name-keyed <-> index-ordered conversion --------------------------- #
    def k_vector(self, k: Mapping[str, int] | Sequence[int] | np.ndarray) -> np.ndarray:
        """Allocation as an index-ordered int vector (accepts dict or seq)."""
        if isinstance(k, Mapping):
            missing = [n for n in self.names if n not in k]
            if missing:
                raise GraphValidationError(f"allocation missing operators: {missing}")
            extra = sorted(set(k) - set(self.names))
            if extra:
                raise GraphValidationError(f"allocation has unknown operators: {extra}")
            return np.array([int(k[n]) for n in self.names], dtype=np.int64)
        vec = np.asarray(k, dtype=np.int64)
        if vec.shape != (self.n,):
            raise GraphValidationError(
                f"allocation must have shape ({self.n},), got {vec.shape}"
            )
        return vec.copy()

    def k_dict(self, k: Sequence[int] | np.ndarray | Mapping[str, int]) -> dict[str, int]:
        """Allocation as a name-keyed dict."""
        return dict(zip(self.names, self.k_vector(k).tolist()))

    # Compilation ------------------------------------------------------- #
    def topology(self, mu: Mapping[str, float] | None = None) -> Topology:
        """Compile to the core Jackson-network model.

        ``mu`` optionally overrides per-operator service-rate priors by
        name (e.g. with measured values).
        """
        overrides = dict(mu or {})
        unknown = set(overrides) - set(self.names)
        if unknown:
            raise GraphValidationError(f"mu overrides for unknown operators: {sorted(unknown)}")
        specs = [op.spec(overrides.get(op.name)) for op in self.ops]
        return Topology(specs, self._lam0.copy(), self._routing.copy())

    def scaling_lists(self) -> tuple[list[str], list[float]]:
        """(scaling mode, group_alpha) per operator, index-ordered — the
        scheduler's view of how processors compose."""
        return [op.scaling for op in self.ops], [op.group_alpha for op in self.ops]

    def hot_shares(self) -> np.ndarray:
        """Each keyed operator's hot-key share, index-ordered; NaN where the
        operator is not keyed."""
        return np.array(
            [np.nan if op.hot_share is None else op.hot_share for op in self.ops]
        )

    # Derivation -------------------------------------------------------- #
    def with_sources(self, sources: Mapping[str, float]) -> "AppGraph":
        """Same graph, different external arrival rates (e.g. a new lam0)."""
        return AppGraph(
            self.ops, self.edges, sources, arrival_kind=self.arrival_kind,
            validate_stability=self.validate_stability,
        )

    def with_fns(self, fns: Mapping[str, Callable]) -> "AppGraph":
        """Same graph with compute fns attached (model-only -> runnable)."""
        unknown = set(fns) - set(self.names)
        if unknown:
            raise GraphValidationError(f"fns for unknown operators: {sorted(unknown)}")
        ops = [
            replace(op, fn=fns.get(op.name, op.fn)) for op in self.ops
        ]
        return AppGraph(
            ops, self.edges, dict(zip(self.names, self._lam0.tolist())),
            arrival_kind=self.arrival_kind,
            validate_stability=self.validate_stability,
        )

    # Binding ----------------------------------------------------------- #
    def bind(self, backend: Any = "des", **kwargs):
        """Bind this graph to a backend and get a :class:`DRSSession`.

        ``backend`` is ``"engine"`` (live StreamEngine), ``"des"``
        (NetworkSimulator), or an already-constructed backend object.
        Session-level options (``config=SchedulerConfig(...)``,
        ``negotiator=...``) and backend options (``seed=``, ``horizon=``,
        ``queue_capacity=``, ...) are passed through ``kwargs``.
        """
        from .session import DRSSession  # local import: session imports backends

        return DRSSession.bind(self, backend, **kwargs)

    # Convenience constructors ------------------------------------------ #
    @staticmethod
    def chain(
        names_mus: Sequence[tuple[str, float]],
        lam0: float,
        *,
        arrival_kind: str = "exponential",
    ) -> "AppGraph":
        """A linear chain: external tuples enter op0, op_i feeds op_{i+1}
        (the VLD shape) — mirrors ``Topology.chain`` declaratively."""
        ops = [OpDef(name=nm, mu=mu) for nm, mu in names_mus]
        edges = [
            Edge(names_mus[i][0], names_mus[i + 1][0])
            for i in range(len(names_mus) - 1)
        ]
        return AppGraph(
            ops, edges, {names_mus[0][0]: lam0}, arrival_kind=arrival_kind
        )

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return (
            f"AppGraph(ops={self.names}, edges={len(self.edges)}, "
            f"sources={ {n: float(self._lam0[self.index[n]]) for n in self.source_names} })"
        )
