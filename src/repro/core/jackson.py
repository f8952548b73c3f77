"""Jackson open queueing network model (paper Eq. 3 + traffic equations).

An application is a directed graph of operators with probabilistic routing.
``routing[i][j] = p`` means a tuple finishing at operator *i* produces an
input to operator *j* with expected multiplicity ``p`` (p may exceed 1 for
fan-out operators such as a feature extractor emitting many features per
frame — Jackson theory handles mean branching factors).

The per-operator arrival rates are tied to the external arrival vector
``lam0`` by the traffic equations

    lam_i = lam0_i + sum_j routing[j][i] * lam_j        (vector: lam = lam0 + P^T lam)

solved as ``lam = (I - P^T)^{-1} lam0``.  Loops (e.g. the paper's FPD
detector self-loop, or autoregressive decode in LLM serving) are supported
as long as the routing matrix has spectral radius < 1 — i.e. loops leak.

End-to-end expected total sojourn time (paper Eq. 3):

    E[T](k) = (1/lam0_total) * sum_i lam_i * E[T_i](k_i).
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field

import numpy as np

from .erlang import expected_sojourn, keyed_sojourn, min_stable_k

__all__ = [
    "OperatorSpec",
    "Topology",
    "UnstableTopologyError",
    "solve_traffic_equations",
]


class UnstableTopologyError(ValueError):
    """Routing matrix has spectral radius >= 1 (a loop that does not leak)."""


@dataclass(frozen=True)
class OperatorSpec:
    """Static description of one operator.

    mu is the mean per-processor service rate (tuples/sec).  ``scaling``
    selects how k processors compose:

    * ``"replica"`` — k independent servers: exact M/M/k (the paper's model).
    * ``"group"``   — the k processors form one gang (e.g. one pjit'd chip
      group); service rate is ``mu * k * group_efficiency(k)`` on an M/M/1
      queue.  See DESIGN.md §2 — this is the TPU chip-group extension.
    * ``"keyed"``   — the k processors are k hash partitions of a keyed
      stream, each M/M/1, with the hot key's share ``hot_share`` of the
      input on one of them (DESIGN.md §20).
    """

    name: str
    mu: float
    scaling: str = "replica"
    # group-mode efficiency curve: eff(k) multiplier on linear scaling.
    # Stored as (alpha) for eff(k) = 1 / (1 + alpha * (k - 1)); alpha=0 -> linear.
    group_alpha: float = 0.0
    min_k: int = 1
    max_k: int = 1 << 30
    hot_share: float = 0.0  # keyed mode: the hot key's share of the input

    def sojourn(self, k: int, lam: float) -> float:
        """E[T_i](k) for this operator under arrival rate lam."""
        if k < self.min_k:
            return math.inf
        if self.scaling == "replica":
            return expected_sojourn(k, lam, self.mu)
        if self.scaling == "group":
            eff = 1.0 / (1.0 + self.group_alpha * (k - 1))
            return expected_sojourn(1, lam, self.mu * k * eff)
        if self.scaling == "keyed":
            return keyed_sojourn(k, lam, self.mu, self.hot_share)
        raise ValueError(f"unknown scaling {self.scaling!r}")

    def min_feasible_k(self, lam: float) -> int:
        """Smallest k with finite sojourn (Algorithm 1 line 2 init)."""
        if self.scaling == "replica":
            return max(self.min_k, min_stable_k(lam, self.mu))
        if self.scaling == "keyed":
            try:
                return max(self.min_k, min_stable_k(lam, self.mu, self.hot_share))
            except ValueError as e:  # the hot key alone saturates a processor
                raise UnstableTopologyError(f"operator {self.name}: {e}") from e
        # group: need mu * k * eff(k) > lam.  With eff(k) = 1/(1+alpha(k-1))
        # the effective rate ASYMPTOTES at mu/alpha as k -> inf, so a load
        # beyond that is unreachable at any k — fail fast instead of
        # searching to max_k.
        if self.group_alpha > 0 and lam >= self.mu / self.group_alpha:
            raise UnstableTopologyError(
                f"operator {self.name}: group scaling saturates at "
                f"mu/alpha = {self.mu / self.group_alpha:.3g} < lam = {lam:.3g}; "
                "no chip count can keep this stage stable"
            )
        k = self.min_k
        while not math.isfinite(self.sojourn(k, lam)):
            k += 1
            if k > self.max_k:
                raise UnstableTopologyError(
                    f"operator {self.name}: no feasible k <= max_k={self.max_k} "
                    f"for lam={lam}, mu={self.mu} (group_alpha={self.group_alpha})"
                )
        return k


def solve_traffic_equations(
    lam0: np.ndarray, routing: np.ndarray, *, check_stability: bool = True
) -> np.ndarray:
    """Solve lam = lam0 + P^T lam for lam (Jackson traffic equations)."""
    lam0 = np.asarray(lam0, dtype=np.float64)
    p = np.asarray(routing, dtype=np.float64)
    n = lam0.shape[0]
    if p.shape != (n, n):
        raise ValueError(f"routing must be ({n},{n}), got {p.shape}")
    if np.any(p < 0):
        raise ValueError("routing probabilities/multiplicities must be >= 0")
    if check_stability:
        try:
            radius = max(abs(np.linalg.eigvals(p)))
        except np.linalg.LinAlgError:  # pragma: no cover - defensive
            radius = np.inf
        if radius >= 1.0 - 1e-12:
            raise UnstableTopologyError(
                f"routing spectral radius {radius:.6f} >= 1; a loop must leak "
                "probability for the open network to be stable"
            )
    lam = np.linalg.solve(np.eye(n) - p.T, lam0)
    # Numerical noise can produce tiny negatives for zero-traffic operators.
    lam[np.abs(lam) < 1e-12] = 0.0
    if np.any(lam < 0):
        raise UnstableTopologyError(f"negative solved arrival rates: {lam}")
    return lam


@dataclass
class Topology:
    """Operator network: specs + external arrivals + routing.

    This is the model-side mirror of a streaming application (or of a
    serving pipeline — see serving/pipeline.py which compiles a serving
    graph down to a Topology).
    """

    operators: list[OperatorSpec]
    lam0: np.ndarray  # external arrival rate per operator
    routing: np.ndarray  # routing[i][j] = expected tuples to j per tuple done at i
    _lam: np.ndarray | None = field(default=None, repr=False)

    def __post_init__(self) -> None:
        self.lam0 = np.asarray(self.lam0, dtype=np.float64)
        self.routing = np.asarray(self.routing, dtype=np.float64)
        n = len(self.operators)
        if self.lam0.shape != (n,):
            raise ValueError(f"lam0 must have shape ({n},), got {self.lam0.shape}")
        if self.routing.shape != (n, n):
            raise ValueError(
                f"routing must have shape ({n},{n}), got {self.routing.shape}"
            )

    # ------------------------------------------------------------------ #
    @property
    def n(self) -> int:
        return len(self.operators)

    @property
    def lam0_total(self) -> float:
        return float(self.lam0.sum())

    @property
    def arrival_rates(self) -> np.ndarray:
        """Per-operator arrival rates lam_i from the traffic equations."""
        if self._lam is None:
            self._lam = solve_traffic_equations(self.lam0, self.routing)
        return self._lam

    @property
    def visit_counts(self) -> np.ndarray:
        """Expected visits to each operator per external tuple: lam_i / lam0."""
        return self.arrival_rates / max(self.lam0_total, 1e-300)

    # ------------------------------------------------------------------ #
    def expected_sojourn(self, k: list[int] | np.ndarray) -> float:
        """E[T](k) — paper Eq. (3)."""
        k = np.asarray(k)
        if k.shape != (self.n,):
            raise ValueError(f"k must have shape ({self.n},), got {k.shape}")
        lam = self.arrival_rates
        total = 0.0
        for i, op in enumerate(self.operators):
            if lam[i] == 0.0:
                continue
            t = op.sojourn(int(k[i]), lam[i])
            if math.isinf(t):
                return math.inf
            total += lam[i] * t
        # Same zero-traffic guard as visit_counts: an idle network (all
        # lam0 == 0, e.g. one quiet measurement window) has E[T] = 0, not
        # a division crash in the middle of a control loop.
        return total / max(self.lam0_total, 1e-300)

    def per_operator_sojourn(self, k: list[int] | np.ndarray) -> np.ndarray:
        lam = self.arrival_rates
        return np.array(
            [op.sojourn(int(ki), lam[i]) for i, (op, ki) in enumerate(zip(self.operators, k))]
        )

    def min_feasible_allocation(self) -> np.ndarray:
        """Algorithm 1 lines 1-3: k_i = ceil(lam_i/mu_i) (stability-bumped)."""
        lam = self.arrival_rates
        return np.array(
            [op.min_feasible_k(lam[i]) for i, op in enumerate(self.operators)],
            dtype=np.int64,
        )

    def utilization(self, k: list[int] | np.ndarray) -> np.ndarray:
        """rho_i = lam_i / (k_i * mu_i) per operator (replica semantics)."""
        lam = self.arrival_rates
        return np.array(
            [
                lam[i] / (int(k[i]) * op.mu) if op.mu > 0 else np.inf
                for i, op in enumerate(self.operators)
            ]
        )

    # Convenience constructors ------------------------------------------ #
    @staticmethod
    def chain(names_mus: list[tuple[str, float]], lam0: float) -> "Topology":
        """A linear chain: source feeds op0, op_i feeds op_{i+1} (VLD shape)."""
        n = len(names_mus)
        ops = [OperatorSpec(name=nm, mu=mu) for nm, mu in names_mus]
        routing = np.zeros((n, n))
        for i in range(n - 1):
            routing[i][i + 1] = 1.0
        lam0_vec = np.zeros(n)
        lam0_vec[0] = lam0
        return Topology(ops, lam0_vec, routing)
