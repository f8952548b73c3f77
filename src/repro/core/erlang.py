"""Erlang M/M/k sojourn-time model (paper Eq. 1-2).

Implements the per-operator performance model of DRS: operator *i* with
``k`` parallel identical processors, Poisson arrivals at rate ``lam`` and
exponential service at rate ``mu`` per processor is an M/M/k queue.  The
expected sojourn time (queueing delay + service) is

    E[T](k) = ErlangC(k, a) / (k*mu - lam) + 1/mu,      a = lam/mu,

which is algebraically identical to paper Eq. (1)-(2) (the paper writes the
waiting term as ``a^k pi_0 / (k! (1-rho)^2 mu k)``).

Two implementations are provided:

* :func:`expected_sojourn_factorial` — the paper-literal factorial form.
  It overflows for k beyond ~170 in float64 and is kept as the oracle for
  small k.
* :func:`expected_sojourn` — the numerically stable Erlang-B recursion
  ``B(0)=1; B(k) = a*B(k-1) / (k + a*B(k-1))`` followed by the standard
  B→C conversion.  Exact to ~1e-12 relative and safe for k in the tens of
  thousands (we allocate across chips of a 1000+ node fleet).

Both return ``math.inf`` when the operator is unstable (``k*mu <= lam``),
matching the paper's Eq. (1) second branch.

A **keyed** operator (DESIGN.md §20) is not one queue: its k processors
are k hash partitions of a keyed stream, each an M/M/1 queue.  The
current hot key carries a share ``h`` of the input and the rest hashes
evenly, so the hottest partition takes ``p_hot = h + (1 - h)/k`` of it and
each other partition ``p_cold = (1 - h)/k``.  A tuple's mean sojourn is

    T(k) = p_hot / (mu - lam p_hot) + (k - 1) p_cold / (mu - lam p_cold),

infinite where ``lam p_hot >= mu``: the hot partition, not the pooled
load, bounds the capacity at ``mu / p_hot``.  ``h = 1`` is a global
aggregate that no processor count helps; ``h = 0`` hashes evenly.
"""

from __future__ import annotations

import math
from functools import lru_cache

import numpy as np

__all__ = [
    "erlang_b",
    "erlang_c",
    "expected_sojourn",
    "expected_sojourn_factorial",
    "expected_queue_delay",
    "keyed_capacity",
    "keyed_sojourn",
    "min_stable_k",
    "sojourn_curve",
    "marginal_benefit",
]


def erlang_b(k: int, a: float) -> float:
    """Erlang-B blocking probability B(k, a) via the stable recursion.

    B(0) = 1;  B(j) = a*B(j-1) / (j + a*B(j-1)).
    """
    if k < 0:
        raise ValueError(f"k must be >= 0, got {k}")
    if a < 0:
        raise ValueError(f"offered load a must be >= 0, got {a}")
    b = 1.0
    for j in range(1, k + 1):
        b = a * b / (j + a * b)
    return b


def erlang_c(k: int, a: float) -> float:
    """Erlang-C probability that an arrival must wait, C(k, a).

    Valid for a < k (stable queue).  Uses C = k*B / (k - a*(1-B)).
    """
    if a >= k:
        return 1.0  # degenerate; callers guard stability separately
    b = erlang_b(k, a)
    return k * b / (k - a * (1.0 - b))


def expected_sojourn(k: int, lam: float, mu: float) -> float:
    """E[T](k) for an M/M/k operator — stable form (paper Eq. 1).

    Returns +inf when k*mu <= lam (unstable queue, paper's second branch).
    """
    if mu <= 0:
        raise ValueError(f"service rate mu must be > 0, got {mu}")
    if lam < 0:
        raise ValueError(f"arrival rate lam must be >= 0, got {lam}")
    if lam == 0.0:
        return 1.0 / mu
    a = lam / mu
    if k <= a:  # k*mu <= lam
        return math.inf
    c = erlang_c(k, a)
    wait = c / (k * mu - lam)
    return wait + 1.0 / mu


def expected_queue_delay(k: int, lam: float, mu: float) -> float:
    """Expected time spent waiting in queue only, E[W] = E[T] - 1/mu."""
    t = expected_sojourn(k, lam, mu)
    return t - 1.0 / mu if math.isfinite(t) else math.inf


def expected_sojourn_factorial(k: int, lam: float, mu: float) -> float:
    """Paper-literal Eq. (1)-(2) with explicit factorials.

    Oracle for tests; overflows for large k — callers should prefer
    :func:`expected_sojourn`.
    """
    if lam == 0.0:
        return 1.0 / mu
    a = lam / mu
    if k <= a:
        return math.inf
    rho = a / k
    # pi_0 per Eq. (2)
    s = sum(a**l / math.factorial(l) for l in range(k))
    s += a**k / (math.factorial(k) * (1.0 - rho))
    pi0 = 1.0 / s
    wait = (a**k) * pi0 / (math.factorial(k) * (1.0 - rho) ** 2 * mu * k)
    return wait + 1.0 / mu


def keyed_capacity(k: int, mu: float, hot: float) -> float:
    """Input rate at which a keyed operator's hot partition, which takes
    ``h + (1 - h)/k`` of it (k floored at 1), saturates."""
    return mu / (hot + (1.0 - hot) / max(k, 1))


def keyed_sojourn(k: int, lam: float, mu: float, hot: float) -> float:
    """E[T](k) of a keyed operator: k M/M/1 partitions, one of them hot
    (module docstring).  +inf when k < 1 or the hot partition is
    unstable.  The float operations are those of the batched tables
    (``core/batched.py``), so their finite entries are bit-identical."""
    if k < 1:
        return math.inf
    p_cold = (1.0 - hot) / k
    p_hot = hot + p_cold
    lam_hot = lam * p_hot
    if not lam_hot < mu:
        return math.inf
    return p_hot / (mu - lam_hot) + (k - 1) * p_cold / (mu - lam * p_cold)


def min_stable_k(lam: float, mu: float, hot: float | None = None) -> int:
    """Smallest k with finite E[T]: ceil(lam/mu), bumped when lam/mu is integral.

    With ``hot`` (a keyed operator's hot-key share) the hot partition sets
    it instead: the least k with ``lam (h + (1 - h)/k) < mu``, which is
    ``floor(lam (1 - h) / (mu - lam h)) + 1``.  Raises ``ValueError`` where
    the hot key alone saturates a processor (``lam h >= mu``).

    Paper Algorithm 1 initialises k_i = ceil(lam_i/mu_i); when lam/mu is an
    exact integer that k gives k*mu == lam which is *unstable*, so one more
    processor is required for a finite sojourn time.  (The paper's pseudocode
    glosses this; its Eq. (1) makes k = lam/mu infinite, and the while-loop
    would immediately add the extra processor anyway.)
    """
    if hot is not None:
        if not lam * hot < mu:
            raise ValueError(
                f"hot key carries {lam * hot:.6g} tuples/s >= mu = {mu:.6g}: "
                "no partition count keeps the hot partition stable"
            )
        k = max(math.floor(lam * (1.0 - hot) / (mu - lam * hot)) + 1, 1)
        # The closed form rounds; settle on the table's own stability test.
        while k > 1 and math.isfinite(keyed_sojourn(k - 1, lam, mu, hot)):
            k -= 1
        while not math.isfinite(keyed_sojourn(k, lam, mu, hot)):
            k += 1
        return k
    if lam == 0.0:
        return 1
    a = lam / mu
    k = math.ceil(a)
    if k <= a:  # a integral
        k += 1
    return max(k, 1)


def sojourn_curve(lam: float, mu: float, k_lo: int, k_hi: int) -> np.ndarray:
    """Vector of E[T](k) for k in [k_lo, k_hi], sharing one B-recursion pass."""
    if k_lo < 0 or k_hi < k_lo:
        raise ValueError(f"bad range [{k_lo}, {k_hi}]")
    if lam == 0.0:
        return np.full(k_hi - k_lo + 1, 1.0 / mu)
    a = lam / mu
    out = np.empty(k_hi - k_lo + 1, dtype=np.float64)
    b = 1.0
    for j in range(1, k_hi + 1):
        b = a * b / (j + a * b)
        if j >= k_lo:
            if j <= a:
                out[j - k_lo] = math.inf
            else:
                c = j * b / (j - a * (1.0 - b))
                out[j - k_lo] = c / (j * mu - lam) + 1.0 / mu
    if k_lo == 0:
        out[0] = math.inf
    return out


def marginal_benefit(k: int, lam: float, mu: float) -> float:
    """delta(k) = lam * (E[T](k) - E[T](k+1)) — Algorithm 1 line 9.

    By convexity of E[T](k) (paper Ineq. 5) this is non-increasing in k,
    which is what makes both the greedy and the heap allocator optimal.
    Returns +inf when E[T](k) is infinite (processor is mandatory).
    """
    t_k = expected_sojourn(k, lam, mu)
    t_k1 = expected_sojourn(k + 1, lam, mu)
    if math.isinf(t_k):
        return math.inf
    return lam * (t_k - t_k1)


@lru_cache(maxsize=65536)
def _cached_sojourn(k: int, lam: float, mu: float) -> float:
    return expected_sojourn(k, lam, mu)


def cached_sojourn(k: int, lam: float, mu: float) -> float:
    """Memoised E[T](k) — the scheduler loop re-evaluates the same points."""
    return _cached_sojourn(k, float(lam), float(mu))
