"""Vectorized discrete-time batch simulator for scenario matrices (DESIGN.md §13).

The event DES (`streaming/des.py`) is the repo's high-fidelity validator —
and a scalar Python heapq loop, so sweeping hundreds of scenarios through
it is minutes of wall-clock.  This module advances **B scenarios x N
operators in parallel** with a discrete-time fluid/queue recurrence:

    served_t   = min(q_t, k * mu_eff * dt)          # drain step-start backlog
    inflow_t   = ext_t + served_{t-1} @ P           # one-step hop delay
    admitted_t = min(inflow_t, max(cap_queue - (q_t - served_t), 0))
    q_{t+1}    = q_t - served_t + admitted_t,  dropped_t = inflow_t - admitted_t

External arrivals ``ext_t`` are **pre-sampled counts** (seeded numpy
Poisson for stochastic kinds, exact ``rate * dt`` for deterministic), so
both backends consume identical randomness:

* **numpy float64** — the bit-exact debugging twin (same seed => bit-
  identical ``BatchSimResult``), and the default off-TPU;
* **jax** — ``jit`` over a ``lax.scan`` whose per-step bounded-queue
  update dispatches through ``kernels/queue_step`` (Pallas on TPU, jnp
  oracle elsewhere; ``force_kernel=True, interpret=True`` exercises the
  kernel on CPU).  Dtype follows JAX's active precision: float64 under
  ``enable_x64`` (matches the twin to ~1e-9), float32 otherwise.

Overload semantics mirror DESIGN.md §11: ``cap_queue = +inf`` encodes
unbounded queues AND the ``block`` policy (blocked producers hold tuples
in a pending line — backlog grows, nothing is shed), finite ``cap_queue``
encodes the shed policies (in fluid volume terms ``shed-newest`` and
``shed-oldest`` drop identical mass; only tuple *age* differs, which a
fluid model does not represent).  Per-operator drop accounting splits
each step's shed mass proportionally between external and routed inflow
so the admitted external rate stays unbiased, exactly like the DES's
``lam0_hat`` rule.

Divergence vs the event DES (bounds in DESIGN.md §13/§17): the fluid
recurrence itself carries no stationary stochastic queueing delay (its
post-warmup backlog is ~0 whenever rho < 1), so the *measurement* layer
composes two wait terms per operator:

* :func:`little_wait` — Little's law on the time-averaged backlog minus
  the one-step admission floor.  Captures rate-driven (overload / trace
  peak) queueing; ~0 in steady stable state.
* :func:`stationary_wait` — the Erlang-C M/M/k waiting time at the
  admitted rate, scaled by the Allen-Cunneen factor ``(ca^2 + cs^2)/2``
  (``ca2``/``cs2`` are the squared coefficients of variation of the
  scenario's inter-arrival and service laws — 1 exponential, 1/3
  uniform, 0 deterministic, cv^2 lognormal).  Captures the stochastic
  waiting the fluid backlog cannot; identically 0 for deterministic/
  deterministic scenarios, so those stay fluid-exact.

The composed estimate is ``max(little, min(stationary, span))`` — max
avoids double counting (the fluid backlog already *is* queueing where it
exists), and the ``span`` clamp keeps a near-saturated window from
reporting a stationary wait longer than the window that measured it.
Throughputs, drop rates, and the saturated-operator set agree with the
DES; DESIGN.md §17 quantifies the sojourn bounds per scenario family.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Sequence

import numpy as np

__all__ = [
    "BatchArrays",
    "BatchSimResult",
    "BatchQueueSim",
    "composed_wait",
    "refuse_keyed",
    "service_capacity",
    "stationary_wait",
    "window_step_fn",
]

# Static iteration bound for the masked Erlang-B recurrence in
# :func:`stationary_wait` — covers every allocation the repo's zoo and
# fleet tables reach (k_max <= 64 per scenario, 512 in the fleet tier).
# Iterations past a lane's k are where-masked no-ops, so the numpy twin
# may stop at max(k) while the jit path always runs to the cap: both
# orderings produce bit-identical lanes.
STATIONARY_K_CAP = 512


def refuse_keyed(names: Sequence[str]) -> None:
    """Raise where keyed operators would enter the window simulation: its
    queues are one per operator, served at the pooled ``k * mu``, and it
    has no per-partition queues to hold a hot key (DESIGN.md §20)."""
    if names:
        raise ValueError(
            f"keyed operators {sorted(set(names))}: the window simulation has "
            "no per-partition queues (a hot key's partition saturates before "
            "k * mu); simulate keyed graphs with the DES (streaming/des.py)"
        )


def service_capacity(k, mu, group, alpha, speed=None):
    """Per-operator service rate (tuples/sec) at allocation ``k`` — replica
    ``k * mu``, chip-gang ``mu * k * eff(k)`` (DESIGN.md §2).  ``speed``
    applies per-operator machine-class factors (heterogeneous pools,
    paper §III-A): processors of class s serve at ``s * mu``."""
    k = np.maximum(np.asarray(k, dtype=np.float64), 0.0)
    if speed is not None:
        mu = mu * speed
    with np.errstate(divide="ignore", invalid="ignore"):
        eff = 1.0 / (1.0 + alpha * (k - 1.0))
    return np.where(group, mu * k * eff, mu * k)


def little_wait(q_mean, admitted_rate, dt: float):
    """Little's-law per-operator wait from a time-averaged backlog, minus
    the one-step admission floor (a tuple admitted at step t is served
    earliest at step t+1 — the known discretization bias, DESIGN.md §13)."""
    with np.errstate(divide="ignore", invalid="ignore"):
        return np.where(
            admitted_rate > 0,
            np.maximum(q_mean / np.maximum(admitted_rate, 1e-300) - dt, 0.0),
            0.0,
        )


def stationary_wait(k, lam, mu, group, alpha, speed=None, ca2=None, cs2=None, xp=np):
    """Stationary stochastic queueing wait per operator (DESIGN.md §17).

    Erlang-C M/M/k waiting time ``C(k, a) / (k*mu - lam)`` at the admitted
    rate ``lam``, scaled by the Allen-Cunneen G/G/k factor
    ``(ca2 + cs2) / 2``.  Replica operators are M/M/k at per-server rate
    ``mu * speed``; chip-gang operators collapse to one effective server
    at the gang capacity (M/M/1), mirroring :func:`service_capacity`.
    Zero where the lane is idle (``lam == 0``), unallocated (``k == 0``),
    or not stable (``rho >= 1`` — there the fluid backlog term owns the
    wait).  ``ca2``/``cs2`` default to 1 (the M/M/k case).

    ``xp`` selects the array namespace: ``numpy`` (the float64 twin) or
    ``jax.numpy`` (the fused jit tick).  Both run the *same* masked
    Erlang-B recurrence ``B_j = a B_{j-1} / (j + a B_{j-1})`` in the same
    op order, so twin and jit agree to float-rounding on every lane.
    """
    # k * 1.0 promotes the integer allocation to mu's float dtype (exact
    # for any realistic k) identically under numpy and jnp.
    kf = xp.maximum(xp.asarray(k) * xp.ones_like(mu), 0.0)
    mu_rep = mu if speed is None else mu * speed
    one = 1.0
    with np.errstate(divide="ignore", invalid="ignore"):
        eff = one / (one + alpha * (kf - one))
        cap = xp.where(group, mu_rep * kf * eff, mu_rep * kf)
        k_srv = xp.where(group, xp.minimum(kf, one), kf)
        mu_srv = xp.where(group, cap, mu_rep + xp.zeros_like(cap))
        a = lam / xp.maximum(mu_srv, 1e-300)
        b = xp.ones_like(a)
        if xp is np:
            j_hi = int(min(max(float(np.max(k_srv, initial=1.0)), 1.0),
                           STATIONARY_K_CAP))
            for j in range(1, j_hi + 1):
                jf = float(j)
                b = xp.where(j <= k_srv, a * b / (jf + a * b), b)
        else:
            from jax import lax

            def body(j, bb):
                jf = j.astype(bb.dtype)
                return xp.where(jf <= k_srv, a * bb / (jf + a * bb), bb)

            b = lax.fori_loop(1, STATIONARY_K_CAP + 1, body, b)
        c = k_srv * b / xp.maximum(k_srv - a * (one - b), 1e-300)
        wait = c / xp.maximum(k_srv * mu_srv - lam, 1e-300)
        scv = one if ca2 is None and cs2 is None else 0.5 * (
            (one if ca2 is None else ca2) + (one if cs2 is None else cs2)
        )
        wait = wait * scv
        stable = (lam > 0) & (k_srv >= one) & (lam < k_srv * mu_srv * (1.0 - 1e-9))
    return xp.where(stable, wait, 0.0)


def composed_wait(q_mean, admitted_rate, dt, span, k, mu, group, alpha,
                  speed=None, ca2=None, cs2=None, xp=np):
    """The §17 measurement-surface wait: ``max(little, min(stationary,
    span))`` — one function so the numpy twin, the window measurement, and
    the fused jit tick compose the two terms in the same op order."""
    if xp is np:
        fluid = little_wait(q_mean, admitted_rate, dt)
    else:
        fluid = xp.where(
            admitted_rate > 0,
            xp.maximum(q_mean / xp.maximum(admitted_rate, 1e-300) - dt, 0.0),
            0.0,
        )
    stat = stationary_wait(
        k, admitted_rate, mu, group, alpha, speed, ca2, cs2, xp=xp
    )
    return xp.maximum(fluid, xp.minimum(stat, span))


def per_op_service_time(cap, mu, group):
    """Per-tuple service time: 1/mu per replica server, 1/(gang capacity)
    for chip-gang operators (DESIGN.md §2)."""
    with np.errstate(divide="ignore", invalid="ignore"):
        return np.where(group, np.where(cap > 0, 1.0 / cap, np.inf), 1.0 / mu)


def visit_sum_sojourn(admitted_rate, wait, svc, ext_rate):
    """Eq.-3-style visit-sum E[T]: sum_i admitted_i * (W_i + S_i) / lam0.
    NaN where no external tuples were admitted (no sojourn is defined —
    mirrors the measurer's empty-window behaviour)."""
    contrib = np.where(admitted_rate > 0, admitted_rate * (wait + svc), 0.0)
    total = contrib.sum(axis=-1)
    return np.where(ext_rate > 0, total / np.maximum(ext_rate, 1e-300), np.nan)


@dataclass(frozen=True)
class BatchArrays:
    """Packed inputs for one batch run (index order per scenario is the
    scenario's AppGraph operator order, padded to the batch-wide N_max
    with zero-traffic lanes)."""

    ext: np.ndarray  # [T, B, N] external arrival counts per step (tuples)
    routing: np.ndarray  # [B, N, N] expected multiplicities
    mu: np.ndarray  # [B, N] per-processor service-rate priors
    group: np.ndarray  # [B, N] bool: chip-gang scaling
    alpha: np.ndarray  # [B, N] group efficiency rolloff
    cap_queue: np.ndarray  # [B, N] queue bound (+inf = unbounded / block)
    dt: float  # step length (seconds)
    warmup_steps: int  # steps excluded from rate/backlog accounting
    # [B, N] bool: which lanes are real operators.  Consumer metadata for
    # slicing batch results back to per-scenario shape — the dynamics need
    # no mask (padding lanes carry zero arrivals, routing, and capacity,
    # so they stay identically zero).
    active: np.ndarray
    # [B, N] machine-class speed factors (None = homogeneous reference
    # class).  Scales service capacity; the controller applies the same
    # factors on the model side (DESIGN.md §14).
    speed: np.ndarray | None = None
    # [B, N] squared coefficients of variation of the inter-arrival and
    # service laws (DESIGN.md §17) — the Allen-Cunneen inputs to
    # :func:`stationary_wait`.  None = 1.0 everywhere (the M/M/k prior);
    # pack_scenarios fills them from each scenario's arrival/service kind.
    ca2: np.ndarray | None = None
    cs2: np.ndarray | None = None

    def __post_init__(self):
        t, b, n = self.ext.shape
        names = ["routing", "mu", "group", "alpha", "cap_queue", "active"]
        for opt in ("speed", "ca2", "cs2"):
            if getattr(self, opt) is not None:
                names.append(opt)
        for name in names:
            got = getattr(self, name).shape
            want = (b, n, n) if name == "routing" else (b, n)
            if got != want:
                raise ValueError(f"{name} must be {want}, got {got}")
        if not 0 <= self.warmup_steps <= t:
            raise ValueError(f"warmup_steps must be in [0, {t}], got {self.warmup_steps}")

    @property
    def steps(self) -> int:
        return self.ext.shape[0]

    @property
    def batch(self) -> int:
        return self.ext.shape[1]

    @property
    def n(self) -> int:
        return self.ext.shape[2]

    def pad_batch(self, b_total: int) -> "BatchArrays":
        """Append ``b_total - B`` inert batch lanes (device-mesh padding,
        DESIGN.md §16): zero arrivals/routing, unit service rate, inactive.
        Such lanes stay identically zero through the recurrence and the
        controller provably decides ``"none"`` on them, so padding never
        influences real scenarios."""
        t, b, n = self.ext.shape
        if b_total < b:
            raise ValueError(f"b_total {b_total} < batch {b}")
        if b_total == b:
            return self
        pad = b_total - b
        return BatchArrays(
            ext=np.concatenate([self.ext, np.zeros((t, pad, n))], axis=1),
            routing=np.concatenate([self.routing, np.zeros((pad, n, n))]),
            mu=np.concatenate([self.mu, np.ones((pad, n))]),
            group=np.concatenate([self.group, np.zeros((pad, n), dtype=bool)]),
            alpha=np.concatenate([self.alpha, np.zeros((pad, n))]),
            cap_queue=np.concatenate([self.cap_queue, np.full((pad, n), np.inf)]),
            dt=self.dt,
            warmup_steps=self.warmup_steps,
            active=np.concatenate([self.active, np.zeros((pad, n), dtype=bool)]),
            speed=None if self.speed is None
            else np.concatenate([self.speed, np.ones((pad, n))]),
            ca2=None if self.ca2 is None
            else np.concatenate([self.ca2, np.ones((pad, n))]),
            cs2=None if self.cs2 is None
            else np.concatenate([self.cs2, np.ones((pad, n))]),
        )


@dataclass
class BatchSimResult:
    """Post-warmup aggregates for every scenario in the batch.

    Rates are per second of post-warmup simulated time; ``sojourn`` is the
    Little's-law visit-sum estimate comparable to the DES's
    ``mean_visit_sum`` (waiting from the time-averaged backlog, service
    from the effective rate at the final allocation)."""

    offered: np.ndarray  # [B, N] tuples offered at each queue tail
    served: np.ndarray  # [B, N] tuples served
    dropped: np.ndarray  # [B, N] tuples shed
    ext_admitted: np.ndarray  # [B] external tuples admitted
    ext_offered: np.ndarray  # [B] external tuples offered
    q_final: np.ndarray  # [B, N] backlog at the horizon
    q_mean: np.ndarray  # [B, N] time-averaged backlog (post-warmup)
    max_backlog: np.ndarray  # [B, N] peak backlog (whole run)
    span: float  # post-warmup simulated seconds
    dt: float  # step length (for the discretization-bias correction)
    per_op_wait: np.ndarray = field(init=False)  # [B, N] Little's-law wait
    arrival_rate: np.ndarray = field(init=False)  # [B, N] offered tuples/s
    drop_rate: np.ndarray = field(init=False)  # [B, N] shed tuples/s

    def __post_init__(self):
        span = max(self.span, 1e-12)
        self.arrival_rate = self.offered / span
        self.drop_rate = self.dropped / span
        admitted_rate = (self.offered - self.dropped) / span
        self.per_op_wait = little_wait(self.q_mean, admitted_rate, self.dt)

    def sojourn(self, k, mu, group, alpha, speed=None, *,
                ca2=None, cs2=None) -> np.ndarray:
        """[B] visit-sum E[T] estimate at allocation ``k`` (Eq. 3 analogue):
        sum_i admitted_rate_i * (W_i + S_i) / external admitted rate, with
        S_i the per-tuple service time at the (possibly gang) allocation
        and W_i the §17 composed wait (fluid backlog term max'd with the
        Allen-Cunneen stationary term at the scenario's ``ca2``/``cs2``).
        NaN for scenarios that admitted no external tuples."""
        cap = service_capacity(k, mu, group, alpha, speed)
        svc = per_op_service_time(cap, mu if speed is None else mu * speed, group)
        span = max(self.span, 1e-12)
        admitted_rate = (self.offered - self.dropped) / span
        ext_rate = self.ext_admitted / span
        wait = composed_wait(
            self.q_mean, admitted_rate, self.dt, span, k, mu, group, alpha,
            speed, ca2, cs2,
        )
        return visit_sum_sojourn(admitted_rate, wait, svc, ext_rate)

    def saturated(
        self, k, mu, group, alpha, speed=None, *, drop_fraction: float = 0.01
    ) -> np.ndarray:
        """[B, N] bool: offered load at/above capacity, or sustained
        shedding — mirrors ``DRSScheduler.overloaded_mask``."""
        cap = service_capacity(k, mu, group, alpha, speed)
        hot = (self.arrival_rate >= cap * (1.0 - 1e-9)) | (
            self.drop_rate > drop_fraction * np.maximum(cap, 1e-300)
        )
        return hot & (self.arrival_rate > 0)  # idle/padding lanes are never hot


# --------------------------------------------------------------------------- #
# numpy float64 twin
# --------------------------------------------------------------------------- #
def _np_window(q, served_prev, ext_chunk, warm, cap_serve_dt, cap_queue, routing):
    """Advance one window in float64 numpy; returns final state + sums."""
    b, n = q.shape
    offered = np.zeros((b, n))
    served_sum = np.zeros((b, n))
    dropped = np.zeros((b, n))
    ext_adm = np.zeros(b)
    ext_off = np.zeros(b)
    q_int = np.zeros((b, n))
    q_max = np.zeros((b, n))
    for t in range(ext_chunk.shape[0]):
        ext_t = ext_chunk[t]
        served = np.minimum(q, cap_serve_dt)
        q1 = q - served
        routed = np.einsum("bi,bij->bj", served_prev, routing)
        inflow = ext_t + routed
        space = np.maximum(cap_queue - q1, 0.0)
        admitted = np.minimum(inflow, space)
        drop_t = inflow - admitted
        q = q1 + admitted
        with np.errstate(divide="ignore", invalid="ignore"):
            adm_frac = np.where(inflow > 0, admitted / np.maximum(inflow, 1e-300), 1.0)
        w = warm[t]
        offered += w * inflow
        served_sum += w * served
        dropped += w * drop_t
        ext_adm += w * (ext_t * adm_frac).sum(axis=-1)
        ext_off += w * ext_t.sum(axis=-1)
        q_int += w * q
        q_max = np.maximum(q_max, q)
        served_prev = served
    return q, served_prev, offered, served_sum, dropped, ext_adm, ext_off, q_int, q_max


# --------------------------------------------------------------------------- #
# jax path (lax.scan; per-step update through kernels/queue_step)
# --------------------------------------------------------------------------- #
_JIT_CACHE: dict = {}


def window_step_fn(*, interpret: bool = False, force_kernel: bool = False):
    """The batch simulator's window step in controller-consumable form.

    Returns ``window(q, served_prev, ext_chunk, warm, cap_serve_dt,
    cap_queue, routing)`` — a pure, traceable function advancing a whole
    control window (one lax.scan over the chunk's steps, each step's
    bounded-queue update dispatching through ``kernels/queue_step``) that
    the fused control loop (core/controller.py ``make_fused_loop``) scans
    *again* across ticks.  It carries **dual accumulators**: the ungated
    window sums (the §13 measurement surface a synthetic snapshot is made
    of) and the ``warm``-weighted sums (the whole-run post-warmup
    aggregates), so one pass serves both consumers.

    Output tuple (15): ``q, served_prev`` (state), then ungated
    ``offered, served, dropped, ext_admitted, ext_offered, q_int, q_max``
    ([B, N] / [B]), then warm-gated ``offered, served, dropped,
    ext_admitted, ext_offered, q_int``.
    """
    import jax
    import jax.numpy as jnp

    from ..kernels.queue_step import ops as qs_ops

    def window(q, served_prev, ext_chunk, warm, cap_serve_dt, cap_queue, routing):
        b, n = q.shape
        capq_flat = cap_queue.reshape(-1)
        caps_flat = cap_serve_dt.reshape(-1)

        def step(carry, xs):
            (q, served_prev, offered, served_sum, dropped, ext_adm, ext_off,
             q_int, q_max, w_off, w_srv, w_drop, w_ea, w_eo, w_qi) = carry
            ext_t, w = xs
            routed = jnp.einsum("bi,bij->bj", served_prev, routing)
            inflow = ext_t + routed
            q_next_f, served_f, drop_f = qs_ops.queue_step(
                q.reshape(-1), inflow.reshape(-1), caps_flat, capq_flat,
                interpret=interpret, force_kernel=force_kernel,
            )
            q_next = q_next_f.reshape(b, n).astype(q.dtype)
            served = served_f.reshape(b, n).astype(q.dtype)
            drop_t = drop_f.reshape(b, n).astype(q.dtype)
            admitted = inflow - drop_t
            adm_frac = jnp.where(inflow > 0, admitted / jnp.maximum(inflow, 1e-300), 1.0)
            ext_adm_t = (ext_t * adm_frac).sum(axis=-1)
            ext_off_t = ext_t.sum(axis=-1)
            carry = (
                q_next,
                served,
                offered + inflow,
                served_sum + served,
                dropped + drop_t,
                ext_adm + ext_adm_t,
                ext_off + ext_off_t,
                q_int + q_next,
                jnp.maximum(q_max, q_next),
                w_off + w * inflow,
                w_srv + w * served,
                w_drop + w * drop_t,
                w_ea + w * ext_adm_t,
                w_eo + w * ext_off_t,
                w_qi + w * q_next,
            )
            return carry, None

        zeros = jnp.zeros_like(q)
        zb = jnp.zeros(b, q.dtype)
        init = (q, served_prev, zeros, zeros, zeros, zb, zb, zeros, zeros,
                zeros, zeros, zeros, zb, zb, zeros)
        out, _ = jax.lax.scan(step, init, (ext_chunk, warm))
        return out

    return window


def _jax_window_fn(interpret: bool, force_kernel: bool):
    """BatchQueueSim's window view: the warm-weighted accumulator set of
    :func:`window_step_fn` (plus the unweighted peak backlog)."""
    dual = window_step_fn(interpret=interpret, force_kernel=force_kernel)

    def window(q, served_prev, ext_chunk, warm, cap_serve_dt, cap_queue, routing):
        (q1, sp1, _off, _srv, _drop, _ea, _eo, _qi, q_max,
         w_off, w_srv, w_drop, w_ea, w_eo, w_qi) = dual(
            q, served_prev, ext_chunk, warm, cap_serve_dt, cap_queue, routing
        )
        return (q1, sp1, w_off, w_srv, w_drop, w_ea, w_eo, w_qi, q_max)

    return window


class BatchQueueSim:
    """Stateful batch simulator: B scenarios advanced window by window.

    ``step_window(k, n_steps)`` advances every scenario under (per-
    scenario) allocation ``k`` and returns that window's aggregates — the
    measurement surface ``ScenarioRunner`` turns into synthetic
    :class:`~repro.core.measurer.MeasurementSnapshot`s.  ``run(k)`` is the
    one-shot whole-horizon convenience.
    """

    def __init__(
        self,
        arrays: BatchArrays,
        *,
        backend: str = "numpy",
        interpret: bool = False,
        force_kernel: bool = False,
    ):
        if backend not in ("numpy", "jax"):
            raise ValueError(f"unknown backend {backend!r}; expected numpy|jax")
        self.arrays = arrays
        self.backend = backend
        self._t = 0  # next step index
        b, n = arrays.batch, arrays.n
        self.q = np.zeros((b, n))
        self._served_prev = np.zeros((b, n))
        # Post-warmup whole-run accumulators (run() / finalize view):
        self._offered = np.zeros((b, n))
        self._served = np.zeros((b, n))
        self._dropped = np.zeros((b, n))
        self._ext_adm = np.zeros(b)
        self._ext_off = np.zeros(b)
        self._q_int = np.zeros((b, n))
        self._q_max = np.zeros((b, n))
        if backend == "jax":
            import jax

            key = (interpret, force_kernel)
            if key not in _JIT_CACHE:  # share traces across sim instances
                _JIT_CACHE[key] = jax.jit(_jax_window_fn(interpret, force_kernel))
            self._window_jit = _JIT_CACHE[key]

    @property
    def now(self) -> float:
        return self._t * self.arrays.dt

    @property
    def step_index(self) -> int:
        """Next step to simulate (== arrays.steps once exhausted)."""
        return self._t

    def capacity(self, k) -> np.ndarray:
        a = self.arrays
        return service_capacity(k, a.mu, a.group, a.alpha, a.speed)

    # ------------------------------------------------------------------ #
    def step_window(self, k, n_steps: int | None = None) -> dict:
        """Advance ``n_steps`` (default: to the horizon) under allocation
        ``k`` ([B, N] ints).  Returns this window's aggregates (offered /
        served / dropped tuples per op, admitted external tuples, backlog
        integral) as plain numpy arrays — *without* the warmup gate, so
        the caller can measure any window; the whole-run accumulators
        apply the warmup mask themselves."""
        a = self.arrays
        if n_steps is None:
            n_steps = a.steps - self._t
        n_steps = min(n_steps, a.steps - self._t)
        if n_steps <= 0:
            raise ValueError("simulation horizon exhausted")
        t0, t1 = self._t, self._t + n_steps
        ext_chunk = a.ext[t0:t1]
        warm_run = (np.arange(t0, t1) >= a.warmup_steps).astype(np.float64)
        ones = np.ones(n_steps)
        cap_serve_dt = self.capacity(k) * a.dt
        if self.backend == "jax":
            import jax.numpy as jnp

            out = self._window_jit(
                jnp.asarray(self.q), jnp.asarray(self._served_prev),
                jnp.asarray(ext_chunk), jnp.asarray(ones),
                jnp.asarray(cap_serve_dt), jnp.asarray(a.cap_queue),
                jnp.asarray(a.routing),
            )
            (q, served_prev, offered, served_sum, dropped,
             ext_adm, ext_off, q_int, q_max) = (np.asarray(x, dtype=np.float64) for x in out)
        else:
            (q, served_prev, offered, served_sum, dropped,
             ext_adm, ext_off, q_int, q_max) = _np_window(
                self.q, self._served_prev, ext_chunk, ones,
                cap_serve_dt, a.cap_queue, a.routing,
            )
        # Whole-run accumulators are warmup-gated; a window that straddles
        # the warmup boundary is re-run on the gated mask (numpy, cheap)
        # only when the gate actually differs.
        if warm_run.all():
            self._offered += offered
            self._served += served_sum
            self._dropped += dropped
            self._ext_adm += ext_adm
            self._ext_off += ext_off
            self._q_int += q_int
        elif warm_run.any():
            (_q2, _sp2, off_w, srv_w, drop_w, ea_w, eo_w, qi_w, _qm2) = _np_window(
                self.q, self._served_prev, ext_chunk, warm_run,
                cap_serve_dt, a.cap_queue, a.routing,
            )
            self._offered += off_w
            self._served += srv_w
            self._dropped += drop_w
            self._ext_adm += ea_w
            self._ext_off += eo_w
            self._q_int += qi_w
        self._q_max = np.maximum(self._q_max, q_max)
        self.q = q
        self._served_prev = served_prev
        self._t = t1
        span = n_steps * a.dt
        return {
            "t0": t0 * a.dt,
            "t1": t1 * a.dt,
            "span": span,
            "offered": offered,
            "served": served_sum,
            "dropped": dropped,
            "ext_admitted": ext_adm,
            "ext_offered": ext_off,
            "q_mean": q_int / max(n_steps, 1),
            "q_final": q,
            "capacity": cap_serve_dt / a.dt,
        }

    def result(self) -> BatchSimResult:
        """Whole-run (post-warmup) aggregates so far."""
        a = self.arrays
        warm_steps = max(min(self._t, a.steps) - a.warmup_steps, 0)
        span = warm_steps * a.dt
        return BatchSimResult(
            offered=self._offered.copy(),
            served=self._served.copy(),
            dropped=self._dropped.copy(),
            ext_admitted=self._ext_adm.copy(),
            ext_offered=self._ext_off.copy(),
            q_final=self.q.copy(),
            q_mean=self._q_int / max(warm_steps, 1),
            max_backlog=self._q_max.copy(),
            span=span,
            dt=a.dt,
        )

    def run(self, k) -> BatchSimResult:
        """Advance to the horizon under a fixed allocation and aggregate."""
        self.step_window(k)
        return self.result()
