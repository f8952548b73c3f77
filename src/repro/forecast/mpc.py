"""MPC lookahead planner: price candidate allocations against the
forecast horizon, commit the cheapest plan that keeps E[T] under T_max
(DESIGN.md §15).

Where the reactive controller (core/controller.py) sizes Programs
(4)/(6) at the *measured* rates — and therefore always lags a ramp by
one control interval and ignores accumulated backlog — the planner here:

1. takes the predictor's per-operator offered-rate forecast
   ``lam_pred [B, H, N]`` (forecast/predictors.py);
2. sizes Program (6) at the **predicted peak** with one analytic pass:
   per-lane Algorithm-1 gains are non-increasing (paper Ineq. 5), so the
   greedy's E[T]-vs-increment curve is the floor E[T] minus the running
   sum of the globally sorted gains — the whole sizing is a sort + a
   cumsum, no sequential greedy, hence jit-able;
3. builds a small candidate set: hold the current allocation, the
   Program-6-at-peak sizing, and its +/- ``neighbor`` hysteresis
   neighbors (allocated via the same masked top-R gain selection the
   reactive jit decide uses — ``kernels/gain_topr``);
4. prices every candidate at every horizon step two ways and takes the
   worse: the analytic M/M/k visit-sum E[T] at the predicted rates
   (steady state), and a bounded-queue fluid rollout of the fused
   window recurrence started from the **actual backlog** ``q0`` (the
   drain-time term the steady-state model cannot see — this is what
   lets the planner keep scaling after a flash crowd until the queue is
   actually gone);
5. picks the cheapest candidate whose predicted E[T] stays under T_max
   across the whole horizon (ties prefer holding, and a cheaper plan
   must undercut ``scale_in_hysteresis * current`` to displace it).

``any_ok = False`` (no candidate survives) and a closed confidence gate
(:func:`~repro.forecast.predictors.confidence`) both mean "fall back to
the reactive ``decide_single`` path" — the caller owns that merge
(core/controller.py ``tick_batch`` / ``make_fused_loop``).

Twin/jit discipline: every function takes ``xp`` and runs the identical
float-op sequence under numpy float64 and jax (the Erlang recursion
mirrors ``core.batched.sojourn_table_jax`` term for term), so the numpy
twin and the compiled path agree to <= 1e-9 under x64 and the whole
predict -> simulate -> price -> commit step stays inside the one
``lax.scan`` program.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass, field

import numpy as np

from .predictors import (
    PredictorParams,
    confidence,
    error_init,
    error_update,
    forecast_rates,
    history_init,
    history_push,
)

__all__ = [
    "MPCConfig",
    "ProactiveController",
    "forecast_init_state",
    "forecast_step",
    "mpc_plan",
    "mpc_plan_compact",
    "gain_topr_np",
    "sojourn_table_arrays",
]

_TINY = 1e-300


def _quiet(fn):
    """The masked-inf arithmetic below is deliberate (infeasible lanes
    price to inf and are where()-ed out) — silence numpy's warnings the
    same way the batchsim twins do (no-op under the traced jax path)."""

    @functools.wraps(fn)
    def wrapped(*args, **kwargs):
        with np.errstate(divide="ignore", invalid="ignore", over="ignore"):
            return fn(*args, **kwargs)

    return wrapped


@dataclass(frozen=True)
class MPCConfig:
    """Knobs of the proactive mode (static: baked into the jit program).

    ``horizon`` is the lookahead in control ticks; ``window`` the rate
    history the predictors smooth over; ``neighbor`` the +/- budget step
    of the candidate set; the three gate knobs close the confidence gate
    (fallback to reactive) when the tracked one-step forecast error is
    too high or too young.  ``headroom`` mirrors the reactive
    Program-(6) provisioning guard.
    """

    horizon: int = 3
    window: int = 12
    predictor: PredictorParams = field(default_factory=PredictorParams)
    neighbor: int = 2
    headroom: float = 1.1
    min_scored: int = 3
    mase_gate: float = 2.0
    smape_gate: float = 0.25
    scale_in_hysteresis: float = 0.8

    def __post_init__(self):
        if self.horizon < 1:
            raise ValueError(f"need horizon >= 1 ticks, got {self.horizon}")
        if self.window < 2:
            raise ValueError(f"need window >= 2 ticks, got {self.window}")
        if self.predictor.kind == "seasonal" and self.window < self.predictor.season:
            raise ValueError(
                f"window {self.window} must cover one season "
                f"({self.predictor.season} ticks) for the seasonal predictor"
            )
        if self.neighbor < 1:
            raise ValueError(f"need neighbor >= 1, got {self.neighbor}")
        if not 0.0 <= self.scale_in_hysteresis <= 1.0:
            raise ValueError(
                f"need 0 <= scale_in_hysteresis <= 1, got {self.scale_in_hysteresis}"
            )


# --------------------------------------------------------------------------- #
# Forecast state plumbing (history window + error tracker as one tuple)
# --------------------------------------------------------------------------- #
def forecast_init_state(b: int, n: int, cfg: MPCConfig, xp=np, dtype=np.float64):
    """``(hist [B,W,N], prev_pred, prev_y, abs_err, naive_err, smape_sum,
    n_obs)`` — a flat tuple of arrays (lax.scan-carry compatible)."""
    return (history_init(b, cfg.window, n, xp=xp, dtype=dtype),) + error_init(
        b, n, xp=xp, dtype=dtype
    )


def forecast_step(state, lam_hat, active, cfg: MPCConfig, xp=np):
    """One tick of the predictor plane: score, push, forecast, gate.

    ``lam_hat [B, N]`` is the window's measured per-operator offered
    rate (non-finite / inactive lanes are treated as 0).  Returns
    ``(state', lam_pred [B, H, N], confident [B])``.
    """
    hist, err = state[0], state[1:]
    y = xp.where(active & xp.isfinite(lam_hat), lam_hat, 0.0)
    hist = history_push(hist, y, err[5], xp=xp)
    lam_pred = forecast_rates(hist, cfg.horizon, cfg.predictor, xp=xp)
    err = error_update(err, lam_pred[:, 0, :], y, xp=xp)
    conf = confidence(
        err,
        active,
        min_scored=cfg.min_scored,
        mase_gate=cfg.mase_gate,
        smape_gate=cfg.smape_gate,
        xp=xp,
    )
    return (hist,) + err, lam_pred, conf


# --------------------------------------------------------------------------- #
# Batched analytic tables (xp-agnostic mirror of core.batched.sojourn_table_jax)
# --------------------------------------------------------------------------- #
@_quiet
def sojourn_table_arrays(lam, mu, group, alpha, k_hi: int, xp=np):
    """``[..., N] -> [..., N, K+1]`` E[T_i](k) table, min_k = 1.

    Term-for-term mirror of :func:`repro.core.batched.sojourn_table_jax`
    (Erlang-B recursion ``b = a b / (j + a b)``, Erlang-C conversion,
    group M/M/1 closed form), written against ``xp`` so the numpy twin
    and the traced jax path produce bit-identical float64 values.  The
    recursion unrolls over the static ``k_hi``.
    """
    dtype = lam.dtype
    a_rep = lam / mu
    b = xp.ones_like(a_rep)
    rows = [b]
    for j in range(1, k_hi + 1):
        b = a_rep * b / (j + a_rep * b)
        rows.append(b)
    btab = xp.stack(rows, axis=-1)  # [..., N, K+1]
    ks = xp.arange(k_hi + 1, dtype=dtype)
    kk = ks[(None,) * (lam.ndim)]  # broadcast over every leading dim
    c = kk * btab / (kk - a_rep[..., None] * (1.0 - btab))
    t_rep = c / (kk * mu[..., None] - lam[..., None]) + 1.0 / mu[..., None]
    t_rep = xp.where(kk > a_rep[..., None], t_rep, xp.inf)
    eff = 1.0 / (1.0 + alpha[..., None] * (kk - 1.0))
    mu_eff = mu[..., None] * kk * eff
    a_grp = lam[..., None] / mu_eff
    bg = a_grp / (1.0 + a_grp)
    cg = bg / (1.0 - a_grp * (1.0 - bg))
    t_grp = cg / (mu_eff - lam[..., None]) + 1.0 / mu_eff
    t_grp = xp.where(a_grp < 1.0, t_grp, xp.inf)
    T = xp.where(group[..., None], t_grp, t_rep)
    return xp.where(kk >= 1.0, T, xp.inf)


def gain_topr_np(cand, budget):
    """Numpy float64 twin of ``kernels/gain_topr`` (threshold + row-major
    tie split — identical take-for-take to the jnp oracle)."""
    cand = np.asarray(cand, dtype=np.float64)
    budget = np.asarray(budget, dtype=np.int64)
    b, n, j = cand.shape
    flat = cand.reshape(b, n * j)
    pos = flat > 0
    pos_row = (cand > 0).sum(axis=-1)
    total_pos = pos.sum(axis=-1)
    use_all = total_pos <= budget
    vals = np.sort(np.where(pos, flat, -np.inf), axis=-1)[:, ::-1]
    idx = np.clip(budget - 1, 0, n * j - 1)
    thresh = np.take_along_axis(vals, idx[:, None], axis=-1)[:, 0]
    strict = ((cand > thresh[:, None, None]) & (cand > 0)).sum(-1)
    ties = ((cand == thresh[:, None, None]) & (cand > 0)).sum(-1)
    rem = budget - strict.sum(axis=-1)
    before = np.cumsum(ties, axis=-1) - ties
    extra = np.clip(np.minimum(ties, rem[:, None] - before), 0, None)
    take = np.where(use_all[:, None], pos_row, strict + extra)
    return np.where(budget[:, None] > 0, take, 0).astype(np.int64)


def _capacity(k, mu_eff, group, alpha, xp):
    """Effective service capacity at allocation ``k`` (group rolloff
    curve; k floored at 0 — the fused simulator's rule)."""
    kf = xp.maximum(k, 0) * xp.ones_like(mu_eff)
    eff = 1.0 / (1.0 + alpha * (kf - 1.0))
    return xp.where(group, mu_eff * kf * eff, mu_eff * kf)


def _price(T, k_vec, lam, lam0, k_hi: int, xp):
    """Visit-sum E[T] of allocation ``k_vec [..., N]`` under table ``T
    [..., N, K+1]`` at rates ``lam [..., N]`` / external ``lam0 [...]``."""
    idx = xp.clip(k_vec, 0, k_hi)[..., None]
    per_op = xp.take_along_axis(T, idx, axis=-1)[..., 0]
    contrib = xp.where(lam > 0, lam * per_op, 0.0)
    return contrib.sum(axis=-1) / xp.maximum(lam0, _TINY)


# --------------------------------------------------------------------------- #
# The planner
# --------------------------------------------------------------------------- #
@_quiet
def mpc_plan(
    lam_pred,
    q0,
    k_cur,
    *,
    mu,
    group,
    alpha,
    speed,
    active,
    src_mask,
    cap_queue,
    t_max,
    k_max,
    span: float,
    cfg: MPCConfig,
    k_hi: int,
    xp=np,
    topr=None,
):
    """One MPC planning pass over the forecast horizon.

    Inputs (all arrays; int/bool as noted): ``lam_pred [B, H, N]``
    predicted per-operator *offered* rates, ``q0 [B, N]`` current
    backlog, ``k_cur [B, N]`` current allocation, ``mu/group/alpha/
    speed/active/src_mask/cap_queue [B, N]`` model statics, ``t_max
    [B]`` (inf = no constraint), ``k_max [B]`` budgets, ``span`` seconds
    per control tick.  ``topr(cand [M,N,J], budget [M]) -> take [M,N]``
    is the top-R gain selection (defaults to the numpy twin; the jit
    path passes ``kernels/gain_topr``).

    Returns ``(k_plan [B, N] int, any_ok [B] bool, et_hold [B],
    et_plan [B], need [B] int)``: the committed allocation, whether any
    candidate met the constraint (False => reactive fallback), the
    predicted next-tick E[T] of holding vs the plan, and the raw
    Program-(6)-at-peak demand (headroom applied; feeds negotiator
    leases in the twin).
    """
    if topr is None:
        topr = gain_topr_np
    dtype = lam_pred.dtype
    b, h, n = lam_pred.shape
    lam_pred = xp.where(active[:, None, :], lam_pred, 0.0)
    mu_eff = mu * speed
    lam_peak = lam_pred.max(axis=1)  # [B, N]

    # ONE table pass for the peak + every horizon step: [B, H+1, N, K+1].
    lam_all = xp.concatenate([lam_peak[:, None, :], lam_pred], axis=1)
    shape = lam_all.shape
    T_all = sojourn_table_arrays(
        lam_all,
        xp.broadcast_to(mu_eff[:, None, :], shape) + xp.zeros(shape, dtype=dtype),
        xp.broadcast_to(group[:, None, :], shape),
        xp.broadcast_to(alpha[:, None, :], shape) + xp.zeros(shape, dtype=dtype),
        k_hi,
        xp=xp,
    )
    T_peak = T_all[:, 0]  # [B, N, K+1]
    T_h = T_all[:, 1:]  # [B, H, N, K+1]
    lam0_h = xp.maximum(
        xp.where(src_mask[:, None, :], lam_pred, 0.0).sum(axis=-1), _TINY
    )  # [B, H]
    lam0_peak = xp.maximum(xp.where(src_mask, lam_peak, 0.0).sum(axis=-1), _TINY)

    # Minimal feasible allocation at the predicted peak (first finite col).
    finite = xp.isfinite(T_peak)
    has_finite = finite.any(axis=-1)
    first = xp.argmax(finite, axis=-1).astype(xp.int32)
    k_start = xp.where(active, xp.where(has_finite, first, k_hi + 1), 0).astype(
        xp.int32
    )
    floor_total = k_start.sum(axis=-1)

    # Algorithm-1 candidate gains from k_start (the reactive jit decide's
    # construction, at the predicted peak instead of the measured rates).
    G = lam_peak[..., None] * (T_peak[..., :-1] - T_peak[..., 1:])
    G = xp.where(xp.isfinite(T_peak[..., :-1]), G, xp.inf)
    j = xp.arange(k_hi, dtype=xp.int32)
    idx = k_start[..., None] + j[None, None, :]
    cand = xp.take_along_axis(G, xp.clip(idx, 0, k_hi - 1), axis=-1)
    cand = xp.where(
        (idx < k_hi) & active[..., None] & xp.isfinite(cand), cand, 0.0
    )

    # Program (6) at the peak, closed form: per-lane gains are
    # non-increasing, so the greedy's E[T] after m increments is
    # et_floor - cumsum(sorted gains)[m-1] / lam0 — count how many
    # increments stay above T_max instead of walking them.
    et_floor = _price(T_peak, k_start, lam_peak, lam0_peak, k_hi, xp)
    g_sorted = xp.sort(cand.reshape(b, n * k_hi), axis=-1)[:, ::-1]
    ets = et_floor[:, None] - xp.cumsum(g_sorted, axis=-1) / lam0_peak[:, None]
    need_extra = xp.where(et_floor > t_max, 1, 0) + (
        ets[:, :-1] > t_max[:, None]
    ).sum(axis=-1)
    need = xp.ceil((floor_total + need_extra) * cfg.headroom).astype(xp.int32)

    # Candidate set: hold, Program-6-at-peak, +/- neighbor.
    step = int(cfg.neighbor)
    budgets = xp.stack([need, need - step, need + step], axis=-1)  # [B, 3]
    budgets = xp.clip(budgets, floor_total[:, None], k_max[:, None])
    extra = xp.clip(budgets - floor_total[:, None], 0, None).astype(xp.int32)
    cand_rep = xp.broadcast_to(cand[:, None, :, :], (b, 3, n, k_hi)).reshape(
        b * 3, n, k_hi
    )
    take = topr(cand_rep, extra.reshape(b * 3))
    k_alloc = k_start[:, None, :] + take.reshape(b, 3, n).astype(xp.int32)
    k_alloc = xp.where(active[:, None, :], k_alloc, 0)
    k_hold = xp.where(active, k_cur, 0).astype(xp.int32)[:, None, :]
    k_cand = xp.concatenate([k_hold, k_alloc], axis=1)  # [B, C=4, N]

    # Price every candidate at every horizon step: analytic steady state...
    kc = xp.clip(k_cand, 0, k_hi).astype(xp.int32)
    per_op = xp.take_along_axis(T_h[:, None], kc[:, :, None, :, None], axis=-1)[
        ..., 0
    ]  # [B, C, H, N]
    lam_h = lam_pred[:, None]  # [B, 1, H, N]
    contrib = xp.where(lam_h > 0, lam_h * per_op, 0.0)
    et_a = contrib.sum(axis=-1) / lam0_h[:, None, :]  # [B, C, H]

    # ...and a bounded-queue fluid rollout from the actual backlog (the
    # batch simulator's window recurrence at tick granularity; lam_pred
    # is already per-op offered rate, so no routing hop is re-applied).
    cap_rate = _capacity(k_cand, mu_eff[:, None, :], group[:, None, :],
                         alpha[:, None, :], xp)  # [B, C, N]
    svc = xp.where(
        group[:, None, :],
        xp.where(cap_rate > 0, 1.0 / xp.maximum(cap_rate, _TINY), xp.inf),
        1.0 / mu_eff[:, None, :],
    )
    q = xp.where(active, q0, 0.0)[:, None, :] + xp.zeros_like(cap_rate)
    et_roll = []
    for hi in range(h):
        lam_s = lam_pred[:, hi][:, None, :]  # [B, 1, N]
        avail = q + lam_s * span
        served = xp.minimum(avail, cap_rate * span)
        q = xp.minimum(avail - served, cap_queue[:, None, :])
        wait = xp.where(cap_rate > 0, q / xp.maximum(cap_rate, _TINY), xp.inf)
        contrib_r = xp.where(lam_s > 0, lam_s * (wait + svc), 0.0)
        et_roll.append(contrib_r.sum(axis=-1) / lam0_h[:, hi][:, None])
    et_r = xp.stack(et_roll, axis=-1)  # [B, C, H]
    et_hat = xp.maximum(et_a, et_r)

    # Feasible = under T_max across the horizon AND within budget.
    tot = k_cand.sum(axis=-1)  # [B, C]
    ok = (
        (et_hat <= t_max[:, None, None]).all(axis=-1)
        & (tot <= k_max[:, None])
        & (floor_total <= k_max)[:, None]
    )
    score = xp.where(ok, tot.astype(dtype), xp.inf)
    choice = xp.argmin(score, axis=-1)  # first min: ties prefer holding
    chosen_tot = xp.take_along_axis(tot, choice[:, None], axis=-1)[:, 0]
    hold_tot = tot[:, 0]
    keep_hold = (
        ok[:, 0]
        & (chosen_tot < hold_tot)
        & (chosen_tot > cfg.scale_in_hysteresis * hold_tot)
    )
    choice = xp.where(keep_hold, 0, choice)
    k_plan = xp.take_along_axis(k_cand, choice[:, None, None], axis=1)[:, 0]
    et_plan = xp.take_along_axis(et_hat, choice[:, None, None], axis=1)[:, 0, 0]
    return k_plan, ok.any(axis=-1), et_hat[:, 0, 0], et_plan, need


def mpc_plan_compact(eligible, lam_pred, q0, k_cur, *, k_max, **plan_kw):
    """:func:`mpc_plan` restricted to the ``eligible [B]`` lanes — the
    twin side of the trigger-gated compaction (DESIGN.md §18).

    A plan is only ever *committed* where the caller's
    ``use = conf & any_ok & complete & ~hot & isfinite(t_max)`` gate is
    open, and ``use`` is a subset of the eligibility mask the caller
    passes here (``conf & complete & ~hot & isfinite(t_max)``), so
    pricing only those lanes is exact: every op in :func:`mpc_plan` is
    per-lane, hence the gathered results are bitwise what a dense pass
    would produce for the same lanes.  Unpriced lanes return the
    fall-back row (``any_ok = False`` — reactive path — plus hold
    allocation, inf E[T], ``need = 0``); none of those defaults is read
    where ``use`` is False except the ``need`` diagnostic, which is
    documented to be 0 on unpriced lanes.
    """
    b = lam_pred.shape[0]
    active = np.asarray(plan_kw["active"], dtype=bool)
    k_plan = np.where(active, np.asarray(k_cur), 0).astype(np.int32)
    any_ok = np.zeros(b, dtype=bool)
    et_hold = np.full(b, np.inf, dtype=lam_pred.dtype)
    et_plan = np.full(b, np.inf, dtype=lam_pred.dtype)
    need = np.zeros(b, dtype=np.int32)
    idx = np.nonzero(np.asarray(eligible, dtype=bool))[0]
    if idx.size:

        def gather(v):
            arr = np.asarray(v)
            return arr[idx] if arr.ndim >= 1 and arr.shape[0] == b else v

        kp, ok, eh, ep, nd = mpc_plan(
            lam_pred[idx],
            np.asarray(q0)[idx],
            np.asarray(k_cur)[idx],
            k_max=np.asarray(k_max)[idx],
            **{key: gather(val) for key, val in plan_kw.items()},
        )
        k_plan[idx] = kp
        any_ok[idx] = ok
        et_hold[idx] = eh
        et_plan[idx] = ep
        need[idx] = nd
    return k_plan, any_ok, et_hold, et_plan, need


# --------------------------------------------------------------------------- #
# Twin-side stateful shell (numpy; the fused path carries the same state
# tuple through its lax.scan instead)
# --------------------------------------------------------------------------- #
@dataclass
class ProactiveController:
    """Forecast state + the sim-side statics the rollout needs, for the
    float64 twin paths (``tick_batch`` and ``DRSScheduler``).

    ``mpc_used`` / ``confident`` / ``need`` hold the last tick's [B]
    outcomes (trajectory surface for ScenarioRunner / benchmarks).
    """

    cfg: MPCConfig
    cap_queue: np.ndarray  # [B, N]
    span: float
    state: tuple
    mpc_used: np.ndarray | None = None
    confident: np.ndarray | None = None
    need: np.ndarray | None = None

    @classmethod
    def create(
        cls, b: int, n: int, cfg: MPCConfig, *, cap_queue=None, span: float = 10.0
    ) -> "ProactiveController":
        cap = (
            np.full((b, n), np.inf)
            if cap_queue is None
            else np.asarray(cap_queue, dtype=np.float64)
        )
        return cls(cfg, cap, float(span), forecast_init_state(b, n, cfg))
