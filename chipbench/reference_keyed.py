"""The plain reference for graphs with keyed operators: the DRS decide
written from its definitions, importing nothing of the program, and the
comparison that decides ``correct`` against it.

It is ``reference.py``'s decide with one more kind of operator.  A keyed
operator's k processors are k hash partitions of a keyed stream, each an
M/M/1 queue; its hot key carries a share ``h`` of the input and the rest
hashes evenly, so the hottest partition takes ``p_hot = h + (1 - h)/k`` of
the rate ``lam`` and each other one ``p_cold = (1 - h)/k``.  Then

* capacity (the overload trigger, the drain gate): ``mu / p_hot``;
* sojourn: ``T(k) = p_hot / (mu - lam p_hot) + (k - 1) p_cold / (mu - lam
  p_cold)`` for ``k >= 1``, infinite where ``lam p_hot >= mu``;
* utilization (the conditioning weights): ``lam p_hot / mu``.

Operators that are not keyed keep ``reference.py``'s M/M/k semantics.
Every function takes an array namespace ``xp`` and a float ``dtype``, as
there: numpy with float64 is the reference; ``jax.numpy`` with bfloat16
the control.
"""

from __future__ import annotations

import numpy as np

from chipbench import reference as ref


class Deployment(ref.Deployment):
    """``reference.Deployment`` with each operator's hot-key share
    (``hot``, NaN where the operator is not keyed)."""

    def __init__(self, cfg: dict):
        super().__init__(cfg)
        self.hot = np.array([
            float(op["hot_share"]) if op["scaling"] == "keyed" else np.nan
            for op in cfg["operators"]
        ])
        self.keyed = ~np.isnan(self.hot)


def _p_hot(xp, hot, k, dtype):
    """Share of the input at the hottest partition at allocation ``k``."""
    h = xp.asarray(np.nan_to_num(hot), dtype=dtype)
    return h + (1 - h) / xp.maximum(xp.asarray(k), 1).astype(dtype)


def capacity(xp, dtype, dep: Deployment, mu, k):
    """Per-operator capacity at allocation ``k`` (floored at 1)."""
    k1 = xp.maximum(xp.asarray(k), 1).astype(dtype)
    return xp.where(xp.asarray(dep.keyed), mu / _p_hot(xp, dep.hot, k, dtype), mu * k1)


def utilization(xp, dtype, dep: Deployment, a, k):
    """Busiest server's utilization per operator at ``k``; ``a = lam / mu``."""
    k1 = xp.maximum(xp.asarray(k), 1).astype(dtype)
    return xp.where(xp.asarray(dep.keyed), a * _p_hot(xp, dep.hot, k, dtype), a / k1)


def sojourn_table(xp, dtype, dep: Deployment, lam, mu, k_hi: int):
    """``[R, N, k_hi + 1]`` sojourn at k = 0 .. k_hi: M/M/k, or the keyed
    form where the operator is keyed."""
    t_rep = ref.sojourn_table(xp, lam, mu, k_hi)
    h = xp.asarray(np.nan_to_num(dep.hot), dtype=dtype)[..., None]
    cols = [xp.full(lam.shape, xp.inf, dtype=lam.dtype)]
    for j in range(1, k_hi + 1):
        p_cold = (1 - h[..., 0]) / j
        p_hot = h[..., 0] + p_cold
        t = p_hot / (mu - lam * p_hot) + (j - 1) * p_cold / (mu - lam * p_cold)
        cols.append(xp.where(lam * p_hot < mu, t, xp.inf))
    t_key = xp.stack(cols, axis=-1)
    return xp.where(xp.asarray(dep.keyed)[:, None], t_key, t_rep)


def decide(xp, dtype, dep: Deployment, lam_hat, mu_hat, drop_hat, lam0_hat, k_cur,
           k_other=None):
    """One tick's decision for ``R`` lanes, as ``reference.decide`` (same
    outputs and margins), with keyed operators priced by their hot
    partition."""
    f = lambda x: xp.asarray(x, dtype=dtype)
    n, k_hi, k_max = dep.n, dep.k_hi, dep.k_max
    lam_m, mu, lam0_m = f(lam_hat), f(mu_hat), f(lam0_hat)
    drops = xp.nan_to_num(f(drop_hat), nan=0.0)
    k = xp.asarray(k_cur).astype(np.int32)
    p = f(dep.routing)
    src = xp.asarray(dep.src)
    adj = xp.asarray(dep.routing > 0)
    keyed = xp.asarray(dep.keyed)
    one = f(1.0)

    # --- overload trigger and the operators downstream of a hot one ------ #
    cap = capacity(xp, dtype, dep, mu, k)
    valid = xp.isfinite(lam_m) & xp.isfinite(mu) & (mu > 0)
    over = valid & ((lam_m >= cap * (1 - 1e-9)) | (drops > ref.DROP_TRIGGER_FRACTION * cap))
    hot = over.any(axis=-1)
    reach = over
    for _ in range(n):
        reach = over | (reach[:, :, None] & adj[None]).any(axis=1)
    capped = (reach[:, :, None] & adj[None]).any(axis=1)

    # --- external rates and the clamped routing ------------------------- #
    lam_src = xp.where(src & xp.isfinite(lam_m), lam_m, 0)
    total_src = xp.maximum(lam_src.sum(axis=-1), 1e-12)
    lam0_cold = xp.where(
        xp.isfinite(lam0_m)[:, None], lam0_m[:, None] * (lam_src / total_src[:, None]), lam_src
    )
    lam0 = xp.where(src, xp.where(hot[:, None], lam_src, lam0_cold), 0)
    colsum = p.sum(axis=0)
    inflow = (p[None] * lam_m[:, :, None]).sum(axis=1)
    rescale = xp.where(
        (colsum > 0) & ~capped & (inflow > 1e-12) & xp.isfinite(lam_m) & (lam_m > 0),
        lam_m / xp.maximum(inflow, 1e-30),
        one,
    )
    a_rows = [
        [(one if i == j else 0 * one) - p[j, i] * rescale[:, i] for j in range(n)]
        for i in range(n)
    ]
    lam = ref._solve(xp, a_rows, [lam0[:, i] for i in range(n)])
    lam = xp.where(xp.abs(lam) < 1e-12, 0, lam)
    solve_bad = (~xp.isfinite(lam) | (lam < 0)).any(axis=-1)
    lam = xp.where(xp.isfinite(lam) & (lam >= 0), lam, 0)
    lam0_total = lam0.sum(axis=-1)

    # --- model and Program (4) ------------------------------------------ #
    table = sojourn_table(xp, dtype, dep, lam, mu, k_hi)
    finite = xp.isfinite(table)
    k_start = xp.where(finite.any(axis=-1), xp.argmax(finite, axis=-1), k_hi + 1)
    k_start = k_start.astype(np.int32)
    floor_total = k_start.sum(axis=-1)
    infeasible = solve_bad | (floor_total > k_max)
    budget = xp.maximum(k_max - floor_total, 0)
    k4 = k_start
    rows = xp.arange(lam.shape[0])
    for step in range(int(budget.max())):
        t0 = ref._gather(xp, table, k4)
        t1 = ref._gather(xp, table, k4 + 1)
        g = lam * (t0 - t1)
        g = xp.where((k4 < k_hi) & xp.isfinite(g) & (g > 0), g, 0)
        best = xp.argmax(g, axis=-1)
        take = (step < budget) & (g[rows, best] > 0)
        k4 = k4 + (take[:, None] & (xp.arange(n)[None] == best[:, None])).astype(np.int32)

    et_cur = ref.sojourn(xp, lam, table, lam0_total, k)
    et4 = ref.sojourn(xp, lam, table, lam0_total, k4)

    # --- gates ---------------------------------------------------------- #
    unchanged = (k4 == k).all(axis=-1)
    cur_ok = xp.isfinite(et_cur) & (et_cur > 0)
    improvement = xp.where(cur_ok, (et_cur - et4) / xp.where(cur_ok, et_cur, 1), xp.inf)
    visit = lam / xp.maximum(lam0_total, 1e-30)[:, None]
    cap_new = (capacity(xp, dtype, dep, mu, k4) / xp.maximum(visit, 1e-12)).min(axis=-1)
    slack = xp.maximum(cap_new - lam0_total, 1e-9)
    drain = lam0_total * dep.pause / slack
    benefit = xp.where(xp.isfinite(et_cur), et_cur - et4, xp.inf)
    lhs = benefit * lam0_total * dep.horizon
    rhs = (dep.pause + drain) * xp.maximum(lam0_total, 1)
    rebalance = (
        ~unchanged & (improvement >= dep.min_improvement)
        & ((lhs > rhs) | ~xp.isfinite(et_cur))
    )
    complete = (
        (xp.isfinite(lam_m) & xp.isfinite(f(mu_hat))).all(axis=-1) & xp.isfinite(lam0_m)
    )
    code = xp.where(rebalance, ref.ACTION["rebalance"], ref.ACTION["none"])
    code = xp.where(infeasible & ~hot | (solve_bad & hot), ref.ACTION["infeasible"], code)
    code = xp.where(hot & ~solve_bad, ref.ACTION["overloaded"], code)
    code = xp.where(~complete, ref.ACTION["none"], code)
    applied = complete & ~solve_bad & ~infeasible & (hot | rebalance)
    k_next = xp.where(applied[:, None], k4, k)
    et_target = xp.where(~infeasible, et4, xp.inf)

    # --- margins: how far each boundary lies from flipping -------------- #
    def rel(x, y):
        d = xp.abs(x - y) / xp.maximum(xp.abs(y), 1e-30)
        return xp.where(xp.isfinite(d), d, xp.inf)

    a = lam / mu
    # Stability flips where the least stable k is a whole number: at
    # lam / mu (M/M/k) or lam (1 - h) / (mu - lam h) (keyed, whose hot
    # key alone saturates a processor where lam h reaches mu).
    h = f(np.nan_to_num(dep.hot))
    x = xp.where(keyed, lam * (1 - h) / xp.where(lam * h < mu, mu - lam * h, 1), a)
    floor_margin = xp.where(lam > 0, rel(x, xp.maximum(xp.round(x), 1)), xp.inf)
    floor_margin = xp.where(keyed & (lam > 0), xp.minimum(floor_margin, rel(lam * h, mu)),
                            floor_margin).min(axis=-1)
    margin = xp.minimum(
        rel(lam_m, cap * (1 - 1e-9)).min(axis=-1),
        rel(drops, ref.DROP_TRIGGER_FRACTION * cap).min(axis=-1),
    )
    margin = xp.minimum(margin, floor_margin)
    rho_cur = utilization(xp, dtype, dep, a, k).max(axis=-1)
    rho4 = utilization(xp, dtype, dep, a, k4).max(axis=-1)
    cond = xp.clip(1 - xp.maximum(xp.where(rho_cur < 1, rho_cur, 0), rho4), 0, 1)
    gates = rel(improvement, dep.min_improvement)
    gates = xp.minimum(gates, xp.where(xp.isfinite(lhs), rel(lhs, rhs), xp.inf))
    margin = xp.minimum(margin, xp.where(xp.isfinite(gates), cond * gates, xp.inf))
    out = {
        "code": code, "k_next": k_next, "et_cur": et_cur, "et_target": et_target,
        "applied": applied, "k4": k4, "lam": lam, "a": a, "margin": margin,
        "floor_margin": floor_margin,
    }
    if k_other is not None:
        out["et_other"] = ref.sojourn(xp, lam, table, lam0_total, xp.asarray(k_other))
    return out


def _stack(recs, key):
    return np.concatenate([np.asarray(r[key]) for r in recs])


@np.errstate(all="ignore")
def service_numbers(dep: Deployment, recs: list) -> dict:
    """``check.decide_numbers`` over the service's records (one tick's
    inputs and outputs each), against this reference, with E[T]'s
    conditioning taken at the busiest server (a keyed operator's hot
    partition)."""
    inputs = {key: _stack(recs, key) for key in ("lam", "mu", "drop", "lam0", "k")}
    code, k_next = _stack(recs, "code"), _stack(recs, "k_next")
    want = decide(np, np.float64, dep, inputs["lam"], inputs["mu"], inputs["drop"],
                  inputs["lam0"], inputs["k"], k_other=k_next)
    applied = _stack(recs, "applied").astype(bool)
    et = {key: _stack(recs, key).astype(np.float64) for key in ("et_cur", "et_target")}
    acts = (code != want["code"]) | (applied != want["applied"])
    for key in et:
        acts |= np.isfinite(et[key]) != np.isfinite(want[key])
    moved = ~acts & (k_next != want["k_next"]).any(axis=-1)
    alike = ~acts & ~moved

    def cond(*allocs):
        rho = np.max([utilization(np, np.float64, dep, want["a"], k).max(axis=-1)
                      for k in allocs], axis=0)
        return np.clip(1.0 - rho, 0.0, 1.0)

    gap = raw = 0.0
    for key, k in (("et_cur", inputs["k"]), ("et_target", want["k4"])):
        both = np.isfinite(et[key]) & np.isfinite(want[key]) & alike
        rel = np.abs(et[key] - want[key]) / np.maximum(np.abs(want[key]), 1e-30)
        gap = max(gap, float(np.max((rel * cond(k))[both], initial=0.0)))
        raw = max(raw, float(np.max(rel[both], initial=0.0)))
    excess = (want["et_other"] - want["et_target"]) / want["et_target"]
    regret = np.minimum(np.where(np.isfinite(excess), excess * cond(k_next, want["k4"]), np.inf),
                        want["floor_margin"])
    bad_budget = (k_next.sum(axis=-1) > dep.k_max) | (k_next < 0).any(axis=-1)
    return {
        "decision_margin": float(np.max(want["margin"][acts], initial=0.0)),
        "alloc_regret": float(np.max(regret[moved], initial=0.0)),
        "et_gap": gap,
        "budget_violations": int(bad_budget.sum()),
        "_et_gap_unweighed": raw,
        "_differing": int(acts.sum()),
        "_moved": int(moved.sum()),
        "_rows": int(code.shape[0]),
    }
