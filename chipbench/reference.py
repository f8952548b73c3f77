"""The plain reference: the DRS decide and the fluid window simulation,
written from their definitions, importing nothing of the program.

Semantics (DESIGN.md §§2, 11, 13, 14 and the paper, arXiv:1501.03610):

* overload trigger (§11): an operator is hot when its measured rate
  reaches its capacity ``mu * max(k, 1)`` or it sheds more than 1% of it;
  the lane then re-plans from the source's measured rate, and operators
  downstream of a hot one keep their declared routing;
* offered-load clamping: elsewhere the routing column into an operator
  is rescaled so that the solved inflow matches the measured rate;
* Jackson traffic equations ``lam = lam0 + P^T lam`` per lane;
* M/M/k sojourn per operator from the Erlang-B recurrence
  ``B_j = a B_{j-1} / (j + a B_{j-1})``, Erlang C
  ``C = k B / (k - a (1 - B))``, ``T = C / (k mu - lam) + 1 / mu``
  (``k > a``, else unstable);
* Program (4): from each operator's least stable allocation, hand out the
  rest of ``k_max`` one processor at a time to the largest positive
  marginal gain ``lam_i (T_i(k) - T_i(k + 1))`` (ties to the lower
  operator index);
* gates: rebalance when the allocation changes, E[T] improves by at
  least ``min_improvement`` and the gain over ``horizon`` seconds pays
  for the pause and the drain of the backlog it builds;
* window simulation (§13): per step ``dt`` each queue serves
  ``min(q, k mu dt)``, then admits external arrivals plus last step's
  completions routed by ``P``.

Every function takes an array namespace ``xp`` and a float ``dtype``:
numpy with float64 is the reference; ``jax.numpy`` with bfloat16 is the
control that a sound comparison must refuse.
"""

from __future__ import annotations

import numpy as np

DROP_TRIGGER_FRACTION = 0.01
ACTION = {"none": 0, "rebalance": 1, "infeasible": 4, "overloaded": 5}


class Deployment:
    """One configuration's graph and decision parameters, lane-invariant."""

    def __init__(self, cfg: dict):
        names = [op["name"] for op in cfg["operators"]]
        n = len(names)
        self.n = n
        self.mu = np.array([float(op["mu"]) for op in cfg["operators"]])
        self.routing = np.zeros((n, n))
        for src, dst, mult in cfg["edges"]:
            self.routing[names.index(src), names.index(dst)] += float(mult)
        self.src = np.array([name in cfg["sources"] for name in names])
        self.k_max = int(cfg["k_max"])
        self.k_hi = self.k_max
        s = cfg["scheduler"]
        self.min_improvement = float(s["min_improvement"])
        self.horizon = float(s["horizon_seconds"])
        self.pause = float(s["pause_seconds"])


def _solve(xp, a_rows, rhs):
    """Solve ``A x = rhs`` per lane by Gaussian elimination without pivoting
    (``I - P^T`` of a leaking graph is diagonally dominant).  ``a_rows`` is
    an N x N nested list of ``[R]`` arrays, ``rhs`` a list of N."""
    n = len(rhs)
    a = [list(row) for row in a_rows]
    b = list(rhs)
    for p in range(n):
        for r in range(p + 1, n):
            f = a[r][p] / a[p][p]
            for c in range(p, n):
                a[r][c] = a[r][c] - f * a[p][c]
            b[r] = b[r] - f * b[p]
    x = [None] * n
    for p in reversed(range(n)):
        acc = b[p]
        for c in range(p + 1, n):
            acc = acc - a[p][c] * x[c]
        x[p] = acc / a[p][p]
    return xp.stack(x, axis=-1)


def sojourn_table(xp, lam, mu, k_hi: int):
    """``[R, N, k_hi + 1]`` M/M/k sojourn time at k = 0 .. k_hi."""
    a = lam / mu
    b = xp.ones_like(a)
    cols = [xp.full(a.shape, xp.inf, dtype=a.dtype)]
    for j in range(1, k_hi + 1):
        b = a * b / (j + a * b)
        c = j * b / (j - a * (1 - b))
        t = c / (j * mu - lam) + 1 / mu
        cols.append(xp.where(j > a, t, xp.inf))
    return xp.stack(cols, axis=-1)


def _gather(xp, table, k):
    k = xp.clip(k, 0, table.shape[-1] - 1)
    return xp.take_along_axis(table, k[..., None], axis=-1)[..., 0]


def sojourn(xp, lam, table, lam0_total, k):
    """E[T] at allocation ``k``: visit-weighted per-operator sojourn."""
    per_op = _gather(xp, table, k)
    contrib = xp.where(lam > 0, lam * per_op, 0).sum(axis=-1)
    return xp.where(lam0_total > 0, contrib / xp.maximum(lam0_total, 1e-30), xp.nan)


def decide(xp, dtype, dep: Deployment, lam_hat, mu_hat, drop_hat, lam0_hat, k_cur,
           k_other=None):
    """One tick's decision for ``R`` lanes (rows).  Returns a dict with the
    program's outputs (``code, k_next, et_cur, et_target, applied``), the
    Program (4) allocation ``k4``, the solved rates ``lam`` and offered
    loads ``a``, and the relative distances of the decision boundaries:
    ``floor_margin [R]`` (an offered load at a whole number of
    processors, where stability flips) and ``margin [R]`` (that, the two
    triggers and the two gates, the gates weighed by their conditioning).
    With ``k_other`` (another allocation of the same rows), also
    ``et_other``: E[T] there under the reference's rates."""
    f = lambda x: xp.asarray(x, dtype=dtype)
    n, k_hi, k_max = dep.n, dep.k_hi, dep.k_max
    lam_m, mu, lam0_m = f(lam_hat), f(mu_hat), f(lam0_hat)
    drops = xp.nan_to_num(f(drop_hat), nan=0.0)
    k = xp.asarray(k_cur).astype(np.int32)
    p = f(dep.routing)
    src = xp.asarray(dep.src)
    adj = xp.asarray(dep.routing > 0)
    one = f(1.0)

    # --- overload trigger and the operators downstream of a hot one ------ #
    cap = mu * xp.maximum(k, 1).astype(dtype)
    valid = xp.isfinite(lam_m) & xp.isfinite(mu) & (mu > 0)
    over = valid & ((lam_m >= cap * (1 - 1e-9)) | (drops > DROP_TRIGGER_FRACTION * cap))
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
    # A = I - (P * rescale)^T, lane by lane.
    a_rows = [
        [(one if i == j else 0 * one) - p[j, i] * rescale[:, i] for j in range(n)]
        for i in range(n)
    ]
    lam = _solve(xp, a_rows, [lam0[:, i] for i in range(n)])
    lam = xp.where(xp.abs(lam) < 1e-12, 0, lam)
    solve_bad = (~xp.isfinite(lam) | (lam < 0)).any(axis=-1)
    lam = xp.where(xp.isfinite(lam) & (lam >= 0), lam, 0)
    lam0_total = lam0.sum(axis=-1)

    # --- model and Program (4) ------------------------------------------ #
    table = sojourn_table(xp, lam, mu, k_hi)
    finite = xp.isfinite(table)
    k_start = xp.where(finite.any(axis=-1), xp.argmax(finite, axis=-1), k_hi + 1)
    k_start = k_start.astype(np.int32)
    floor_total = k_start.sum(axis=-1)
    infeasible = solve_bad | (floor_total > k_max)
    budget = xp.maximum(k_max - floor_total, 0)
    k4 = k_start
    rows = xp.arange(lam.shape[0])

    def gain_at(alloc):
        t0 = _gather(xp, table, alloc)
        t1 = _gather(xp, table, alloc + 1)
        g = lam * (t0 - t1)
        return xp.where((alloc < k_hi) & xp.isfinite(g) & (g > 0), g, 0)

    for step in range(int(budget.max())):
        g = gain_at(k4)
        best = xp.argmax(g, axis=-1)
        g_best = g[rows, best]
        take = (step < budget) & (g_best > 0)
        k4 = k4 + (take[:, None] & (xp.arange(n)[None] == best[:, None])).astype(np.int32)

    et_cur = sojourn(xp, lam, table, lam0_total, k)
    et4 = sojourn(xp, lam, table, lam0_total, k4)

    # --- gates ---------------------------------------------------------- #
    unchanged = (k4 == k).all(axis=-1)
    cur_ok = xp.isfinite(et_cur) & (et_cur > 0)
    improvement = xp.where(cur_ok, (et_cur - et4) / xp.where(cur_ok, et_cur, 1), xp.inf)
    visit = lam / xp.maximum(lam0_total, 1e-30)[:, None]
    cap_new = (k4.astype(dtype) * mu / xp.maximum(visit, 1e-12)).min(axis=-1)
    slack = xp.maximum(cap_new - lam0_total, 1e-9)
    drain = lam0_total * dep.pause / slack
    benefit = xp.where(xp.isfinite(et_cur), et_cur - et4, xp.inf)
    lhs = benefit * lam0_total * dep.horizon
    rhs = (dep.pause + drain) * xp.maximum(lam0_total, 1)
    worthwhile = lhs > rhs
    rebalance = (
        ~unchanged & (improvement >= dep.min_improvement)
        & (worthwhile | ~xp.isfinite(et_cur))
    )
    complete = (
        (xp.isfinite(lam_m) & xp.isfinite(f(mu_hat))).all(axis=-1) & xp.isfinite(lam0_m)
    )
    code = xp.where(rebalance, ACTION["rebalance"], ACTION["none"])
    code = xp.where(infeasible & ~hot | (solve_bad & hot), ACTION["infeasible"], code)
    code = xp.where(hot & ~solve_bad, ACTION["overloaded"], code)
    code = xp.where(~complete, ACTION["none"], code)
    applied = complete & ~solve_bad & ~infeasible & (hot | rebalance)
    k_next = xp.where(applied[:, None], k4, k)
    et_target = xp.where(~infeasible, et4, xp.inf)

    # --- margins: how far each boundary lies from flipping -------------- #
    def rel(x, y):
        d = xp.abs(x - y) / xp.maximum(xp.abs(y), 1e-30)
        return xp.where(xp.isfinite(d), d, xp.inf)

    a = lam / mu
    near_int = xp.maximum(xp.round(a), 1)
    floor_margin = xp.where(lam > 0, rel(a, near_int), xp.inf).min(axis=-1)
    margin = xp.minimum(
        rel(lam_m, cap * (1 - 1e-9)).min(axis=-1),
        rel(drops, DROP_TRIGGER_FRACTION * cap).min(axis=-1),
    )
    margin = xp.minimum(margin, floor_margin)
    # E[T] near saturation moves by eps / (1 - rho) for a rounding eps in
    # the rates, so the gates are weighed by 1 - rho of the fullest
    # operator at the current (where stable) and new allocation.
    rho_cur = (a / xp.maximum(k, 1)).max(axis=-1)
    rho4 = (a / xp.maximum(k4, 1)).max(axis=-1)
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
        out["et_other"] = sojourn(xp, lam, table, lam0_total, xp.asarray(k_other))
    return out


def window(xp, dtype, dep: Deployment, state, ext, k, dt: float, warm):
    """Advance one control window of ``ext [S, B, N]`` steps under the
    allocation ``k [B, N]``.  ``state = (q, served_prev)``; ``warm [S]``
    gates the run sums.  Returns the new state, the window's own sums
    ``(offered, dropped, ext_admitted)`` and the warm-gated increments
    ``(offered, served, dropped, ext_admitted, ext_offered)`` with the
    window's peak backlog."""
    f = lambda x: xp.asarray(x, dtype=dtype)
    p = f(dep.routing)
    cap_dt = f(dep.mu) * xp.maximum(xp.asarray(k), 0).astype(dtype) * dt
    q, served_prev = state
    zeros = xp.zeros_like(q)
    offered, dropped, ext_adm = zeros, zeros, zeros[:, 0]
    w_off, w_srv, w_drop, w_ea, w_eo = zeros, zeros, zeros, zeros[:, 0], zeros[:, 0]
    q_max = zeros
    for s in range(ext.shape[0]):
        ext_s = f(ext[s])
        served = xp.minimum(q, cap_dt)
        routed = (served_prev[:, :, None] * p[None]).sum(axis=1)
        inflow = ext_s + routed
        q = q - served + inflow  # unbounded queues admit everything
        adm_ext = ext_s.sum(axis=-1)
        offered, ext_adm = offered + inflow, ext_adm + adm_ext
        w = float(warm[s])
        if w:
            w_off, w_srv = w_off + inflow, w_srv + served
            w_ea, w_eo = w_ea + adm_ext, w_eo + adm_ext
        q_max = xp.maximum(q_max, q)
        served_prev = served
    return (q, served_prev), (offered, dropped, ext_adm), (
        w_off, w_srv, w_drop, w_ea, w_eo), q_max
