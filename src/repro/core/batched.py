"""Batched analytic core — vectorized Erlang/Jackson evaluation (DESIGN.md §12).

The scalar modules (erlang.py, jackson.py) price ONE allocation of ONE
topology per call; every control tick the allocator then re-walks the
Erlang-B recursion thousands of times.  This module evaluates the model in
bulk along three axes:

* **k axis** — :func:`sojourn_table` materialises ``E[T_i](k)`` for every
  operator at every ``k in [0, k_hi]`` in ONE pass of the Erlang-B
  recursion (``[N, k_hi+1]``); :func:`gain_table` turns it into the
  marginal-benefit table Algorithm 1 consumes.
* **allocation batch axis** — :func:`expected_sojourn_batch` prices a
  ``[B, N]`` batch of candidate allocations (what-if configurations)
  against one topology via table gather.
* **tenant/scenario batch axis** — :func:`solve_traffic_batch` solves the
  Jackson traffic equations for a ``[B, N]`` batch of ``lam0`` vectors
  (optionally a ``[B, N, N]`` batch of routing matrices) in one
  ``linalg.solve``.

Backends and the fallback rule (DESIGN.md §12): every function has a
float64 **numpy** implementation — the default off-TPU, and the one the
allocator's bit-exactness guarantee rests on (it replays the scalar
recursion's float ops verbatim, vectorized across lanes) — and a pure-jnp
``jit``/``vmap``-able implementation (``backend="jax"``) whose hot
Erlang-B recursion dispatches to the Pallas kernel
(``kernels/erlang_c``) on TPU and the lax.scan oracle elsewhere.  The jnp
path inherits JAX's active precision (float32 unless x64 is enabled), so
CPU tests pin tolerances accordingly.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from .jackson import Topology

__all__ = [
    "OperatorArrays",
    "operator_arrays",
    "sojourn_table",
    "gain_table",
    "sojourn_from_table",
    "expected_sojourn_batch",
    "solve_traffic_batch",
    "sojourn_table_jax",
    "expected_sojourn_batch_jax",
    "solve_traffic_batch_jax",
]


# --------------------------------------------------------------------------- #
# Topology -> flat arrays
# --------------------------------------------------------------------------- #
@dataclass(frozen=True)
class OperatorArrays:
    """Flat per-operator arrays the batched kernels consume (index order
    matches the Topology's)."""

    lam: np.ndarray  # solved per-operator arrival rates [N]
    mu: np.ndarray  # per-processor service-rate priors/estimates [N]
    group: np.ndarray  # bool [N]: True = chip-gang scaling (M/M/1 @ mu*k*eff)
    alpha: np.ndarray  # group efficiency rolloff [N]
    min_k: np.ndarray  # per-operator floor [N]
    lam0_total: float
    hot: np.ndarray  # keyed scaling's hot-key share [N]; NaN = not keyed


def operator_arrays(top: Topology) -> OperatorArrays:
    ops = top.operators
    return OperatorArrays(
        lam=np.asarray(top.arrival_rates, dtype=np.float64),
        mu=np.array([op.mu for op in ops], dtype=np.float64),
        group=np.array([op.scaling == "group" for op in ops], dtype=bool),
        alpha=np.array([op.group_alpha for op in ops], dtype=np.float64),
        min_k=np.array([op.min_k for op in ops], dtype=np.int64),
        lam0_total=top.lam0_total,
        hot=np.array(
            [op.hot_share if op.scaling == "keyed" else np.nan for op in ops],
            dtype=np.float64,
        ),
    )


# --------------------------------------------------------------------------- #
# numpy float64 path (default off-TPU; bit-compatible with the scalar core)
# --------------------------------------------------------------------------- #
def sojourn_table(top: Topology, k_hi: int) -> np.ndarray:
    """``T[i, k] = E[T_i](k)`` for ``k in [0, k_hi]`` — ``[N, k_hi+1]`` float64.

    Entries below the operator's ``min_k`` or in the unstable region
    (``k*mu <= lam`` replica / ``mu_eff(k) <= lam`` group / the hot
    partition's ``lam * p_hot(k) >= mu`` keyed) are ``+inf``,
    mirroring ``OperatorSpec.sojourn`` exactly: the vectorized recursion
    performs the same float64 operations in the same order as the scalar
    ``erlang.expected_sojourn``, so finite entries are bit-identical to the
    scalar values — that is what lets the table-driven greedy reproduce
    ``assign_processors_naive`` decision-for-decision.
    """
    if k_hi < 0:
        raise ValueError(f"k_hi must be >= 0, got {k_hi}")
    arr = operator_arrays(top)
    n = arr.lam.shape[0]
    T = np.full((n, k_hi + 1), np.inf, dtype=np.float64)

    keyed = ~np.isnan(arr.hot)
    rep = ~arr.group & ~keyed
    if rep.any():
        lam, mu = arr.lam[rep], arr.mu[rep]
        a = lam / mu
        r = int(rep.sum())
        # Erlang-B recursion B(j) = aB/(j + aB).  It is sequential in j, so
        # the loop stays — but its body is kept to the bare recursion and,
        # for narrow operator sets, run in plain Python floats (~30x less
        # per-step overhead than numpy scalar-array ops; the float ops are
        # the same either way, preserving bit-equality with erlang.erlang_b).
        B = np.empty((r, k_hi + 1), dtype=np.float64)
        B[:, 0] = 1.0
        if r <= 64:
            for i in range(r):
                ai = float(a[i])
                row = B[i]
                b = 1.0
                for j in range(1, k_hi + 1):
                    ab = ai * b
                    b = ab / (j + ab)
                    row[j] = b
        else:
            b = np.ones_like(a)
            for j in range(1, k_hi + 1):
                ab = a * b
                b = ab / (j + ab)
                B[:, j] = b
        # B -> C -> E[T], one vectorized pass over the whole [r, k_hi+1]
        # grid (elementwise ops in the scalar expressions' order).
        ks = np.arange(k_hi + 1, dtype=np.int64)[None, :]
        with np.errstate(divide="ignore", invalid="ignore", over="ignore"):
            c = ks * B / (ks - a[:, None] * (1.0 - B))
            t = c / (ks * mu[:, None] - lam[:, None]) + 1.0 / mu[:, None]
            sub = np.where(ks > a[:, None], t, np.inf)
        T[rep] = sub

    if arr.group.any():
        ks = np.arange(k_hi + 1, dtype=np.float64)
        for i in np.nonzero(arr.group)[0]:
            lam, mu, alpha = arr.lam[i], arr.mu[i], arr.alpha[i]
            with np.errstate(divide="ignore", invalid="ignore", over="ignore"):
                eff = 1.0 / (1.0 + alpha * (ks - 1.0))
                mu_eff = mu * ks * eff
                a = lam / mu_eff
                stable = 1.0 > a  # M/M/1: scalar inf branch is `1 <= a`
                # j=1 step of the B recursion with b0=1: a*1/(1 + a*1)
                b = a / (1.0 + a)
                c = b / (1.0 - a * (1.0 - b))
                t = c / (mu_eff - lam) + 1.0 / mu_eff
            row = np.full(k_hi + 1, np.inf)
            row[stable] = t[stable]
            T[i] = row

    if keyed.any():
        T[keyed] = _keyed_table(
            np, arr.lam[keyed], arr.mu[keyed], arr.hot[keyed], k_hi
        )

    for i in range(n):
        lo = min(int(arr.min_k[i]), k_hi + 1)
        T[i, :lo] = np.inf
    return T


def _keyed_table(xp, lam, mu, hot, k_hi: int, dtype=None):
    """``[R, k_hi+1]`` sojourn of R keyed operators (DESIGN.md §20): k M/M/1
    partitions, the hottest at ``p_hot = h + (1 - h)/k`` of the input.  The
    same float operations as ``erlang.keyed_sojourn``, for numpy (float64)
    and jnp alike."""
    ks = xp.arange(k_hi + 1, dtype=dtype or lam.dtype)[None, :]
    lam, mu, hot = lam[:, None], mu[:, None], hot[:, None]
    with np.errstate(divide="ignore", invalid="ignore", over="ignore"):
        p_cold = (1.0 - hot) / xp.maximum(ks, 1.0)
        p_hot = hot + p_cold
        lam_hot = lam * p_hot
        t = p_hot / (mu - lam_hot) + (ks - 1.0) * p_cold / (mu - lam * p_cold)
    return xp.where((ks >= 1.0) & (lam_hot < mu), t, xp.inf)


def gain_table(top: Topology, k_hi: int) -> tuple[np.ndarray, np.ndarray]:
    """``(T, G)`` where ``G[i, k] = lam_i * (T[i,k] - T[i,k+1])`` — the
    Algorithm-1 marginal benefit of the k -> k+1 processor, ``[N, k_hi]``.

    ``G`` is ``+inf`` where ``T[i, k]`` is infinite (the processor is
    mandatory), matching ``erlang.marginal_benefit``.
    """
    T = sojourn_table(top, k_hi)
    lam = np.asarray(top.arrival_rates, dtype=np.float64)
    with np.errstate(invalid="ignore"):
        G = lam[:, None] * (T[:, :-1] - T[:, 1:])
    G[np.isinf(T[:, :-1])] = np.inf
    return T, G


def sojourn_from_table(T: np.ndarray, k: np.ndarray, lam: np.ndarray, lam0_total: float):
    """Vector of per-op sojourns + E[T] (paper Eq. 3) gathered from the table.

    ``k`` may be ``[N]`` or ``[B, N]``; returns ``(per_op, e2e)`` with the
    matching leading shape.  Uses a vectorized sum (tolerance ~1e-12 of the
    scalar sequential sum; callers needing the scalar-exact value recompute
    via ``Topology.expected_sojourn``).
    """
    k = np.asarray(k, dtype=np.int64)
    per_op = np.take_along_axis(
        np.broadcast_to(T, k.shape[:-1] + T.shape), k[..., None], axis=-1
    )[..., 0]
    with np.errstate(invalid="ignore"):  # 0 * inf on zero-traffic operators
        contrib = np.where(lam > 0, lam * per_op, 0.0)
    e2e = contrib.sum(axis=-1) / max(lam0_total, 1e-300)  # idle-network guard
    return per_op, e2e


def expected_sojourn_batch(top: Topology, k_batch, *, backend: str = "numpy"):
    """E[T](k) for a ``[B, N]`` batch of allocations — ``[B]`` floats.

    ``backend="numpy"`` (default): float64 table + gather.
    ``backend="jax"``: the jit'd jnp path (float32 unless x64 is enabled).
    """
    k_batch = np.atleast_2d(np.asarray(k_batch, dtype=np.int64))
    if k_batch.shape[-1] != top.n:
        raise ValueError(f"k batch must be [B, {top.n}], got {k_batch.shape}")
    if backend == "jax":
        return np.asarray(expected_sojourn_batch_jax(top, k_batch))
    k_hi = int(k_batch.max(initial=0))
    T = sojourn_table(top, k_hi)
    _, e2e = sojourn_from_table(T, k_batch, top.arrival_rates, top.lam0_total)
    return e2e


def solve_traffic_batch(lam0_batch, routing, *, backend: str = "numpy") -> np.ndarray:
    """Traffic equations ``lam = lam0 + P^T lam`` for a batch of externals.

    ``lam0_batch`` is ``[B, N]``; ``routing`` is one shared ``[N, N]`` or a
    per-scenario ``[B, N, N]``.  Returns ``[B, N]`` solved arrival rates
    (tiny negatives from numerical noise are clamped to 0, as in the scalar
    ``solve_traffic_equations``).
    """
    lam0 = np.atleast_2d(np.asarray(lam0_batch, dtype=np.float64))
    p = np.asarray(routing, dtype=np.float64)
    n = lam0.shape[-1]
    if p.shape not in ((n, n),) and p.shape != (lam0.shape[0], n, n):
        raise ValueError(
            f"routing must be ({n},{n}) or ({lam0.shape[0]},{n},{n}), got {p.shape}"
        )
    if backend == "jax":
        return np.asarray(solve_traffic_batch_jax(lam0, p))
    pt = np.swapaxes(p, -1, -2)
    a = np.eye(n) - pt
    lam = np.linalg.solve(a, lam0[..., None])[..., 0] if a.ndim == 3 else (
        np.linalg.solve(a, lam0.T).T
    )
    lam[np.abs(lam) < 1e-12] = 0.0
    return lam


# --------------------------------------------------------------------------- #
# jnp path — pure functions, jit/vmap-able; Pallas recursion kernel on TPU
# --------------------------------------------------------------------------- #
def sojourn_table_jax(
    lam,
    mu,
    *,
    k_hi: int,
    group=None,
    alpha=None,
    min_k=None,
    hot=None,
    interpret: bool = False,
    force_kernel: bool = False,
    unroll: int = 1,
):
    """jnp ``[N, k_hi+1]`` sojourn table (the numpy path's jit-able twin).

    The Erlang-B recursion runs through ``kernels.erlang_c.ops`` — Pallas
    on TPU, lax.scan elsewhere; pass ``force_kernel=True, interpret=True``
    to exercise the Pallas kernel itself on CPU (``interpret`` alone does
    not switch the dispatch — repo kernel idiom, see kernels/__init__.py).
    Group-scaled operators use the M/M/1 closed form and are merged in
    with ``jnp.where`` so the whole function stays traceable.  ``unroll``
    tunes the reference scan's unroll factor — bitwise-safe, so callers
    may autotune it freely (kernels/decide_fused does).  ``hot`` (``[N]``,
    NaN where an operator is not keyed) merges in the keyed closed form
    (DESIGN.md §20); ``None`` traces none of it.
    """
    import jax.numpy as jnp

    from ..kernels.erlang_c import ops as _erlang_ops

    lam = jnp.asarray(lam)
    dtype = lam.dtype
    mu = jnp.asarray(mu, dtype=dtype)
    n = lam.shape[0]
    group = (
        jnp.zeros(n, dtype=bool) if group is None else jnp.asarray(group, dtype=bool)
    )
    alpha = jnp.zeros(n, dtype=dtype) if alpha is None else jnp.asarray(alpha, dtype=dtype)
    min_k = (
        jnp.ones(n, dtype=jnp.int32) if min_k is None else jnp.asarray(min_k, jnp.int32)
    )
    ks = jnp.arange(k_hi + 1, dtype=dtype)  # [K+1]

    # Replica: one recursion pass over the operator lane.
    a_rep = lam / mu
    btab = _erlang_ops.erlang_b_table(
        a_rep, k_hi=k_hi, interpret=interpret, force_kernel=force_kernel,
        unroll=unroll,
    ).T.astype(dtype)  # [N, K+1]
    kk = ks[None, :]
    c = kk * btab / (kk - a_rep[:, None] * (1.0 - btab))
    t_rep = c / (kk * mu[:, None] - lam[:, None]) + 1.0 / mu[:, None]
    t_rep = jnp.where(kk > a_rep[:, None], t_rep, jnp.inf)

    # Group: M/M/1 at mu * k * eff(k).
    eff = 1.0 / (1.0 + alpha[:, None] * (kk - 1.0))
    mu_eff = mu[:, None] * kk * eff
    a_grp = lam[:, None] / mu_eff
    b = a_grp / (1.0 + a_grp)
    cg = b / (1.0 - a_grp * (1.0 - b))
    t_grp = cg / (mu_eff - lam[:, None]) + 1.0 / mu_eff
    t_grp = jnp.where(a_grp < 1.0, t_grp, jnp.inf)

    T = jnp.where(group[:, None], t_grp, t_rep)
    if hot is not None:
        hot = jnp.asarray(hot, dtype=dtype)
        keyed = ~jnp.isnan(hot)
        t_key = _keyed_table(jnp, lam, mu, jnp.where(keyed, hot, 0.0), k_hi, dtype)
        T = jnp.where(keyed[:, None], t_key, T)
    return jnp.where(kk >= min_k[:, None], T, jnp.inf)


def expected_sojourn_batch_jax(top: Topology, k_batch, *, interpret: bool = False):
    """E[T](k) over a ``[B, N]`` jnp batch of allocations (gather on the
    jnp table).  Returns a jnp ``[B]`` vector."""
    import jax.numpy as jnp

    arr = operator_arrays(top)
    k_batch = jnp.atleast_2d(jnp.asarray(k_batch, dtype=jnp.int32))
    k_hi = int(np.asarray(k_batch).max(initial=0))
    T = sojourn_table_jax(
        jnp.asarray(arr.lam),
        jnp.asarray(arr.mu),
        k_hi=k_hi,
        group=arr.group,
        alpha=arr.alpha,
        min_k=arr.min_k,
        hot=arr.hot if not np.isnan(arr.hot).all() else None,
        interpret=interpret,
    )
    per_op = jnp.take_along_axis(
        jnp.broadcast_to(T, k_batch.shape[:1] + T.shape), k_batch[..., None], axis=-1
    )[..., 0]
    lam = jnp.asarray(arr.lam, dtype=per_op.dtype)
    contrib = jnp.where(lam > 0, lam * per_op, 0.0)
    return contrib.sum(axis=-1) / max(arr.lam0_total, 1e-300)


def solve_traffic_batch_jax(lam0_batch, routing):
    """jnp traffic-equation solve for ``[B, N]`` externals (shared or
    per-scenario routing) via ``jnp.linalg.solve``."""
    import jax.numpy as jnp

    lam0 = jnp.atleast_2d(jnp.asarray(lam0_batch))
    p = jnp.asarray(routing, dtype=lam0.dtype)
    n = lam0.shape[-1]
    pt = jnp.swapaxes(p, -1, -2)
    a = jnp.eye(n, dtype=lam0.dtype) - pt
    if a.ndim == 3:
        lam = jnp.linalg.solve(a, lam0[..., None])[..., 0]
    else:
        lam = jnp.linalg.solve(a, lam0.T).T
    return jnp.where(jnp.abs(lam) < 1e-12, 0.0, lam)
