"""Pallas TPU kernel: the whole batch decide in one VMEM-resident pass.

One grid step per scenario.  The per-operator lanes sit on the 128-wide
lane axis; the allocation axis ``k`` walks the float32 sublane tiles of
two VMEM scratch buffers:

1. **Recurrence** — a ``fori_loop`` over ``k = 1..k_hi`` carries the
   Erlang-B blocking row ``B(k)`` and the previous sojourn row, writing
   one ``(1, N)`` row of the ``E[T_i](k)`` table (Erlang-C conversion
   for replica lanes, the M/M/1 closed form for group-scaled lanes) and
   one Algorithm-1 gain row ``G[k-1] = lam * (T[k-1] - T[k])`` per step.
2. **Floor** — ``k_start`` = first finite table row per lane (min-reduce
   over a row iota; ``k_hi + 1`` marks an infeasible active lane), and
   the Program-4 budget = ``max(k_max - sum k_start, 0)`` from the SMEM
   scalar.
3. **Selection** — the budget-th largest gain inside each lane's
   ``[k_start, k_start + j_cap)`` window is pinned by 31 bisection steps
   over float32 bit patterns (positive IEEE-754 floats order like their
   int32 bits — the ``kernels/gain_topr`` technique, applied here to the
   *unshifted* gain table: the window mask replaces the two-pass path's
   gather, which selects exactly the same entries).  Threshold ties are
   distributed in operator order via a strictly-lower-triangular matmul
   prefix-sum.
4. **Pricing** — ``T[k4]`` and ``T[k_cur]`` leave the core as two
   ``(1, N)`` rows (one-hot row selects), so the caller can price the
   allocation without the ``[B, N, K]`` table ever reaching HBM.

Everything is float32 (allocation counts are exact integers far below
2^24).  The jnp oracle (`ref.py`) computes the identical result in the
caller's dtype; interpret-mode tests assert elementwise agreement on
float32 inputs.  HBM traffic per scenario drops from the two-pass
path's ~``3 * N * K`` table floats to ``6 * N`` lane floats in and
``4 * N`` out.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..gain_topr.kernel import row_total

__all__ = ["batch_decide_pallas"]

_LANE = 128


@functools.lru_cache(maxsize=None)
def _pad_shapes(n: int, k_hi: int, n_pad: int) -> tuple[int, int, int]:
    """(lane-padded N, T-table rows, G-table rows), tile-aligned.

    Hoisted out of the traced wrapper body (and cached per shape) so
    retracing never recomputes pad arithmetic — the same hoist as
    ``kernels/gain_topr``.
    """
    npad = n + ((-n) % n_pad)
    rows_t = (k_hi + 1) + ((-(k_hi + 1)) % 8)  # float32 sublane tile
    rows_g = k_hi + ((-k_hi) % 8)
    return npad, rows_t, rows_g


def _decide_fused_kernel(
    lam_ref, mu_ref, grp_ref, alpha_ref, act_ref, kcur_ref, kmax_ref,
    k4_ref, kst_ref, tcur_ref, t4_ref,
    t_scr, g_scr,
    *, k_hi: int, j_cap: int,
):
    lam = lam_ref[...]  # (1, Np) float32
    mu = mu_ref[...]
    grp = grp_ref[...] > 0.0
    alpha = alpha_ref[...]
    act = act_ref[...] > 0.0
    kcur = kcur_ref[...]
    # Per-scenario scalars ride as lane-replicated (1, Np) rows (see
    # kernels/gain_topr ``row_total``).
    kmax = jnp.broadcast_to(kmax_ref[...], lam.shape).astype(jnp.float32)

    inf = jnp.float32(jnp.inf)
    one = jnp.float32(1.0)  # typed: weak-float where() would promote to f64
    zero = jnp.float32(0.0)
    a_rep = lam / mu
    row_inf = jnp.full_like(lam, inf)
    t_scr[pl.ds(0, 1), :] = row_inf  # k = 0 is never feasible (min_k = 1)

    def body(k, carry):
        b_prev, t_prev = carry
        kf = k.astype(jnp.float32)
        bb = a_rep * b_prev / (kf + a_rep * b_prev)
        # Erlang-C conversion + replica sojourn (core/batched.py mirror).
        c = kf * bb / (kf - a_rep * (1.0 - bb))
        t_rep = c / (kf * mu - lam) + 1.0 / mu
        t_rep = jnp.where(kf > a_rep, t_rep, inf)
        # Group-scaled lanes: M/M/1 at mu * k * eff(k).
        eff = 1.0 / (1.0 + alpha * (kf - 1.0))
        mug = mu * kf * eff
        ag = lam / mug
        bg = ag / (1.0 + ag)
        cg = bg / (1.0 - ag * (1.0 - bg))
        t_grp = cg / (mug - lam) + 1.0 / mug
        t_grp = jnp.where(ag < 1.0, t_grp, inf)
        t = jnp.where(grp, t_grp, t_rep)
        t_scr[pl.ds(k, 1), :] = t
        g = lam * (t_prev - t)
        g_scr[pl.ds(k - 1, 1), :] = jnp.where(jnp.isfinite(t_prev), g, inf)
        return bb, t

    jax.lax.fori_loop(1, k_hi + 1, body, (jnp.ones_like(lam), row_inf))
    rows_t, rows_g = t_scr.shape[0], g_scr.shape[0]
    for r in range(k_hi + 1, rows_t):  # static tile-pad rows, masked below
        t_scr[pl.ds(r, 1), :] = row_inf
    for r in range(k_hi, rows_g):
        g_scr[pl.ds(r, 1), :] = jnp.zeros_like(lam)

    T = t_scr[...]
    G = g_scr[...]
    # Mosaic's iota is integer-only; the row indices are exact in f32.
    kio_t = jax.lax.broadcasted_iota(jnp.int32, T.shape, 0).astype(jnp.float32)
    kio_g = jax.lax.broadcasted_iota(jnp.int32, G.shape, 0).astype(jnp.float32)

    # Minimal feasible allocation: first finite table row per lane.
    fin = jnp.isfinite(T) & (kio_t <= k_hi)
    first = jnp.min(
        jnp.where(fin, kio_t, jnp.float32(rows_t + 1)), axis=0, keepdims=True
    )
    has_f = first <= k_hi
    kst = jnp.where(act, jnp.where(has_f, first, jnp.float32(k_hi + 1)), 0.0)
    floor_total = row_total(kst)
    bud = jnp.maximum(kmax - floor_total, 0.0)

    # Program 4: masked top-R over the raw gain table.  The window mask
    # IS the two-pass path's shifted gather (same entries, same order).
    win = (
        (kio_g >= kst) & (kio_g < kst + j_cap) & (kio_g < k_hi)
        & act & jnp.isfinite(G)
    )
    pos = win & (G > 0.0)

    def count_row(mask):  # (rows, Np) mask -> (1, Np) per-operator count
        return jnp.sum(jnp.where(mask, one, zero), axis=0, keepdims=True)

    pos_row = count_row(pos)
    use_all = row_total(pos_row) <= bud

    def bisect(_, lohi):
        lo, hi = lohi
        mid = lo + ((hi - lo) >> 1)  # overflow-safe midpoint (hi > lo)
        t = jax.lax.bitcast_convert_type(mid, jnp.float32)
        c = row_total(count_row(pos & (G >= t)))
        enough = c >= bud  # still >= budget entries at/above mid
        return jnp.where(enough, mid, lo), jnp.where(enough, hi, mid)

    # Invariant: count(>= bitcast(lo)) >= budget > count(>= bitcast(hi));
    # 31 halvings leave bitcast(lo) == the budget-th largest positive gain.
    np_ = lam.shape[-1]
    lo, _hi = jax.lax.fori_loop(
        0, 31, bisect,
        (jnp.full((1, np_), 1, jnp.int32), jnp.full((1, np_), 0x7F800000, jnp.int32)),
    )
    thresh = jax.lax.bitcast_convert_type(lo, jnp.float32)
    strict = count_row(pos & (G > thresh))
    ties = count_row(pos & (G == thresh))
    rem = bud - row_total(strict)
    row = jax.lax.broadcasted_iota(jnp.int32, (np_, np_), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (np_, np_), 1)
    lower = jnp.where(row < col, one, zero)  # strictly-lower mask
    before = jnp.dot(ties, lower, preferred_element_type=jnp.float32)
    extra = jnp.clip(jnp.minimum(ties, rem - before), zero, None)
    take = jnp.where(use_all, pos_row, strict + extra)
    take = jnp.where(bud > 0, take, 0.0)
    k4 = kst + take

    # E[T] at the current and proposed allocations: one-hot row selects
    # (select-then-sum, not multiply: inf rows must ride through intact).
    k4c = jnp.clip(k4, 0.0, jnp.float32(k_hi))
    kcc = jnp.clip(kcur, 0.0, jnp.float32(k_hi))
    t4 = jnp.sum(jnp.where(kio_t == k4c, T, zero), axis=0, keepdims=True)
    tcur = jnp.sum(jnp.where(kio_t == kcc, T, zero), axis=0, keepdims=True)

    k4_ref[...] = k4
    kst_ref[...] = kst
    tcur_ref[...] = tcur
    t4_ref[...] = t4


@functools.partial(
    jax.jit, static_argnames=("k_hi", "j_cap", "interpret", "n_pad")
)
def batch_decide_pallas(
    lam,
    mu_eff,
    group,
    alpha,
    active,
    k_cur,
    k_max,
    *,
    k_hi: int,
    j_cap: int | None = None,
    interpret: bool = False,
    n_pad: int = _LANE,
):
    """``[B, N]`` rates -> ``(k4 i32, k_start i32, t_cur f32, t4 f32)``.

    Float32 throughout; operator lanes are padded to ``n_pad`` (the lane
    tiling static — multiples of 128) and padding rides through as
    inactive lanes, which every mask discards.  ``j_cap`` bounds the
    selection window (see ref.py — exact whenever ``budget <= j_cap``).
    """
    if lam.ndim != 2:
        raise ValueError(f"lam must be [B, N], got shape {lam.shape}")
    if n_pad % _LANE:
        raise ValueError(f"n_pad must be a multiple of {_LANE}, got {n_pad}")
    b, n = lam.shape
    jc = k_hi if j_cap is None else max(min(int(j_cap), k_hi), 1)
    npad, rows_t, rows_g = _pad_shapes(n, k_hi, n_pad)

    def lane(x, fill=0.0):
        x = jnp.asarray(x, dtype=jnp.float32)
        x = jnp.pad(x, ((0, 0), (0, npad - n)), constant_values=fill)
        return x.reshape(b, 1, npad)

    # Per-scenario operands carry a unit axis and the block squeezes the
    # leading one, so each block's last two dims equal the array's (the
    # TPU (8, 128) tiling rule).
    args = (
        lane(lam), lane(mu_eff), lane(group), lane(alpha), lane(active),
        lane(k_cur),
        jnp.asarray(k_max, dtype=jnp.int32).reshape(b, 1, 1),
    )
    row_spec = pl.BlockSpec((None, 1, npad), lambda i: (i, 0, 0))
    out = pl.pallas_call(
        functools.partial(_decide_fused_kernel, k_hi=k_hi, j_cap=jc),
        grid=(b,),
        in_specs=[row_spec] * 6 + [pl.BlockSpec((None, 1, 1), lambda i: (i, 0, 0))],
        out_specs=[row_spec] * 4,
        out_shape=[jax.ShapeDtypeStruct((b, 1, npad), jnp.float32)] * 4,
        scratch_shapes=[
            pltpu.VMEM((rows_t, npad), jnp.float32),
            pltpu.VMEM((rows_g, npad), jnp.float32),
        ],
        interpret=interpret,
    )(*args)
    k4f, kstf, tcurf, t4f = (o[:, 0, :n] for o in out)
    return k4f.astype(jnp.int32), kstf.astype(jnp.int32), tcurf, t4f
