"""Pallas TPU kernel: batched masked top-R marginal-gain selection.

One grid step per scenario.  The candidate-gain tile sits in VMEM as a
``(J, N)`` block (gain index on sublanes, operators on lanes, both padded
to the float32 tile shape) and the budget as a ``(1, 1)`` VMEM block.
Instead of a sort, the budget-th largest positive gain is found by
**bisection over float bit patterns**: positive IEEE-754 floats order like their int32
bits, so 31 fori_loop steps of one masked VPU count-reduction each pin
the threshold *exactly* (no epsilon).  Per-operator takes are then two
more masked row counts, and threshold ties are distributed in operator
order via a lower-triangular matmul prefix-sum (MXU) — the same
tie-breaking as ``allocator.greedy_increments``.

The selection is exact on the float32 values it is given; the jnp oracle
(`ref.py`) computes the identical result with a sort, which the
interpret-mode CPU test asserts elementwise.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["gain_topr_pallas"]

_LANE = 128


@functools.lru_cache(maxsize=None)
def _pad_shapes(n: int, j: int) -> tuple[int, int]:
    """(lane-padded N, sublane-padded J) for the float32 tile.

    Hoisted out of the traced wrapper body and cached per shape, so
    retracing a new (B, N, J) never recomputes the pad arithmetic; the
    padded entries ride through as zero gains, which the positivity mask
    discards — asserted exactly in tests/test_kernels_all.py.
    """
    return n + ((-n) % _LANE), j + ((-j) % 8)


def row_total(row):
    """The sum of a ``(1, Np)`` row, replicated across its lanes.

    Mosaic bitcasts and compares vectors, not scalar registers, and it
    cannot broadcast a ``(1, 1)`` vector over sublanes and lanes at once.
    So every per-scenario scalar here (budget, counts, bisection bounds)
    is a lane-replicated ``(1, Np)`` row, and comparing one against the
    ``(Jp, Np)`` tile broadcasts over sublanes only.
    """
    return jnp.broadcast_to(jnp.sum(row, axis=1, keepdims=True), row.shape)


def _gain_topr_kernel(cand_ref, budget_ref, take_ref):
    x = cand_ref[...]  # (Jp, Np) float32; masked/padding entries are 0
    np_ = x.shape[-1]
    budget = jnp.broadcast_to(budget_ref[...], (1, np_))  # int32 row
    budget_f = budget.astype(jnp.float32)

    def count_row(mask):  # (Jp, Np) mask -> (1, Np) per-operator count
        return jnp.sum(jnp.where(mask, 1.0, 0.0), axis=0, keepdims=True)

    pos = x > 0.0
    pos_row = count_row(pos)
    use_all = row_total(pos_row) <= budget_f

    def body(_, lohi):
        lo, hi = lohi
        mid = lo + ((hi - lo) >> 1)  # overflow-safe midpoint (hi > lo)
        t = jax.lax.bitcast_convert_type(mid, jnp.float32)
        c = row_total(count_row(pos & (x >= t)))
        enough = c >= budget_f  # still >= budget entries at/above mid
        return jnp.where(enough, mid, lo), jnp.where(enough, hi, mid)

    # Invariant: count(>= bitcast(lo)) >= budget > count(>= bitcast(hi)).
    # 31 halvings of the positive-float bit range leave hi == lo + 1, so
    # bitcast(lo) IS the budget-th largest positive gain.
    lo, _hi = jax.lax.fori_loop(
        0, 31, body,
        (jnp.full((1, np_), 1, jnp.int32), jnp.full((1, np_), 0x7F800000, jnp.int32)),
    )
    thresh = jax.lax.bitcast_convert_type(lo, jnp.float32)
    strict = count_row(pos & (x > thresh))
    ties = count_row(pos & (x == thresh))
    rem = budget_f - row_total(strict)
    row = jax.lax.broadcasted_iota(jnp.int32, (np_, np_), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (np_, np_), 1)
    lower = jnp.where(row < col, 1.0, 0.0)  # strictly-lower mask
    before = jnp.dot(ties, lower, preferred_element_type=jnp.float32)
    extra = jnp.clip(jnp.minimum(ties, rem - before), 0.0, None)
    take = jnp.where(use_all, pos_row, strict + extra)
    take_ref[...] = jnp.where(budget > 0, take, 0.0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def gain_topr_pallas(cand, budget, *, interpret: bool = False):
    """``cand [B, N, J]`` + ``budget [B]`` -> ``take [B, N]`` int32.

    Computes in float32 (counts are exact integers far below 2^24).
    Operators and gain columns are padded to the 128-lane tile; padding
    rides through as zero gains, which the positivity mask discards.
    """
    if cand.ndim != 3:
        raise ValueError(f"cand must be [B, N, J], got shape {cand.shape}")
    b, n, j = cand.shape
    npad, jpad = _pad_shapes(n, j)
    x = jnp.pad(
        jnp.asarray(cand, dtype=jnp.float32), ((0, 0), (0, npad - n), (0, jpad - j))
    )
    x = jnp.swapaxes(x, 1, 2)  # (B, Jp, Np): gains on sublanes, ops on lanes
    # Per-scenario operands carry a unit axis and the block squeezes the
    # leading one, so each block's last two dims equal the array's (the
    # TPU (8, 128) tiling rule).
    bud = jnp.asarray(budget, dtype=jnp.int32).reshape(b, 1, 1)
    take = pl.pallas_call(
        _gain_topr_kernel,
        grid=(b,),
        in_specs=[
            pl.BlockSpec((None, jpad, npad), lambda i: (i, 0, 0)),
            pl.BlockSpec((None, 1, 1), lambda i: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, 1, npad), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, 1, npad), jnp.float32),
        interpret=interpret,
    )(x, bud)
    return take[:, 0, :n].astype(jnp.int32)
