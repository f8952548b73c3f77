"""Pallas TPU kernels: batched masked top-R marginal-gain selection.

Instead of a sort, the budget-th largest positive gain of a scenario is
found by **bisection over float bit patterns**: positive IEEE-754 floats
order like their int32 bits, so 31 fori_loop steps of one masked VPU
count-reduction each pin the threshold *exactly* (no epsilon).
Per-operator takes are then two more masked counts, and threshold ties
are distributed in operator order — the same tie-breaking as
``allocator.greedy_increments``.

Two block layouts run this one algorithm; ``gain_topr_pallas`` picks one
from the shape it is given.

* **Lanes** (``gain_topr_lanes``), for ``B >= 128``: scenarios lie on the
  128-wide lane axis.  The operand is ``(N, Jp, Bp)`` and one grid step
  takes an ``(N, Jp, Bt)`` block — operators on a leading untiled axis,
  gains on sublanes, ``Bt`` scenarios on lanes — so every per-scenario
  scalar (budget, counts, bisection bounds) is a ``(1, Bt)`` row, a
  count is VPU adds over the operators plus one sublane reduce, and the
  tie prefix is a running sum over the static operator axis.  A fleet of
  N = 3 operators fills every lane instead of 3 of 128.
* **Per scenario** (``gain_topr_pallas``), otherwise: one grid step per
  scenario over a ``(Jp, Npad)`` tile, gains on sublanes and operators on
  lanes, the tie prefix a lower-triangular MXU matmul.  It serves
  ``B < 128`` — above all the planner's one merged ``[1, R, budget]``
  fleet table, which lanes would pad 128-fold — and operator counts
  whose lane block would not fit scoped VMEM.

The selection is exact on the float32 values it is given; the jnp oracle
(`ref.py`) computes the identical result with a sort, which the
interpret-mode CPU tests assert elementwise for both layouts.
"""

from __future__ import annotations

import functools
import operator

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["gain_topr_lanes", "gain_topr_pallas"]

_LANE = 128
# The lane tile's two caps, from a sweep of Bt on a v5e (PERF.md): at
# N = 3, J = 22 the time per call falls to Bt = 1024 as each grid step's
# fixed cost is spread, then rises; at N = 8, J = 64 it is least at 256
# lanes, a 512 KiB block.  Far below the 16 MiB of scoped VMEM a v5e
# grants, which the double-buffered block and the bisection's masks share.
_MAX_BT = 1024
_BLOCK_BYTES = 512 << 10


@functools.lru_cache(maxsize=None)
def _pad_shapes(n: int, j: int) -> tuple[int, int]:
    """(lane-padded N, sublane-padded J) for the float32 tile.

    Hoisted out of the traced wrapper body and cached per shape, so
    retracing a new (B, N, J) never recomputes the pad arithmetic; the
    padded entries ride through as zero gains, which the positivity mask
    discards — asserted exactly in tests/test_kernels_all.py.
    """
    return n + ((-n) % _LANE), j + ((-j) % 8)


@functools.lru_cache(maxsize=None)
def _lane_tile(b: int, n: int, jpad: int) -> int | None:
    """The lane tile ``Bt`` for ``b`` scenarios of ``(n, jpad)`` gains, or
    ``None`` where the per-scenario layout serves the shape.

    ``Bt`` is a multiple of 128 of at most ``_MAX_BT`` lanes and
    ``_BLOCK_BYTES`` per block, spread evenly over the fewest grid steps,
    so ``B`` is padded by less than 128 lanes a step.
    """
    fit = min(_MAX_BT, _BLOCK_BYTES // (4 * n * jpad)) // _LANE
    if b < _LANE or fit < 1:
        return None
    tiles = -(-b // _LANE)
    steps = -(-tiles // fit)
    return _LANE * -(-tiles // steps)


def row_total(row):
    """The sum of a ``(1, Np)`` row, replicated across its lanes.

    Mosaic bitcasts and compares vectors, not scalar registers, and it
    cannot broadcast a ``(1, 1)`` vector over sublanes and lanes at once.
    So every per-scenario scalar of the per-scenario layout (budget,
    counts, bisection bounds) is a lane-replicated ``(1, Np)`` row, and
    comparing one against the ``(Jp, Np)`` tile broadcasts over sublanes
    only.
    """
    return jnp.broadcast_to(jnp.sum(row, axis=1, keepdims=True), row.shape)


def _bisect(budget_f, total):
    """The budget-th largest positive gain of each scenario, as float32.

    ``total(mask_of)`` counts, per scenario, the positive gains for which
    ``mask_of(x)`` holds.  Invariant: count(>= bitcast(lo)) >= budget >
    count(>= bitcast(hi)); 31 halvings of the positive-float bit range
    leave hi == lo + 1, so bitcast(lo) IS the threshold.
    """

    def body(_, lohi):
        lo, hi = lohi
        mid = lo + ((hi - lo) >> 1)  # overflow-safe midpoint (hi > lo)
        t = jax.lax.bitcast_convert_type(mid, jnp.float32)
        enough = total(lambda x: x >= t) >= budget_f
        return jnp.where(enough, mid, lo), jnp.where(enough, hi, mid)

    shape = budget_f.shape
    lo, _hi = jax.lax.fori_loop(
        0, 31, body,
        (jnp.full(shape, 1, jnp.int32), jnp.full(shape, 0x7F800000, jnp.int32)),
    )
    return jax.lax.bitcast_convert_type(lo, jnp.float32)


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
    thresh = _bisect(budget_f, lambda mask_of: row_total(count_row(pos & mask_of(x))))
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


def _gain_topr_lanes_kernel(cand_ref, budget_ref, take_ref):
    n = cand_ref.shape[0]  # cand (N, Jp, Bt); budget (1, Bt); take (N, 1, Bt)
    budget = budget_ref[...]
    budget_f = budget.astype(jnp.float32)

    def hits(mask_of):  # per operator, 1.0 where a positive gain passes
        return [jnp.where((x > 0.0) & mask_of(x), 1.0, 0.0)
                for x in (cand_ref[i] for i in range(n))]

    def counts(mask_of):  # per operator, a (1, Bt) count
        return [jnp.sum(h, axis=0, keepdims=True) for h in hits(mask_of)]

    def total(mask_of):  # added over operators first, then one sublane reduce
        return jnp.sum(functools.reduce(operator.add, hits(mask_of)), axis=0, keepdims=True)

    pos_row = counts(lambda x: True)
    use_all = functools.reduce(operator.add, pos_row) <= budget_f
    thresh = _bisect(budget_f, total)
    strict = counts(lambda x: x > thresh)
    ties = counts(lambda x: x == thresh)
    rem = budget_f - functools.reduce(operator.add, strict)
    before = jnp.zeros_like(budget_f)  # ties of the operators before this one
    for i in range(n):
        extra = jnp.clip(jnp.minimum(ties[i], rem - before), 0.0, None)
        take = jnp.where(use_all, pos_row[i], strict[i] + extra)
        take_ref[i] = jnp.where(budget > 0, take, 0.0).astype(jnp.int32)
        before = before + ties[i]


@functools.partial(jax.jit, static_argnames=("bt", "interpret"))
def gain_topr_lanes(cand, budget, *, bt: int, interpret: bool = False):
    """The lane layout at lane tile ``bt`` (a multiple of 128):
    ``cand [B, N, J]`` + ``budget [B]`` -> ``take [B, N]`` int32.

    ``cand`` goes to ``(N, Jp, Bp)`` with ``Bp`` a multiple of ``bt``;
    padded lanes carry budget 0, so they take 0.
    """
    b, n, j = cand.shape
    jpad = _pad_shapes(n, j)[1]
    bp = -(-b // bt) * bt
    x = jnp.pad(jnp.asarray(cand, dtype=jnp.float32), ((0, bp - b), (0, 0), (0, jpad - j)))
    x = jnp.transpose(x, (1, 2, 0))  # (N, Jp, Bp): gains on sublanes, scenarios on lanes
    bud = jnp.pad(jnp.asarray(budget, dtype=jnp.int32), (0, bp - b)).reshape(1, bp)
    take = pl.pallas_call(
        _gain_topr_lanes_kernel,
        grid=(bp // bt,),
        in_specs=[
            pl.BlockSpec((n, jpad, bt), lambda i: (0, 0, i)),
            pl.BlockSpec((1, bt), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((n, 1, bt), lambda i: (0, 0, i)),
        out_shape=jax.ShapeDtypeStruct((n, 1, bp), jnp.int32),
        interpret=interpret,
        name="gain_topr_lanes",
    )(x, bud)
    return take[:, 0, :b].T


@functools.partial(jax.jit, static_argnames=("interpret",))
def gain_topr_pallas(cand, budget, *, interpret: bool = False):
    """``cand [B, N, J]`` + ``budget [B]`` -> ``take [B, N]`` int32.

    Computes in float32 (counts are exact integers far below 2^24).  The
    lane layout serves ``B >= 128`` where its block fits (`_lane_tile`),
    the per-scenario layout every other shape; padding rides through as
    zero gains, which the positivity mask discards.
    """
    if cand.ndim != 3:
        raise ValueError(f"cand must be [B, N, J], got shape {cand.shape}")
    b, n, j = cand.shape
    npad, jpad = _pad_shapes(n, j)
    bt = _lane_tile(b, n, jpad)
    if bt is not None:
        return gain_topr_lanes(cand, budget, bt=bt, interpret=interpret)
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
        name="gain_topr_pallas",
    )(x, bud)
    return take[:, 0, :n].astype(jnp.int32)
