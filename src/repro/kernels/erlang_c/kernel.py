"""Pallas TPU kernel: the Erlang-B recurrence table over a lane of loads.

The grid walks tiles of 128*m lanes; in each, the offered loads sit in a
(1, tile) VMEM row and the fori_loop walks j = 1..k_hi writing one
(1, tile) row of the table per step:

    B(j) = a * B(j-1) / (j + a * B(j-1)).

The recursion is inherently sequential in j, so the kernel's only
parallelism is across lanes — which is exactly the batch axis the
scheduler needs (operators x tenants).  The tile width is chosen from
k_hi so that one (k_hi+1, tile) table block, double-buffered, stays
within ``_BLOCK_BYTES`` of VMEM whatever S is: at most 14464 lanes per
tile at k_hi = 64, and 128 at k_hi = 4096.  Every lane runs the same
recurrence whatever tile it lands in, so the table does not depend on
the tiling.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["erlang_b_table_pallas"]

_LANE = 128
# VMEM for one table block, double-buffered by the pipeline; well inside
# the 16 MiB scoped-VMEM default of a TPU v5e core.
_BLOCK_BYTES = 8 << 20


def _lane_tiles(s: int, rows: int) -> tuple[int, int]:
    """(tile width, tile count) covering ``s`` lanes with the least padding
    such that a ``(rows, tile)`` float32 block, double-buffered, fits in
    ``_BLOCK_BYTES``."""
    groups = -(-s // _LANE)
    max_groups = max(_BLOCK_BYTES // (2 * 4 * rows * _LANE), 1)
    n_tiles = -(-groups // max_groups)
    return _LANE * -(-groups // n_tiles), n_tiles


def _erlang_b_kernel(a_ref, out_ref, *, k_hi: int):
    a = a_ref[...]  # (1, tile)
    ones = jnp.ones_like(a)
    out_ref[pl.ds(0, 1), :] = ones

    def body(j, b):
        b = a * b / (j.astype(a.dtype) + a * b)
        out_ref[pl.ds(j, 1), :] = b
        return b

    jax.lax.fori_loop(1, k_hi + 1, body, ones)


@functools.partial(jax.jit, static_argnames=("k_hi", "interpret"))
def erlang_b_table_pallas(
    a: jnp.ndarray, *, k_hi: int, interpret: bool = False
) -> jnp.ndarray:
    """[S] offered loads -> [k_hi+1, S] Erlang-B blocking table (float32).

    Row j holds B(j, a) for every lane; row 0 is all-ones.  Lanes are
    padded to a whole number of tiles and the pad is sliced off before
    returning.
    """
    if a.ndim != 1:
        raise ValueError(f"a must be 1-D, got shape {a.shape}")
    s = a.shape[0]
    rows = k_hi + 1
    rows_pad = rows + (-rows) % 8  # float32 sublane tile
    tile, n_tiles = _lane_tiles(s, rows_pad)
    s_pad = tile * n_tiles
    a2 = jnp.pad(a.astype(jnp.float32), (0, s_pad - s)).reshape(1, s_pad)
    out = pl.pallas_call(
        functools.partial(_erlang_b_kernel, k_hi=k_hi),
        grid=(n_tiles,),
        in_specs=[pl.BlockSpec((1, tile), lambda i: (0, i))],
        out_specs=pl.BlockSpec((rows_pad, tile), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((rows_pad, s_pad), jnp.float32),
        interpret=interpret,
    )(a2)
    return out[:rows, :s]
