"""Pallas TPU kernel: blocked pairwise squared-L2 distance (+ fused count).

The paper's VLD matcher bolt computes L2 distances between every frame
descriptor and a pre-generated logo library — its dominant compute (the
recommended allocation 10:11:1 puts half the cluster on this bolt).  On
TPU the distance matrix should ride the MXU via

    ||a - b||^2 = ||a||^2 + ||b||^2 - 2 a.b^T,

so the kernel is a blocked matmul with two fused rank-1 corrections:

* grid (M/bm, N/bn); each step loads an A tile (bm, D) and B tile (bn, D)
  into VMEM, computes the cross term with ``jnp.dot`` (MXU,
  preferred_element_type=f32), adds the row/col norms (VPU), clamps at 0.
* ``l2_match_count_kernel`` additionally fuses the threshold + column
  reduction, accumulating per-library-row match counts across the M grid
  axis — the minor grid axis, whose steps run sequentially on one out
  block, so the accumulation is safe (init at i == 0); this keeps the
  (M, N) distance matrix entirely out of HBM, turning an O(M*N) memory
  intermediate into O(N).  The squared threshold is an SMEM scalar.

Block sizes default to MXU-aligned (128, 128); D is kept whole in VMEM
(descriptor dims are small: 64-128 for SIFT-like features).  VMEM budget
per step = bm*D + bn*D + bm*bn floats ~ (128*128)*3 * 4B = 192 KiB << 16 MiB.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["pairwise_sq_l2_pallas", "match_count_pallas"]


def _dist_kernel(a_ref, b_ref, out_ref):
    a = a_ref[...].astype(jnp.float32)  # (bm, D)
    b = b_ref[...].astype(jnp.float32)  # (bn, D)
    cross = jnp.dot(a, b.T, preferred_element_type=jnp.float32)  # MXU
    a2 = jnp.sum(a * a, axis=1, keepdims=True)  # (bm, 1)
    b2 = jnp.sum(b * b, axis=1, keepdims=True).T  # (1, bn)
    out_ref[...] = jnp.maximum(a2 + b2 - 2.0 * cross, 0.0)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "interpret"))
def pairwise_sq_l2_pallas(
    a: jnp.ndarray,
    b: jnp.ndarray,
    *,
    bm: int = 128,
    bn: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    """[M,D] x [N,D] -> [M,N] squared L2 distances. M % bm == N % bn == 0."""
    m, d = a.shape
    n, d2 = b.shape
    assert d == d2, f"feature dims differ: {d} vs {d2}"
    assert m % bm == 0 and n % bn == 0, (m, n, bm, bn)
    grid = (m // bm, n // bn)
    return pl.pallas_call(
        _dist_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, d), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, d), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        interpret=interpret,
    )(a, b)


def _count_kernel(a_ref, b_ref, valid_ref, thresh_ref, out_ref):
    i = pl.program_id(1)
    a = a_ref[...].astype(jnp.float32)
    b = b_ref[...].astype(jnp.float32)
    cross = jnp.dot(a, b.T, preferred_element_type=jnp.float32)
    a2 = jnp.sum(a * a, axis=1, keepdims=True)
    b2 = jnp.sum(b * b, axis=1, keepdims=True).T
    d2 = jnp.maximum(a2 + b2 - 2.0 * cross, 0.0)  # (bm, bn)
    t2 = thresh_ref[0]  # SMEM scalar
    hits = (d2 <= t2) & (valid_ref[...] > 0)  # valid: (bm, 1)
    partial = jnp.sum(hits.astype(jnp.int32), axis=0, keepdims=True)  # (1, bn)

    @pl.when(i == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[...] += partial


@functools.partial(jax.jit, static_argnames=("bm", "bn", "interpret"))
def match_count_pallas(
    a: jnp.ndarray,
    b: jnp.ndarray,
    valid: jnp.ndarray,
    threshold: float | jnp.ndarray,
    *,
    bm: int = 128,
    bn: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    """Fused distance+threshold+count: int32 [N] without materialising [M,N].

    Accumulates across the (sequential) M grid axis.  M is the minor grid
    axis, so each out block is visited m//bm times in a row and stays in
    VMEM while it accumulates.
    """
    m, d = a.shape
    n, _ = b.shape
    assert m % bm == 0 and n % bn == 0, (m, n, bm, bn)
    t2 = jnp.asarray([jnp.float32(threshold) ** 2])
    grid = (n // bn, m // bm)
    out = pl.pallas_call(
        _count_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, d), lambda j, i: (i, 0)),
            pl.BlockSpec((bn, d), lambda j, i: (j, 0)),
            pl.BlockSpec((bm, 1), lambda j, i: (i, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((1, bn), lambda j, i: (0, j)),
        out_shape=jax.ShapeDtypeStruct((1, n), jnp.int32),
        interpret=interpret,
    )(a, b, valid.astype(jnp.int32).reshape(m, 1), t2)
    return out[0]
