"""Pure-jnp oracle for the Erlang-B recurrence table (lax.scan over j)."""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["erlang_b_table"]


def erlang_b_table(a: jnp.ndarray, *, k_hi: int) -> jnp.ndarray:
    """[S] offered loads -> [k_hi+1, S] table; dtype follows the input
    (float64 under enable_x64, else float32)."""
    a = jnp.asarray(a)
    b0 = jnp.ones_like(a)

    def step(b, j):
        b = a * b / (j + a * b)
        return b, b

    js = jnp.arange(1, k_hi + 1, dtype=a.dtype)
    _, rows = jax.lax.scan(step, b0, js)
    return jnp.concatenate([b0[None, :], rows], axis=0)
