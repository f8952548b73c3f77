"""Dispatch wrapper: Pallas on TPU, jnp scan reference elsewhere."""

from __future__ import annotations

import jax

from . import kernel as _kernel, ref as _ref

__all__ = ["erlang_b_table"]


def erlang_b_table(
    a,
    *,
    k_hi: int,
    interpret: bool = False,
    force_kernel: bool = False,
):
    """[S] offered loads -> [k_hi+1, S] Erlang-B blocking table."""
    if force_kernel or jax.default_backend() == "tpu":
        return _kernel.erlang_b_table_pallas(a, k_hi=k_hi, interpret=interpret)
    return _ref.erlang_b_table(a, k_hi=k_hi)
