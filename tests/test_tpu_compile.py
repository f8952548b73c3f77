"""The control plane's Pallas kernels compile for a TPU v5e.

Interpret-mode parity tests cannot see the TPU's lowering rules (block
tiling, Mosaic's vector-only ops, scoped VMEM).  These tests ask the
installed TPU compiler to compile each main-path kernel at fleet widths
for a described v5e chip, which needs no chip attached, and check that
the compiled program holds the kernel itself (``tpu_custom_call``).

The topology is described inside a fixture, never at import, so every
pytest-xdist worker collects the same tests and only the worker that runs
this file loads the TPU library.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.decide_fused import kernel as dk
from repro.kernels.erlang_c import kernel as ek
from repro.kernels.gain_topr import kernel as gk
from repro.kernels.l2_match import kernel as lk
from repro.kernels.queue_step import kernel as qk

F32, I32 = jnp.float32, jnp.int32
B, N, K = 16384, 8, 64  # a 16384-lane fleet of N <= 8 operators, k_max 64

# name -> (kernel, argument (shape, dtype)s)
CASES = {
    "queue_step_M131072": (
        qk.queue_step_pallas, [((B * N,), F32)] * 4,
    ),
    "erlang_c_S131072_k64": (
        functools.partial(ek.erlang_b_table_pallas, k_hi=K), [((B * N,), F32)],
    ),
    "erlang_c_S32768_k512": (
        functools.partial(ek.erlang_b_table_pallas, k_hi=512), [((32768,), F32)],
    ),
    "gain_topr_B16384": (
        gk.gain_topr_pallas, [((B, N, K), F32), ((B,), I32)],
    ),
    "gain_topr_B1_fleet_rows": (  # plan_batched: one merged [rows, budget] table
        gk.gain_topr_pallas, [((1, 384, 1024), F32), ((1,), I32)],
    ),
    # The cells' decide shapes: VLD (N = 3, k_max 22) dense, at the
    # compacted rungs 4096 and 1024, and FPD (k_max 21).
    "gain_topr_lanes_B16384_J22": (
        gk.gain_topr_pallas, [((16384, 3, 22), F32), ((16384,), I32)],
    ),
    "gain_topr_lanes_B4096_J22": (
        gk.gain_topr_pallas, [((4096, 3, 22), F32), ((4096,), I32)],
    ),
    "gain_topr_lanes_B1024_J22": (
        gk.gain_topr_pallas, [((1024, 3, 22), F32), ((1024,), I32)],
    ),
    "gain_topr_lanes_B16384_J21": (
        gk.gain_topr_pallas, [((16384, 3, 21), F32), ((16384,), I32)],
    ),
    # The NEXmark cell's dense decide: N = 7 operators, k_max 44.
    "gain_topr_lanes_B16384_N7_J44": (
        gk.gain_topr_pallas, [((16384, 7, 44), F32), ((16384,), I32)],
    ),
    "erlang_c_S114688_k44": (
        functools.partial(ek.erlang_b_table_pallas, k_hi=44), [((16384 * 7,), F32)],
    ),
    "decide_fused_B16384_k64": (
        functools.partial(dk.batch_decide_pallas, k_hi=K),
        [((B, N), F32)] * 6 + [((B,), I32)],
    ),
    "decide_fused_B1_k512": (
        functools.partial(dk.batch_decide_pallas, k_hi=512),
        [((1, N), F32)] * 6 + [((1,), I32)],
    ),
    "l2_match_count": (
        lambda a, b, valid: lk.match_count_pallas(a, b, valid, 0.5),
        [((512, 128), F32), ((256, 128), F32), ((512,), jnp.bool_)],
    ),
}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # A program compiled for a described chip is written to the persistent
    # cache but cannot be read back without one: keep the cache out of it.
    # JAX decides once whether the cache is in use; reset_cache makes it
    # look at the flag again.
    from jax.experimental.compilation_cache import compilation_cache

    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cache_on)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(name, one_chip):
    kernel, shapes = CASES[name]
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = jax.jit(kernel).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    if name.startswith("gain_topr"):  # the layout follows B: lanes from 128 up
        assert ("gain_topr_lanes" in text) == (shapes[0][0][0] >= 128)
