"""The control plane's Pallas kernels compile for a TPU v5e.

Interpret-mode parity tests cannot see the TPU's lowering rules (block
tiling, Mosaic's vector-only ops, scoped VMEM).  These tests ask the
installed TPU compiler to compile each main-path kernel at fleet widths
for a described v5e chip, which needs no chip attached, and check that
the compiled program holds the kernel itself (``tpu_custom_call``).  The
whole decide at the cells' shapes is lowered for the same chip (Pallas
to Mosaic included); compiling it takes about a minute on a CPU host.

The topology is described inside a fixture, never at import, so every
pytest-xdist worker collects the same tests and only the worker that runs
this file loads the TPU library.
"""

from __future__ import annotations

import functools
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

import repro.core.controller as ctl
from repro.api import AppGraph, Edge, OpDef
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


def _chain(mus, hot=None):
    """A chain of operators; ``hot[i]`` makes operator i keyed."""
    hot = hot or {}
    names = [f"op{i}" for i in range(len(mus))]
    ops = [OpDef(nm, mu=m, scaling="keyed" if i in hot else "replica",
                 hot_share=hot.get(i)) for i, (nm, m) in enumerate(zip(names, mus))]
    return AppGraph(ops, [Edge(a, b) for a, b in zip(names, names[1:])], {names[0]: 1.0})


# name -> (one lane's graph, k_max, compacted); the cells' decide shapes:
# VLD (N = 3, k_max 22) dense and compacted, NEXmark (N = 7, k_max 44,
# four keyed operators) dense.
DECIDES = {
    "decide_vld_B16384_k22": (_chain((2.0, 5.0, 50.0)), 22, False),
    "decide_vld_compact_B16384_k22": (_chain((2.0, 5.0, 50.0)), 22, True),
    "decide_nexmark_keyed_B16384_k44": (
        _chain((2000.0, 12000.0, 5000.0, 5000.0, 2000.0, 1200.0, 5000.0),
               {1: 0.3, 2: 0.3, 4: 0.3, 5: 0.3}), 44, False,
    ),
}


@pytest.mark.parametrize("name", sorted(DECIDES))
def test_decide_lowers_for_v5e(name, one_chip):
    """The decide core with its statics as operands at B = 16384: each
    program holds the Erlang table and the lane top-R kernels."""
    graph, k_max, compact = DECIDES[name]
    static = ctl.ControllerStatic.from_graphs([graph])
    full = lambda v: np.full(1, v)
    params = ctl.ControllerParams(
        t_max=full(np.nan), k_max=np.full(1, k_max, np.int64), headroom=full(1.1),
        scale_in_hysteresis=full(0.8), min_improvement=full(0.05),
        horizon_seconds=full(300.0), allocator=("table",),
    )
    b, n = 16384, static.n

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(
            shape, jax.dtypes.canonicalize_dtype(dtype), sharding=one_chip
        )

    st = {k: sds((b,) + v.shape[1:], v.dtype)
          for k, v in ctl._decide_statics(static, params).items()}
    args = [sds((b, n), F32)] * 3 + [sds((b,), F32), sds((b, n), I32)]
    core = ctl._make_decide_core(n, k_max, 5.0, interpret=False, force_kernel=True)
    if compact:
        core = ctl._make_compact_decide(core, b, ctl._resolve_ladder(True, b))
        cache = jax.eval_shape(lambda: ctl.init_decide_cache(b, n, dtype=F32))
        args.append(jax.tree.map(lambda x: sds(x.shape, x.dtype), cache))
    text = jax.jit(core).lower(st, *args).as_text()
    kernels = re.findall(r'kernel_name = "([^"]+)"', text)
    rungs = len(ctl._resolve_ladder(True, b)) if compact else 1
    assert kernels.count("_erlang_b_kernel") == rungs, kernels
    assert kernels.count("gain_topr_lanes") == rungs, kernels
    assert static.keyed == ("keyed" in name)
