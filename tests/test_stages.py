"""Stage scopes in the control plane's device programs (DESIGN.md §19).

Each compiled instruction of the dense decide, the compacted decide and
the fused loop maps to the stage that produced it; the map is resolved
from what the programs recorded of their first call, which costs no
trace and no compile, and it stays readable after the programs' owners
let go of them.
"""

from __future__ import annotations

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.core.controller as ctl
from repro.api import AppGraph, Edge, OpDef
from repro.api.session import ScenarioRunner
from repro.core import stages
from repro.streaming.scenarios import fpd_scenario, vld_scenario

B = 64


@pytest.fixture
def fresh():
    """An empty program table, emptied again afterwards."""
    stages.clear()
    yield
    stages.clear()


@pytest.fixture
def events():
    """Backend compiles and jaxpr traces, counted from JAX's monitoring
    events (as ``chipbench/bench.py`` ``CompileClock`` counts compiles)."""
    counts = {"compile": 0, "trace": 0}
    names = {"/jax/core/compile/backend_compile_duration": "compile",
             "/jax/core/compile/jaxpr_trace_duration": "trace"}

    def on(event, duration, **_):
        if event in names:
            counts[names[event]] += 1

    jax.monitoring.register_event_duration_secs_listener(on)
    yield counts
    jax.monitoring.unregister_event_duration_listener(on)


@pytest.fixture(scope="module")
def vld():
    """B copies of the paper's VLD chain under static budgets."""
    return ScenarioRunner([vld_scenario(negotiated=False, k_max=22)] * B,
                          tick_interval=5.0, backend="jax")


def _inputs(static, seed=0):
    b, n = static.batch, static.n
    rng = np.random.default_rng(seed)
    lam = np.abs(rng.normal(6.0, 2.0, (b, n))).astype(np.float32)
    mu = np.repeat([[2.0, 5.0, 50.0]], b, axis=0).astype(np.float32)
    drop = np.zeros((b, n), np.float32)
    lam0 = lam[:, 0].copy()
    k = np.repeat([[13, 7, 2]], b, axis=0).astype(np.int32)
    return lam, mu, drop, lam0, k


def _staged(table):
    return {s for s in table.values() if s is not None}


def _only_program():
    (program, shapes, _), = stages._programs
    return program, shapes


def _nexmark_shaped(b):
    """B lanes of the NEXmark cell's decide shape: N = 7, four keyed
    operators, k_max 44."""
    keyed = (False, True, True, False, True, True, False)
    ops = [OpDef(f"o{i}", mu=5000.0, scaling="keyed" if kd else "replica",
                 hot_share=0.5 if kd else None) for i, kd in enumerate(keyed)]
    graph = AppGraph(ops, [Edge(f"o{i}", f"o{i + 1}") for i in range(6)], {"o0": 10000.0})
    static = ctl.ControllerStatic.from_graphs([graph] * b)
    full = lambda v: np.full(b, v)
    params = ctl.ControllerParams(
        t_max=full(np.nan), k_max=np.full(b, 44, np.int64), headroom=full(1.1),
        scale_in_hysteresis=full(0.8), min_improvement=full(0.05),
        horizon_seconds=full(300.0), allocator=("table",) * b,
    )
    return static, params


def test_stage_of_takes_the_innermost_scope():
    path = "jit(decide)/drs.compact/cond/branch_1_fun/drs.topr/pallas_call"
    assert stages.stage_of(path) == "topr"
    assert stages.stage_of("jit(run)/while/body/drs.window/while/body/add") == "window"
    assert stages.stage_of("jit(f)/drs.solve/drs.nope/lu") == "solve"
    assert stages.stage_of("jit(decide)/jit(solve)/vmap()/lu") is None
    assert stages.stage_of("") is None


def test_scope_names_only_known_stages():
    with pytest.raises(ValueError):
        stages.scope("mpc")
    assert set(stages.DECIDE) < set(stages.STAGES)


def test_instruction_stages_parse_the_compiled_text():
    text = "\n".join([
        "%fused_computation.2 (param_0.5: f32[8]) -> f32[8] {",
        '  ROOT %mul.4 = f32[8]{0} multiply(%a, %b), metadata={op_name="jit(f)/drs.table/mul"}',
        "}",
        "ENTRY %main.5 (x.1: f32[8]) -> f32[8] {",
        '  %x.1 = f32[8]{0} parameter(0), metadata={op_name="x"}',
        "  %copy.6 = s32[] copy(%constant.10)",
        '  ROOT %multiply_fusion = f32[8]{0} fusion(%x.1), kind=kLoop, '
        'metadata={op_name="jit(f)/drs.table/mul" stack_frame_id=4}',
        "}",
    ])
    assert stages.instruction_stages(text) == {
        "mul.4": "table", "x.1": None, "copy.6": None, "multiply_fusion": "table",
    }


def test_dense_decide_covers_the_decide_stages(vld, fresh):
    decide = ctl.make_decide_jax(vld.static, vld._params())
    decide(*_inputs(vld.static))
    table = stages.op_stages()
    assert _staged(table) == set(stages.DECIDE)


@pytest.mark.parametrize("shape", ["vld", "nexmark"])
def test_candidates_and_price_gather_nothing(vld, fresh, shape):
    """The candidates are G masked in place and the price a one-hot select
    (DESIGN.md §19): no gather along k in either stage, and each still
    owns a compiled instruction."""
    if shape == "vld":
        static, params = vld.static, vld._params()
        args = _inputs(static)
    else:
        static, params = _nexmark_shaped(B)
        rng = np.random.default_rng(1)
        lam = rng.uniform(1000.0, 9000.0, (B, 7)).astype(np.float32)
        args = (lam, np.full((B, 7), 5000.0, np.float32), np.zeros((B, 7), np.float32),
                lam[:, 0].copy(), np.full((B, 7), 3, np.int32))
    assert (static.n, int(params.k_max.max())) == {"vld": (3, 22), "nexmark": (7, 44)}[shape]
    ctl.make_decide_jax(static, params)(*args)
    program, shapes = _only_program()
    text = program.lower(*shapes).compile().as_text()
    owner = stages.instruction_stages(text)
    gathers = re.findall(r"^\s*(?:ROOT\s+)?%?([^\s=]+) = [^\n]* gather\(", text, re.MULTILINE)
    assert not {owner[g] for g in gathers} & {"candidates", "price"}
    assert {"candidates", "price"} <= set(owner.values())


def test_compacted_decide_adds_compact(vld, fresh):
    decide = ctl.make_decide_jax(vld.static, vld._params(), compact=True)
    decide(*_inputs(vld.static), decide.init_cache())
    assert _staged(stages.op_stages()) == set(stages.DECIDE) | {"compact"}


def test_fused_loop_covers_window_measure_and_decide(fresh):
    scen = fpd_scenario(negotiated=False, horizon=10.0, warmup=5.0, dt=0.05)
    r = ScenarioRunner([scen] * 8, tick_interval=5.0, backend="jax")
    loop, n_ticks = ctl.make_fused_loop(
        r.arrays, r.static, r._params(), steps_per_tick=r._steps_per_tick,
        warmup_seconds=scen.warmup,
    )
    assert n_ticks == 2
    loop(r.k)
    del loop  # the table keeps what it needs
    assert _staged(stages.op_stages()) == (
        set(stages.DECIDE) | {"window", "measure"}
    )


@pytest.mark.parametrize("placed", [False, True], ids=["host", "device"])
def test_recorded_shapes_lower_the_called_program(vld, fresh, placed):
    # The map is read from a program lowered from the recorded shapes; its
    # instruction names are the trace's only if that is the module the call
    # itself ran.
    args = _inputs(vld.static)
    if placed:
        args = jax.device_put(args, jax.devices()[0])
    decide = ctl.make_decide_jax(vld.static, vld._params())
    decide(*args)
    program, shapes = _only_program()
    assert program.lower(*shapes).as_text() == program.lower(*args).as_text()


def test_recording_traces_and_compiles_nothing(events, fresh):
    def program():  # a new function each time: nothing cached between them
        return jax.jit(lambda x: jnp.sin(x) * 2.0)

    x = jnp.ones(16)

    def cost(fn):
        before = dict(events)
        fn(x)
        return {k: events[k] - before[k] for k in events}

    plain = cost(program())
    rec = stages.recorded(program())
    assert cost(rec) == plain
    assert plain["compile"] >= 1
    assert cost(rec) == {"compile": 0, "trace": 0}
    assert len(stages._programs) == 1


def test_decide_first_call_compiles_once(vld, fresh, events):
    decide = ctl.make_decide_jax(vld.static, vld._params())
    args = jax.device_put(_inputs(vld.static))
    jax.block_until_ready(decide(*args))
    assert events["compile"] == 1
    jax.block_until_ready(decide(*args))
    assert events["compile"] == 1


def test_table_keeps_the_newest_programs(fresh):
    progs = [jax.jit(lambda x, i=i: x + i) for i in range(stages.KEEP + 2)]
    for p in progs:
        stages.recorded(p)(np.float32(1.0))
    assert [p for p, _, _ in stages._programs] == progs[2:]


def test_one_name_in_two_stages_reads_none(fresh):
    def program(stage):
        def f(x):
            with stages.scope(stage):
                return jnp.sin(x)

        return jax.jit(f)

    x = jnp.ones(8)
    stages.recorded(program("solve"))(x)
    one = stages.op_stages()
    assert _staged(one) == {"solve"}
    stages.recorded(program("solve"))(x)
    assert stages.op_stages() == one  # same names, same stage: no conflict
    stages.recorded(program("topr"))(x)
    assert stages.op_stages() is None
