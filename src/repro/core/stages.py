"""Stage names for the control plane's device programs (DESIGN.md §19).

The decide and the fused loop label their work with :func:`scope`, a
``jax.named_scope("drs.<stage>")``.  XLA keeps the scope path in each
compiled instruction's ``op_name`` metadata (a fusion carries the
``op_name`` of its root instruction), and a profiler trace names each
device operation by its compiled instruction.  So the stage of every
device op in a trace is known once the program's instructions are.

The programs report those themselves.  :func:`recorded` wraps a jitted
program so that its first call puts the program and the *shapes* of its
arguments (never the buffers) into a small process-wide table; later
calls pay one flag test.  :func:`op_stages` lowers and compiles the
recorded programs again when someone asks (a persistent-cache hit where
the compile cache is on) and maps each compiled instruction name to its
stage.  The table holds the programs themselves, so the map stays
readable after their owners let go of them.
"""

from __future__ import annotations

import collections
import re

# The stages of the dense decide core, in dataflow order.
DECIDE = ("trigger", "solve", "table", "candidates", "topr", "price", "gates")
# Every stage: the decide core's, the compacted decide's trigger scan and
# bucketed dispatch, and the fused loop's simulated window and window
# measurement.
STAGES = DECIDE + ("compact", "window", "measure")
PREFIX = "drs."
# Programs kept in the table; the oldest goes first.
KEEP = 8

_SEGMENT = re.compile(re.escape(PREFIX) + r"([A-Za-z_]+)")
# One instruction of ``Compiled.as_text()``: its name and the rest of
# its line, which holds its ``op_name`` where it has one.
_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([^\s=]+) = (.*)$", re.MULTILINE)
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_UNRESOLVED = object()

_programs: collections.deque = collections.deque(maxlen=KEEP)
_resolved = _UNRESOLVED


def scope(stage: str):
    """The named scope of ``stage``: ``jax.named_scope("drs.<stage>")``."""
    import jax

    if stage not in STAGES:
        raise ValueError(f"unknown stage {stage!r}; stages are {STAGES}")
    return jax.named_scope(PREFIX + stage)


def stage_of(op_name: str) -> str | None:
    """The innermost ``drs.<stage>`` segment of an ``op_name`` path, or
    ``None`` where the path holds none."""
    found = [s for s in _SEGMENT.findall(op_name) if s in STAGES]
    return found[-1] if found else None


def _shape(x):
    """What a jitted call sees of one argument: its abstract value (shape,
    canonical dtype, weak type) and, for an array committed to devices,
    its sharding.  An uncommitted array lowers with no sharding, and so
    does its record."""
    import jax

    aval = jax.typeof(x)
    committed = getattr(x, "committed", False)
    return jax.ShapeDtypeStruct(
        aval.shape, aval.dtype, sharding=x.sharding if committed else None,
        weak_type=aval.weak_type,
    )


def recorded(program):
    """``program`` (a jitted function) as a function whose first call
    records the program and its arguments' shapes, dtypes and shardings
    in the table, then calls it.  Recording traces and compiles nothing."""
    import jax

    pending = True

    def call(*args):
        global _resolved
        nonlocal pending
        if pending:
            pending = False
            shapes = jax.tree.map(_shape, args)
            _programs.append((program, shapes, bool(jax.config.jax_enable_x64)))
            _resolved = _UNRESOLVED
        return program(*args)

    return call


def instruction_stages(text: str) -> dict:
    """``{instruction name: stage or None}`` over every instruction of a
    compiled module's text (``None``: its ``op_name`` names no stage, or
    it has none)."""
    out = {}
    for name, rest in _INSTRUCTION.findall(text):
        op = _OP_NAME.search(rest)
        out[name] = stage_of(op.group(1)) if op else None
    return out


def op_stages() -> dict | None:
    """``{compiled instruction name: stage or None}`` over the recorded
    programs; ``None`` (no stage) marks an instruction outside every
    scope.  The names are those a profiler trace gives the device ops.
    Returns ``None`` where one name belongs to two stages in two programs:
    a trace op of that name cannot be placed."""
    global _resolved
    if _resolved is _UNRESOLVED:
        _resolved = _resolve()
    return _resolved


def _resolve() -> dict | None:
    import jax

    out: dict = {}
    for program, shapes, x64 in list(_programs):
        # Lowered under the precision the first call saw, as that call was.
        with jax.enable_x64(x64):
            text = program.lower(*shapes).compile().as_text()
        for name, stage in instruction_stages(text).items():
            if out.get(name, stage) != stage:
                return None
            out[name] = stage
    return out


def clear() -> None:
    """Forget every recorded program."""
    global _resolved
    _programs.clear()
    _resolved = _UNRESOLVED
