"""Device time by program stage.

The program names the stage of each of its compiled instructions
(``repro.core.stages.op_stages()``: the ``drs.<stage>`` named scopes of
the decide and the fused loop, a fusion taking its root instruction's).
A trace names each device op by its compiled instruction.  So each op of
the traced window is put in its stage, and its self time (its time
outside the ops it encloses) summed there.  An op of no stage of its own
that runs inside another op takes that op's stage; the rest are summed
under ``unstaged``.  Self times partition the device's busy time: the
sums over every stage and ``unstaged`` add up to ``busy_s``.

Every function returns ``None`` where there is nothing to read: no trace,
no device op, a program without stage scopes, or a map that puts one
instruction name in two stages.
"""

from __future__ import annotations

UNSTAGED = "unstaged"


def op_stages():
    """The program's ``{instruction name: stage or None}``, or ``None``."""
    try:
        from repro.core import stages
    except ImportError:  # a program without stage scopes
        return None
    return stages.op_stages()


def split(trace, names: dict) -> dict:
    """``{stage or "unstaged": seconds}`` of the trace's device self time
    per chip.  Each op is placed by its instruction name through
    ``names``; an op of no stage that runs inside another op (a copy XLA
    put into a scoped loop's body) takes the stage of the op enclosing
    it."""
    chips = max(len(trace.chips), 1)
    placed: list = []
    out: dict = {}
    for i, ((name, _, _), ns) in enumerate(zip(trace.ops, trace.self_ns)):
        stage = names.get(name.split(" = ")[0].lstrip("%"))
        if stage is None and trace.parent[i] >= 0:
            stage = placed[trace.parent[i]]
        placed.append(stage)
        key = stage or UNSTAGED
        out[key] = out.get(key, 0.0) + ns * 1e-9 / chips
    return out


def seconds(ctx, stage_names=None):
    """The traced window's device seconds by stage; with ``stage_names``,
    only those stages (``None`` where the program has none of them)."""
    trace = ctx["trace"]
    if trace is None or not trace.ops:
        return None
    names = op_stages()
    if not names:
        return None
    if stage_names is not None and not set(stage_names) & set(names.values()):
        return None
    return split(trace, names)


def per_tick_ms(ctx, stage_names) -> float | None:
    """Milliseconds per tick of the window in ``stage_names``."""
    secs, ticks = seconds(ctx, stage_names), ctx["result"].get("attempted")
    if secs is None or not ticks:
        return None
    return 1e3 * sum(secs.get(s, 0.0) for s in stage_names) / ticks


def per_window_ms(ctx, stage_names) -> float | None:
    """Milliseconds per simulated control window (calls x the mix's
    ``ticks``) in ``stage_names``."""
    secs = seconds(ctx, stage_names)
    windows = ctx["result"].get("attempted", 0) * ctx["traffic"].get("ticks", 0)
    if secs is None or not windows:
        return None
    return 1e3 * sum(secs.get(s, 0.0) for s in stage_names) / windows


def unstaged_share(ctx) -> float | None:
    """Share (%) of the device's busy time in ops of no stage."""
    secs = seconds(ctx)
    if secs is None or not sum(secs.values()):
        return None
    return 100.0 * secs.get(UNSTAGED, 0.0) / sum(secs.values())
