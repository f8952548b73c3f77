"""The yardstick's arithmetic: compile accounting, percentiles and rates,
the reduction of a profiler trace, and the chip's published peaks."""

from __future__ import annotations

import glob
import math
import os

# Published peaks per chip, keyed by ``jax.Device.device_kind``.  Source:
# Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16, 393 TOP/s
# int8, 16 GB HBM at 819 GB/s).  A kind that is not here is an error.
PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12, "hbm_bytes": 16e9,
                    "hbm_bytes_per_s": 819e9},
}

# Host spans the harness writes around each part of a tick or a call.
HOST_SPANS = ("generate", "handoff", "call", "fetch")
WINDOW_SPAN = "window"


def peaks(kind: str) -> dict:
    if kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {kind!r}")
    return PEAKS[kind]


class CompileClock:
    """Seconds the backend spends compiling programs (or fetching them from
    the persistent cache), summed from JAX's monitoring events, and the
    persistent-cache hits.  Tracing is left out: its events nest.
    (Copied from the repo's ``chip_smoke.py``.)"""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.cache_hits = 0
        self.compiles = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.compiles += 1

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


# --------------------------------------------------------------------------- #
# Percentiles and rates
# --------------------------------------------------------------------------- #
def percentile(values, q: float) -> float:
    """The ``q``-th percentile (0..100) by linear interpolation between
    order statistics (numpy's default), over every value."""
    xs = sorted(values)
    if not xs:
        raise ValueError("no values")
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def rate(count: float, seconds: float) -> float:
    if seconds <= 0:
        raise ValueError("rate over an empty window")
    return count / seconds


# --------------------------------------------------------------------------- #
# Trace reduction
# --------------------------------------------------------------------------- #
def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


class TraceSummary:
    """What the metrics read from one traced window.

    ``ops``: device operations ``(name, start_ns, end_ns)`` on every TPU
    plane's "XLA Ops" line, clipped to the window.  Operations nest there
    (a loop's event spans its body's events): ``parent[i]`` is the index
    of the innermost operation enclosing op i (-1 for none) and
    ``self_ns[i]`` its time outside its children.  ``spans``: the
    harness's host spans ``(name, start_ns, end_ns)``; ``window``: the
    ``window`` span; ``chips``: the device planes seen.
    """

    def __init__(self, ops, spans, window, chips):
        self.ops = sorted(ops, key=lambda o: (o[1], -o[2]))
        self.spans, self.window, self.chips = spans, window, chips
        self.parent, self.self_ns = _nest(self.ops)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def busy_intervals(self):
        return _union((s, e) for _, s, e in self.ops)

    @property
    def busy_s(self) -> float:
        """Seconds in which some operation ran, averaged over the chips."""
        busy = sum(e - s for s, e in self.busy_intervals()) * 1e-9
        return busy / max(len(self.chips), 1)

    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def matching(self, match: str) -> list:
        """The operations whose HLO name (the text before `` = ``) holds
        ``match``."""
        return [op for op in self.ops if match in op[0].split(" = ")[0]]

    def op_seconds(self, match: str) -> float:
        """Device seconds of the operations whose HLO name holds ``match``."""
        return sum(e - s for _, s, e in self.matching(match)) * 1e-9

    def enclosing(self, match: str) -> list:
        """The distinct operations that directly enclose an operation whose
        name holds ``match`` (e.g. the loop that launches a kernel)."""
        idx = {self.parent[i] for i, (name, _, _) in enumerate(self.ops)
               if match in name.split(" = ")[0]}
        return [self.ops[i] for i in sorted(idx) if i >= 0]

    def idle_gaps(self):
        """Idle intervals of the device inside the window, each named by the
        host span that overlaps it most (``other`` where none does)."""
        gaps, t = [], self.window[0]
        for s, e in self.busy_intervals() + [[self.window[1], self.window[1]]]:
            if s > t:
                gaps.append((t, s))
            t = max(t, e)
        named = []
        for gs, ge in gaps:
            best, cover = "other", 0
            for name, s, e in self.spans:
                c = min(ge, e) - max(gs, s)
                if c > cover:
                    best, cover = name, c
            named.append((best, (ge - gs) * 1e-9))
        return named

    def breakdown(self, top: int = 10) -> dict:
        """The operations with the most self time (by HLO name, the text
        before `` = ``) and the longest idle gaps, in seconds."""
        by_name: dict = {}
        for (name, _, _), ns in zip(self.ops, self.self_ns):
            key = name.split(" = ")[0].lstrip("%")
            by_name[key] = by_name.get(key, 0) + ns
        ops = sorted(by_name.items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(self.idle_gaps(), key=lambda g: -g[1])[:top]
        return {"device_ops": [[n, ns * 1e-9] for n, ns in ops],
                "idle_gaps": [[n, s] for n, s in gaps]}


def _nest(ops):
    """Parents and self times of time-nested events sorted by (start, -end)."""
    parent = [-1] * len(ops)
    self_ns = [e - s for _, s, e in ops]
    stack = []
    for i, (_, s, e) in enumerate(ops):
        while stack and ops[stack[-1]][2] <= s:
            stack.pop()
        if stack and e <= ops[stack[-1]][2]:
            parent[i] = stack[-1]
            self_ns[stack[-1]] -= e - s
        stack.append(i)
    return parent, self_ns


def read_trace(log_dir: str) -> TraceSummary:
    """Reduce the newest ``.xplane.pb`` under ``log_dir``."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True),
                   key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return summarize(ProfileData.from_file(files[-1]))


def summarize(profile) -> TraceSummary:
    spans, window, raw_ops, chips = [], None, [], []
    for plane in profile.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == WINDOW_SPAN:
                        window = (ev.start_ns, ev.start_ns + ev.duration_ns)
                    elif ev.name in HOST_SPANS:
                        spans.append((ev.name, ev.start_ns, ev.start_ns + ev.duration_ns))
        elif plane.name.startswith("/device:TPU:"):
            chips.append(plane.name)
            for line in plane.lines:
                if line.name == "XLA Ops":
                    raw_ops += [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                                for ev in line.events]
    if window is None:
        raise ValueError(f"the trace has no {WINDOW_SPAN!r} span")
    lo, hi = window
    ops = [(n, max(s, lo), min(e, hi)) for n, s, e in raw_ops if e > lo and s < hi]
    return TraceSummary(ops, spans, window, chips)
