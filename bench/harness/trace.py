"""Reduction of a profiler trace to device busy time, idle share, op counts
and a breakdown of device ops and idle gaps.

The reduction works on plain intervals; :func:`load_profile` is the one place
that reads JAX's ``.xplane.pb`` format. Host spans that the harness writes
with ``jax.profiler.TraceAnnotation`` are named ``bench.*``; an idle gap is
labelled by the innermost such span around its middle.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os
from typing import Iterable, List, Optional, Sequence, Tuple

SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclasses.dataclass
class Trace:
    """Device op events per chip, and the harness's host spans."""

    device_ops: List[List[Event]]
    spans: List[Event]


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    n_chips: int  # accelerator planes that had op events
    busy_s: float  # union of op intervals, averaged over chips
    n_ops: int  # op events inside the window, summed over chips
    device_ops: List[Tuple[str, float]]  # top ops by summed self seconds
    idle_gaps: List[Tuple[str, float]]  # longest gaps, labelled

    @property
    def idle_share(self) -> Optional[float]:
        """``None`` where the trace holds no accelerator ops to read."""
        return 1.0 - self.busy_s / self.window_s if self.n_chips else None


def merge(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Union of ``(start, end)`` intervals as sorted, disjoint intervals."""
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(intervals, lo: float, hi: float) -> List[Tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def gaps(merged: Sequence[Tuple[float, float]], lo: float, hi: float):
    """The idle intervals of ``[lo, hi]`` between disjoint busy intervals."""
    out, t = [], lo
    for s, e in merged:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def label(t: float, spans: Sequence[Event]) -> str:
    """The innermost (shortest) span that holds ``t``, or ``"outside"``."""
    best: Optional[Event] = None
    for sp in spans:
        if sp.start_ns <= t <= sp.end_ns and (best is None or sp.dur_ns < best.dur_ns):
            best = sp
    return best.name if best else "outside"


def window_of(trace: Trace) -> Tuple[float, float]:
    """The ``bench.window`` span; the traced window is what it covers."""
    wins = [sp for sp in trace.spans if sp.name == WINDOW_SPAN]
    if len(wins) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN} span in the trace, found {len(wins)}")
    return wins[0].start_ns, wins[0].end_ns


def self_times(events: Sequence[Event]) -> List[Tuple[str, float]]:
    """``(name, self ns)`` per event: its duration less that of the events
    nested in it (a ``while`` op holds its body's ops on the same line)."""
    order = sorted(range(len(events)), key=lambda i: (events[i].start_ns, -events[i].dur_ns))
    self_ns = [ev.dur_ns for ev in events]
    stack: List[int] = []
    for i in order:
        ev = events[i]
        while stack and events[stack[-1]].end_ns <= ev.start_ns:
            stack.pop()
        if stack:
            self_ns[stack[-1]] -= ev.dur_ns
        stack.append(i)
    return [(ev.name, ns) for ev, ns in zip(events, self_ns)]


def reduce(trace: Trace, top: int = 10) -> TraceSummary:
    lo, hi = window_of(trace)
    busy, n_ops, per_op, all_gaps = 0.0, 0, {}, []
    for ops in trace.device_ops:
        inside = [Event(ev.name, max(ev.start_ns, lo), min(ev.end_ns, hi) - max(ev.start_ns, lo))
                  for ev in ops if ev.end_ns > lo and ev.start_ns < hi]
        n_ops += len(inside)
        for name, ns in self_times(inside):
            per_op[name] = per_op.get(name, 0.0) + ns
        merged = merge(clip(((ev.start_ns, ev.end_ns) for ev in inside), lo, hi))
        busy += sum(e - s for s, e in merged)
        all_gaps += gaps(merged, lo, hi)
    n_chips = len(trace.device_ops)
    all_gaps.sort(key=lambda g: g[0] - g[1])
    return TraceSummary(
        window_s=(hi - lo) * 1e-9,
        n_chips=n_chips,
        busy_s=busy / max(n_chips, 1) * 1e-9,
        n_ops=n_ops,
        device_ops=[(name, ns * 1e-9) for name, ns in
                    sorted(per_op.items(), key=lambda kv: -kv[1])[:top]],
        idle_gaps=[(label((s + e) / 2, trace.spans), (e - s) * 1e-9)
                   for s, e in all_gaps[:top]],
    )


def is_device_plane(name: str) -> bool:
    return name.startswith("/device:") and "CPU" not in name


OP_LINES = ("XLA Ops",)
MODULE_LINE = "XLA Modules"


def _module_of(modules: Sequence[Event], starts: Sequence[float], t: float) -> str:
    i = bisect.bisect_right(starts, t) - 1
    return modules[i].name if i >= 0 and t < modules[i].end_ns else "?"


def load_profile(log_dir: str) -> Trace:
    """Read the newest ``.xplane.pb`` under ``log_dir``: op events of the
    ``XLA Ops`` line of every accelerator plane, each named
    ``<program>/<op>`` after the ``XLA Modules`` event that holds it, and
    every ``bench.*`` event of the host planes."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True),
                   key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    data = ProfileData.from_file(paths[-1])
    device_ops, spans = [], []
    for plane in data.planes:
        if is_device_plane(plane.name):
            lines = {line.name: list(line.events) for line in plane.lines}
            modules = sorted((Event(ev.name.split("(")[0], ev.start_ns, ev.duration_ns)
                              for ev in lines.get(MODULE_LINE, [])), key=lambda e: e.start_ns)
            starts = [m.start_ns for m in modules]
            ops = [Event(f"{_module_of(modules, starts, ev.start_ns)}/{ev.name.split(' = ')[0]}",
                         ev.start_ns, ev.duration_ns)
                   for name in OP_LINES for ev in lines.get(name, [])]
            if ops:
                device_ops.append(ops)
        elif plane.name.startswith("/host:"):
            spans += [Event(ev.name, ev.start_ns, ev.duration_ns)
                      for line in plane.lines for ev in line.events
                      if ev.name.startswith(SPAN_PREFIX)]
    return Trace(device_ops=device_ops, spans=spans)
