"""Device time by named scope, and device idle time by program span, from a
kept profile.

The program names its layers with ``jax.named_scope``; the name lands in the
``op_name`` metadata of every HLO instruction traced inside it. On a TPU the
profiler keeps that path in the ``tf_op`` stat of each device op's event
metadata, for example ``jit(decode_step)/layer_scan/while/body/attention/
cim_linear/cim.quantize/div:``. ``jax.profiler.ProfileData`` does not expose
event metadata, so :func:`op_names` reads it from the ``.xplane.pb`` itself.

An op belongs to the innermost name on its path that is in :data:`SCOPES`,
after autodiff and remat wrappers are taken off (``transpose(jvp(mlp))`` is
``mlp``), so backward ops fall under their forward scope; ops with none are
``other``. Fusions carry the metadata of their root op.

The program's own host spans (``repro.obs.span`` under an active tracer) are
profiler annotations named ``serve.*``, ``train.*``, ``data.*`` and
``fabric.*``, beside the harness's ``bench.*``. An idle gap is labelled by
the innermost span of either kind around its middle.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import heapq
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

from bench.harness.trace import (
    MODULE_LINE,
    OP_LINES,
    WINDOW_SPAN,
    Event,
    gaps,
    is_device_plane,
    merge,
    self_times,
    window_of,
)

# The program's scope names (``repro.obs.scopes``), as this yardstick reads them.
CIM_SUBSCOPES = ("cim.quantize", "cim.tiles", "cim.ste", "cim.mac", "cim.adc")
SCOPES = frozenset((
    "embed", "norm", "attention", "kv_cache", "mlp", "cim_linear", "linear",
    *CIM_SUBSCOPES, "lm_head", "layer_scan", "optimizer", "fabric.requant",
    "fabric.matmul", "fabric.norm", "fabric.attention", "fabric.silu_gate",
    "fabric.residual", "fabric.moe_gate",
))
OTHER = "other"
SPAN_PREFIXES = ("bench.", "serve.", "train.", "data.", "fabric.")

# metric -> (what it reads, the scope or span read, the harness span of one unit)
METRICS = {
    "cim_linear_ms.serve": ("scope", "cim_linear", "bench.serve_batch"),
    "cim_linear_ms.train": ("scope", "cim_linear", "bench.train_step"),
    "attention_ms.serve": ("scope", "attention", "bench.serve_batch"),
    "layer_scan_ms.serve": ("scope", "layer_scan", "bench.serve_batch"),
    "adc_ms.fabric": ("scope", "cim.adc", "bench.fabric_call"),
    "fetch_idle_ms.serve": ("idle", "serve.fetch", "bench.serve_batch"),
    "feed_idle_ms.train": ("idle", "data.batch", "bench.train_step"),
}

_WRAPPER = re.compile(r"^(?:\w+\()+|\)+$")


def scope_of(op_name: str) -> str:
    """The innermost vocabulary scope on an ``op_name`` path, or ``other``.

    >>> scope_of("jit(train_step)/transpose(jvp(layer_scan))/while/body/mlp/cim_linear/dot_general:")
    'cim_linear'
    """
    for part in reversed(op_name.split("/")):
        if _WRAPPER.sub("", part) in SCOPES:
            return _WRAPPER.sub("", part)
    return OTHER


def within(scope: str, root: str) -> bool:
    """Whether ``scope``'s time counts toward a metric over ``root``: the
    scope itself, and under ``cim_linear`` its ``cim.*`` sub-scopes."""
    return scope == root or (root == "cim_linear" and scope in CIM_SUBSCOPES)


# -- the .xplane.pb wire format, as far as event metadata needs it ----------


def _varint(buf, i: int) -> Tuple[int, int]:
    x = shift = 0
    while True:
        b = buf[i]
        i += 1
        x |= (b & 0x7F) << shift
        if b < 0x80:
            return x, i
        shift += 7


def _fields(buf):
    """``(field number, value)`` of one message: an int for varints, a
    memoryview for length-delimited fields; fixed-width fields are skipped."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
            continue
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield field, value


def _entry(buf) -> Tuple[int, memoryview]:
    """A map entry's key and value."""
    f = dict(_fields(buf))
    return f.get(1, 0), f.get(2, memoryview(b""))


def op_names(path: str) -> Dict[str, Dict[str, str]]:
    """Per accelerator plane, each device op's event name -> its ``op_name``
    path (the ``tf_op`` stat, without its trailing ``:type``)."""
    with open(path, "rb") as fh:
        space = memoryview(fh.read())
    out = {}
    for field, plane in _fields(space):
        if field != 1:  # XSpace.planes
            continue
        name, events, stat_names = "", [], {}
        for f, v in _fields(plane):
            if f == 2:  # XPlane.name
                name = bytes(v).decode()
            elif f == 4:  # XPlane.event_metadata
                events.append(v)
            elif f == 5:  # XPlane.stat_metadata
                sid, md = _entry(v)
                stat_names[sid] = next((bytes(x).decode() for g, x in _fields(md) if g == 2), "")
        if not is_device_plane(name):
            continue
        tf_op = next((sid for sid, n in stat_names.items() if n == "tf_op"), None)
        names = {}
        for ev in events:
            _, md = _entry(ev)
            ev_name, path_ = "", ""
            for f, v in _fields(md):
                if f == 2:  # XEventMetadata.name
                    ev_name = bytes(v).decode()
                elif f == 5:  # XEventMetadata.stats
                    stat = dict(_fields(v))
                    if stat.get(1) == tf_op and 5 in stat:  # metadata_id, str_value
                        path_ = bytes(stat[5]).decode().rsplit(":", 1)[0]
            if path_:
                names[ev_name] = path_
        out[name] = names
    return out


# -- loading and reduction ---------------------------------------------------


@dataclasses.dataclass
class ScopedTrace:
    """Device ops per chip, each named by its scope, and host spans."""

    device_ops: List[List[Event]]
    spans: List[Event]
    op_paths: Dict[str, str]  # ``<program>/<op>`` -> op_name path, for ``other``
    op_events: List[List[Event]]  # the same ops named ``<program>/<op>``


def load(log_dir: str) -> ScopedTrace:
    """Read the newest ``.xplane.pb`` under ``log_dir``: op events of every
    accelerator plane, named by scope, and the host spans of the harness and
    the program."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True),
                   key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    names = op_names(paths[-1])
    data = ProfileData.from_file(paths[-1])
    scoped, named, spans, op_paths = [], [], [], {}
    for plane in data.planes:
        if is_device_plane(plane.name):
            lines = {line.name: list(line.events) for line in plane.lines}
            modules = sorted((Event(ev.name.split("(")[0], ev.start_ns, ev.duration_ns)
                              for ev in lines.get(MODULE_LINE, [])), key=lambda e: e.start_ns)
            starts = [m.start_ns for m in modules]
            paths_ = names.get(plane.name, {})
            ops, evs = [], []
            for ev in (ev for line in OP_LINES for ev in lines.get(line, [])):
                path = paths_.get(ev.name, "")
                i = bisect.bisect_right(starts, ev.start_ns) - 1
                program = modules[i].name if i >= 0 and ev.start_ns < modules[i].end_ns else "?"
                op = f"{program}/{ev.name.split(' = ')[0]}"
                op_paths[op] = path
                ops.append(Event(scope_of(path), ev.start_ns, ev.duration_ns))
                evs.append(Event(op, ev.start_ns, ev.duration_ns))
            if ops:
                scoped.append(ops)
                named.append(evs)
        elif plane.name.startswith("/host:"):
            spans += [Event(ev.name, ev.start_ns, ev.duration_ns)
                      for line in plane.lines for ev in line.events
                      if ev.name.startswith(SPAN_PREFIXES)]
    return ScopedTrace(scoped, spans, op_paths, named)


def label_gaps(gap_list: Sequence[Tuple[float, float]], spans: Sequence[Event]) -> List[str]:
    """The innermost (shortest) span around each gap's middle, or
    ``"outside"``, in one sweep over the spans sorted by start."""
    order = sorted(range(len(gap_list)), key=lambda i: gap_list[i][0] + gap_list[i][1])
    by_start = sorted(spans, key=lambda sp: sp.start_ns)
    out, heap, k = [""] * len(gap_list), [], 0
    for i in order:
        t = (gap_list[i][0] + gap_list[i][1]) / 2
        while k < len(by_start) and by_start[k].start_ns <= t:
            sp = by_start[k]
            heapq.heappush(heap, (sp.dur_ns, sp.end_ns, k, sp.name))
            k += 1
        while heap and heap[0][1] < t:  # ended before t, and so before every later gap
            heapq.heappop(heap)
        out[i] = heap[0][3] if heap else "outside"
    return out


def idle_inside(span: Event, busy: Sequence[Tuple[float, float]], ends: Sequence[float]) -> float:
    """The part of ``span`` that no interval of ``busy`` (sorted, disjoint,
    with ``ends`` their ends) covers."""
    lo, hi = span.start_ns, span.end_ns
    covered, i = 0.0, bisect.bisect_right(ends, lo)
    while i < len(busy) and busy[i][0] < hi:
        covered += min(busy[i][1], hi) - max(busy[i][0], lo)
        i += 1
    return (hi - lo) - covered


@dataclasses.dataclass
class ScopeSummary:
    window_s: float
    n_chips: int
    busy_s: float  # union of op intervals, averaged over chips
    scopes: List[Tuple[str, float]]  # device self seconds per scope, summed over chips
    span_idle: Dict[str, float]  # device idle seconds inside each span name, averaged
    idle_gaps: List[Tuple[str, float]]  # longest gaps, labelled by the innermost span
    units: Dict[str, int]  # harness spans in the window, by name
    other_ops: List[Tuple[str, str, float]]  # largest ops with no scope: (op, op_name, s)

    @property
    def other_share(self) -> Optional[float]:
        total = sum(s for _, s in self.scopes)
        return dict(self.scopes).get(OTHER, 0.0) / total if total else None


def _inside(events: Sequence[Event], lo: float, hi: float) -> List[Event]:
    return [Event(ev.name, max(ev.start_ns, lo), min(ev.end_ns, hi) - max(ev.start_ns, lo))
            for ev in events if ev.end_ns > lo and ev.start_ns < hi]


def reduce(trace: ScopedTrace, top: int = 10) -> ScopeSummary:
    lo, hi = window_of(trace)
    spans = _inside(trace.spans, lo, hi)
    program = [sp for sp in spans if not sp.name.startswith("bench.")]
    per_scope: Dict[str, float] = {}
    per_other: Dict[str, float] = {}
    span_idle: Dict[str, float] = {}
    busy, all_gaps = 0.0, []
    for ops, named in zip(trace.device_ops, trace.op_events):
        inside = _inside(ops, lo, hi)
        for (scope, ns), op in zip(self_times(inside), _inside(named, lo, hi)):
            per_scope[scope] = per_scope.get(scope, 0.0) + ns
            if scope == OTHER:
                per_other[op.name] = per_other.get(op.name, 0.0) + ns
        merged = merge((ev.start_ns, ev.end_ns) for ev in inside)
        busy += sum(e - s for s, e in merged)
        all_gaps += gaps(merged, lo, hi)
        ends = [e for _, e in merged]
        for sp in program:
            span_idle[sp.name] = span_idle.get(sp.name, 0.0) + idle_inside(sp, merged, ends)
    n_chips = max(len(trace.device_ops), 1)
    all_gaps.sort(key=lambda g: g[0] - g[1])
    longest = all_gaps[:top]
    units: Dict[str, int] = {}
    for sp in spans:
        if sp.name.startswith("bench.") and sp.name != WINDOW_SPAN:
            units[sp.name] = units.get(sp.name, 0) + 1
    return ScopeSummary(
        window_s=(hi - lo) * 1e-9,
        n_chips=len(trace.device_ops),
        busy_s=busy / n_chips * 1e-9,
        scopes=[(k, v * 1e-9) for k, v in sorted(per_scope.items(), key=lambda kv: -kv[1])],
        span_idle={k: v / n_chips * 1e-9 for k, v in sorted(span_idle.items())},
        idle_gaps=[(name, (e - s) * 1e-9)
                   for name, (s, e) in zip(label_gaps(longest, spans), longest)],
        units=units,
        other_ops=[(op, trace.op_paths.get(op, ""), ns * 1e-9) for op, ns in
                   sorted(per_other.items(), key=lambda kv: -kv[1])[:top]],
    )


def metric(summary: ScopeSummary, name: str) -> Optional[float]:
    """One of :data:`METRICS` in ms per unit, or ``None`` where the trace
    holds no such unit, scope or span."""
    kind, what, unit = METRICS[name]
    n = summary.units.get(unit, 0)
    if not n or not summary.n_chips:
        return None
    if kind == "scope":
        found = [s for scope, s in summary.scopes if within(scope, what)]
        return sum(found) * 1e3 / n if found else None
    if what not in summary.span_idle:
        return None
    return summary.span_idle[what] * 1e3 / n
