"""Lightweight span/tracer API for the fabric serving stack.

Tracing is *contextvar-scoped*, exactly like
``launch.shardings.record_fallbacks``: callers that open a
:func:`tracing` block get every span and event produced inside it
(nesting composes — inner blocks also feed enclosing tracers), and code
outside any block pays near-zero cost — :func:`span` returns one shared
no-op singleton and :func:`event` returns before building a record.

Instrumentation is strictly host-side: spans wall-clock Python-level
work and never touch traced values, so enabling tracing provably cannot
perturb a compiled program — ``GraphProgram.collective_counts`` and the
fused logits are asserted bit-identical with tracing on/off in
``tests/test_obs.py``. Under an active tracer each span also opens a
``jax.profiler.TraceAnnotation`` of its name, so a ``jax.profiler`` trace
shows the program's spans on the device ops' clock.
"""

from __future__ import annotations

import contextlib
import contextvars
import itertools
import time
from typing import Iterator, List, Optional

import jax

from repro.obs.sinks import JsonlSink

__all__ = ["Tracer", "tracing", "span", "event", "enabled"]

# Stack of active tracers (innermost last). A ContextVar keeps concurrent
# threads / async serving tasks from seeing each other's spans.
_TRACERS: contextvars.ContextVar[tuple] = contextvars.ContextVar(
    "obs_tracers", default=()
)
# The id of the innermost open span: each record's ``parent``.
_OPEN: contextvars.ContextVar[Optional[int]] = contextvars.ContextVar(
    "obs_open_span", default=None
)
_IDS = itertools.count()


class Tracer:
    """Collects finished spans and point events for one :func:`tracing` block.

    ``spans`` / ``events`` are lists of plain dicts (JSON-ready); when the
    block was opened with ``jsonl=path`` every record is also appended to
    that file as one JSON line the moment it is produced.

    Example::

        >>> from repro.obs import tracing, span
        >>> with tracing() as tr:
        ...     with span("demo", layer=0):
        ...         pass
        >>> tr.spans[0]["name"], tr.spans[0]["attrs"]["layer"]
        ('demo', 0)
    """

    def __init__(self, sink: Optional[JsonlSink] = None):
        self.spans: List[dict] = []
        self.events: List[dict] = []
        self._sink = sink

    def _emit(self, record: dict) -> None:
        (self.spans if record["kind"] == "span" else self.events).append(record)
        if self._sink is not None:
            self._sink.write(record)


@contextlib.contextmanager
def tracing(jsonl=None) -> Iterator[Tracer]:
    """Scope span/event recording to a block.

    Every :func:`span` / :func:`event` inside the block lands on the
    yielded :class:`Tracer` (and on any enclosing tracer — nesting
    composes). ``jsonl`` optionally streams each record to a JSONL file
    (:class:`repro.obs.JsonlSink`). Outside any block, instrumentation
    is a no-op.

    Example::

        >>> from repro.obs import tracing, event
        >>> with tracing() as tr:
        ...     event("request.done", tokens=32)
        >>> tr.events[0]["name"]
        'request.done'
    """
    sink = JsonlSink(jsonl) if jsonl is not None else None
    tr = Tracer(sink)
    token = _TRACERS.set(_TRACERS.get() + (tr,))
    try:
        yield tr
    finally:
        _TRACERS.reset(token)
        if sink is not None:
            sink.close()


def enabled() -> bool:
    """Whether any :func:`tracing` block is active in this context.

    Example::

        >>> from repro.obs import enabled, tracing
        >>> enabled()
        False
        >>> with tracing():
        ...     enabled()
        True
    """
    return bool(_TRACERS.get())


class _NullSpan:
    """The shared disabled-path span: every method is a no-op."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **attrs) -> None:
        pass


_NULL_SPAN = _NullSpan()


class _Span:
    __slots__ = ("name", "attrs", "id", "parent", "_tracers", "_t0", "_token", "_mark")

    def __init__(self, name: str, attrs: dict, tracers: tuple):
        self.name = name
        self.attrs = attrs
        self.id = next(_IDS)
        self._tracers = tracers

    def __enter__(self):
        self.parent = _OPEN.get()
        self._token = _OPEN.set(self.id)
        self._mark = jax.profiler.TraceAnnotation(self.name)
        self._mark.__enter__()
        self._t0 = time.perf_counter()
        return self

    def set(self, **attrs) -> None:
        """Attach attributes discovered mid-span (e.g. a resolved backend)."""
        self.attrs.update(attrs)

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        self._mark.__exit__(*exc)
        _OPEN.reset(self._token)
        record = {
            "kind": "span",
            "name": self.name,
            "id": self.id,
            "parent": self.parent,
            "t_s": self._t0,
            "duration_s": t1 - self._t0,
            "attrs": self.attrs,
        }
        for tr in self._tracers:
            tr._emit(record)
        return False


def span(name: str, **attrs):
    """A wall-clock span context manager.

    With no active tracer this returns one shared no-op singleton (zero
    allocation, the documented disabled-path cost). With tracers active
    it opens a ``jax.profiler.TraceAnnotation`` of ``name`` for its
    duration and records ``{name, id, parent, t_s, duration_s, attrs}``
    to every one of them on exit: ``id`` is unique in the process and
    ``parent`` is the id of the innermost span open around it (``None``
    at the top).

    Example::

        >>> from repro.obs import span, tracing
        >>> with tracing() as tr:
        ...     with span("fabric.execute", layer="q_proj") as sp:
        ...         sp.set(tiles=4)
        >>> tr.spans[0]["attrs"]
        {'layer': 'q_proj', 'tiles': 4}
        >>> with tracing() as tr:
        ...     with span("serve.decode"):
        ...         with span("serve.decode_step"):
        ...             pass
        >>> inner, outer = tr.spans
        >>> inner["parent"] == outer["id"], outer["parent"]
        (True, None)
    """
    tracers = _TRACERS.get()
    if not tracers:
        return _NULL_SPAN
    return _Span(name, attrs, tracers)


def event(name: str, **attrs) -> None:
    """Record a point-in-time event (no duration) to every active tracer.

    No-op without an active :func:`tracing` block. The fabric layers use
    this for structured fallback records (``fabric.fallback`` events with
    canonical ``reason`` strings — :mod:`repro.obs.fallback`).

    Example::

        >>> from repro.obs import event, tracing
        >>> with tracing() as tr:
        ...     event("fabric.fallback", reason="ragged_batch")
        >>> tr.events[0]["attrs"]["reason"]
        'ragged_batch'
    """
    tracers = _TRACERS.get()
    if not tracers:
        return
    record = {
        "kind": "event",
        "name": name,
        "parent": _OPEN.get(),
        "t_s": time.perf_counter(),
        "attrs": attrs,
    }
    for tr in tracers:
        tr._emit(record)

