"""Low-overhead span tracer with Chrome-trace/Perfetto JSON export.

One process-wide :class:`Tracer` (installed with :func:`enable`, removed
with :func:`disable`) collects ``(name, cat, ts, dur, tid, args)`` events
into a bounded thread-safe ring. Instrumentation sites call the module
API::

    from repro.obs import trace

    with trace.span("storage.read_chunk", "read", chunk=k):
        ...                      # timed while a tracer is installed
    trace.instant("residency.evict", "read", chunk=k)

and pay only a module-attribute load, a ``None`` check and the profiler's
own enabled test when tracing is off — the disabled path allocates nothing
and takes no locks, which is what keeps the instrumented hot loops
(protocol step, ring write, staging) inside the <5% overhead budget pinned
by ``tests/test_obs.py``.

While a ``jax.profiler`` trace is active, every span is also written as a
``jax.profiler.TraceAnnotation(name, **args)``, whether or not a ring is
installed: the program's spans then land on the profiler's host plane, on
the clock its device ops use, and each span name's count and total
duration are summed in-process (:func:`profiled`), so a tool that runs the
profiler can read them without parsing its file. Counters
(:func:`count`) are summed there the same way. The bridge is looked up
only once some other module has imported JAX, so processes that never
touch JAX (service children) stay free of it.

Design notes:

* the ring is a ``collections.deque(maxlen=capacity)`` — appends are
  atomic under the GIL, so producer threads never contend on a lock;
  overflow silently drops the *oldest* events (``dropped`` counts them),
  which is the right bias for "dump the trace at the end of the run".
* timestamps are ``perf_counter`` seconds relative to the tracer's epoch;
  export converts to the microseconds Chrome's ``chrome://tracing`` and
  Perfetto's trace processor expect (``ph: "X"`` complete events).
* spans nest naturally: each ``with`` records one complete event at exit,
  and the viewer reconstructs the stack per thread from containment.
"""

from __future__ import annotations

import json
import sys
import threading
import time
from collections import deque
from pathlib import Path

__all__ = [
    "Tracer",
    "count",
    "disable",
    "enable",
    "get",
    "instant",
    "profiled",
    "span",
    "tracing",
]


class _NullSpan:
    """Shared, reentrant no-op context manager for the disabled path."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **args) -> None:
        pass


_NULL_SPAN = _NullSpan()


class _Span:
    """One live span: records a complete event in the ring (when a tracer
    is installed) and a profiler annotation (when a profiler trace is
    active) around the ``with`` body."""

    __slots__ = ("_tracer", "name", "cat", "args", "_t0", "_annotation")

    def __init__(self, tracer: "Tracer | None", name: str, cat: str, args,
                 annotate: bool = False):
        self._tracer = tracer
        self.name = name
        self.cat = cat
        self.args = args
        self._annotation = (
            _annotation_class(name, **(args or {})) if annotate else None
        )

    def __enter__(self):
        if self._annotation is not None:
            self._annotation.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        if self._annotation is not None:
            self._annotation.__exit__(*exc)
            _add_profiled(self.name, t1 - self._t0)
        if self._tracer is not None:
            self._tracer.complete(
                self.name, self.cat, self._t0, t1 - self._t0, self.args
            )
        return False

    def set(self, **args) -> None:
        """Add args known only inside the span (before it exits)."""
        self.args = {**(self.args or {}), **args}
        if self._annotation is not None:
            self._annotation.set_metadata(**args)


class Tracer:
    """Thread-safe bounded ring of trace events."""

    def __init__(self, capacity: int = 65536):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.capacity = int(capacity)
        self._events: deque = deque(maxlen=self.capacity)
        self._recorded = 0
        self._epoch = time.perf_counter()
        self._tid_lock = threading.Lock()
        self._tids: "dict[int, int]" = {}
        self._tid_names: "dict[int, str]" = {}

    # ------------------------------------------------------------ recording
    def _tid(self) -> int:
        """Small stable id for the calling thread (Chrome tid field)."""
        ident = threading.get_ident()
        tid = self._tids.get(ident)
        if tid is None:
            with self._tid_lock:
                tid = self._tids.setdefault(ident, len(self._tids))
                self._tid_names.setdefault(
                    tid, threading.current_thread().name
                )
        return tid

    def span(self, name: str, cat: str = "", **args) -> _Span:
        return _Span(self, name, cat, args or None, _profiling())

    def complete(
        self, name: str, cat: str, t0: float, dur: float, args=None
    ) -> None:
        """Record a finished span: ``t0`` is absolute ``perf_counter``."""
        self._events.append(
            (name, cat, t0 - self._epoch, dur, self._tid(), args)
        )
        self._recorded += 1

    def instant(self, name: str, cat: str = "", **args) -> None:
        """Record a zero-duration point event."""
        self.complete(name, cat, time.perf_counter(), -1.0, args or None)

    # ------------------------------------------------------------ inspection
    def __len__(self) -> int:
        return len(self._events)

    @property
    def dropped(self) -> int:
        """Events lost to ring overflow (oldest-first)."""
        return self._recorded - len(self._events)

    def events(self) -> "list[tuple]":
        """Snapshot of the ring: ``(name, cat, ts_s, dur_s, tid, args)``
        tuples (``dur_s < 0`` marks an instant event)."""
        return list(self._events)

    def clear(self) -> None:
        self._events.clear()
        self._recorded = 0

    # -------------------------------------------------------------- export
    def to_chrome(self) -> dict:
        """The Chrome Trace Event JSON object (Perfetto-loadable)."""
        trace_events = []
        for tid, tname in sorted(self._tid_names.items()):
            trace_events.append({
                "ph": "M", "pid": 0, "tid": tid, "name": "thread_name",
                "args": {"name": tname},
            })
        for name, cat, ts, dur, tid, args in self._events:
            ev = {
                "name": name,
                "cat": cat or "default",
                "pid": 0,
                "tid": tid,
                "ts": round(ts * 1e6, 3),
            }
            if dur < 0:
                ev["ph"] = "i"
                ev["s"] = "t"
            else:
                ev["ph"] = "X"
                ev["dur"] = round(dur * 1e6, 3)
            if args:
                ev["args"] = {k: _jsonable(v) for k, v in args.items()}
            trace_events.append(ev)
        return {
            "traceEvents": trace_events,
            "displayTimeUnit": "ms",
            "otherData": {"dropped_events": self.dropped},
        }

    def dump(self, path: "str | Path") -> Path:
        """Write the Chrome-trace JSON to ``path`` (open in Perfetto UI or
        ``chrome://tracing``)."""
        path = Path(path)
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(json.dumps(self.to_chrome()))
        return path


def _jsonable(v):
    if isinstance(v, (str, int, float, bool)) or v is None:
        return v
    try:
        return int(v)  # numpy scalars
    except (TypeError, ValueError):
        return str(v)


# ------------------------------------------------------------- module state
_active: "Tracer | None" = None

# ``TraceAnnotation`` and its enabled test, looked up once JAX is loaded.
_annotation_class = None
_annotation_enabled = None


def _profiling() -> bool:
    """True while a ``jax.profiler`` trace is active in this process."""
    enabled = _annotation_enabled
    if enabled is None:
        if "jax" not in sys.modules:
            return False
        enabled = _load_bridge()
    return enabled()


def _load_bridge():
    global _annotation_class, _annotation_enabled
    from jax.profiler import TraceAnnotation

    _annotation_class = TraceAnnotation
    _annotation_enabled = TraceAnnotation.is_enabled
    return _annotation_enabled


# Count and total seconds of each span name that ran while a profiler
# trace was active (its annotation written).
_profiled: "dict[str, tuple[int, float]]" = {}
_profiled_lock = threading.Lock()


def _add_profiled(name: str, dur: float) -> None:
    with _profiled_lock:
        n, total = _profiled.get(name, (0, 0.0))
        _profiled[name] = (n + 1, total + dur)


def count(name: str, value: float) -> None:
    """Add ``value`` to the counter ``name`` if a ``jax.profiler`` trace is
    active: :func:`profiled` then holds ``(times counted, summed value)``
    under the counter's name, as it sums a span's seconds."""
    if _profiling():
        _add_profiled(name, value)


def profiled() -> "dict[str, tuple[int, float]]":
    """``{span name: (count, seconds)}`` of the spans begun while a
    ``jax.profiler`` trace was active in this process, and ``{counter name:
    (times counted, summed value)}`` of the counters (:func:`count`)."""
    with _profiled_lock:
        return dict(_profiled)


def enable(capacity: int = 65536) -> Tracer:
    """Install (and return) the process-wide tracer. Idempotent-ish: a
    second ``enable`` replaces the tracer (the old one keeps its events)."""
    global _active
    _active = Tracer(capacity=capacity)
    return _active


def disable() -> "Tracer | None":
    """Remove the process-wide tracer; returns it (events intact)."""
    global _active
    t, _active = _active, None
    return t


def get() -> "Tracer | None":
    """The installed tracer, or None when tracing is off."""
    return _active


def span(name: str, cat: str = "", **args):
    """Module-level span: a real span when a tracer is installed or a
    profiler trace is active, a shared no-op context manager otherwise
    (the hot-path fast exit)."""
    t = _active
    annotate = _profiling()
    if t is None and not annotate:
        return _NULL_SPAN
    return _Span(t, name, cat, args or None, annotate)


def instant(name: str, cat: str = "", **args) -> None:
    """A zero-duration point event, in the ring only."""
    t = _active
    if t is not None:
        t.instant(name, cat, **args)


class tracing:
    """``with tracing() as t:`` — enable for a scope, restore on exit."""

    def __init__(self, capacity: int = 65536):
        self.capacity = capacity
        self.tracer: "Tracer | None" = None

    def __enter__(self) -> Tracer:
        global _active
        self._prev = _active
        self.tracer = Tracer(capacity=self.capacity)
        _active = self.tracer
        return self.tracer

    def __exit__(self, *exc):
        global _active
        _active = self._prev
        return False
