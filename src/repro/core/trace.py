"""Structured tracing: spans, instants, counters, and latency histograms.

The tracer is the observability backbone for the whole stack (PR 9):
``run_job`` phases, ``SplitReader`` fetch attempts and the PR-6/7 failure
ladder, ``ColumnFileReader`` block decode / cache hits, and ``ServeEngine``
admission all emit events here.

Spans have two sinks and one emission path, ``span(name, **args)``:

* **The profiler.**  Every span enters ``jax.profiler.TraceAnnotation``,
  which records only while a profile is being captured, on the profiler's
  own clock, so host spans (the token feed's ``feed.*``, ``split.*``,
  ``storage.read``, ``colfile.verify``, ``decode.*``) line up with the
  device operations on one timeline.  With no capture running an
  annotation costs about a microsecond.
* **The installed tracer.**  When ``live()`` is not None the same span is
  also recorded there as an "X" event with the same args.
  ``Tracer.span`` goes through the same path.

Design constraints of the tracer, in order:

* **Zero cost when disabled.**  The module-level active tracer defaults to
  a disabled singleton; ``live()`` returns ``None`` for it, so hot paths
  capture ``self._tr = trace.live()`` once at construction and guard every
  emission with ``if tr is not None`` — one attribute test per event site,
  no allocation.  A disabled tracer's ``span()`` returns the shared
  ``_NULL_SPAN`` singleton (no object is created per call).  The
  module-level ``span()`` is the exception: it always pays its profiler
  annotation, so it marks per-batch, per-split and per-file work only.
* **Thread-safe and nestable.**  Events append under one lock; span depth
  is tracked per thread so nested spans reconstruct without relying on
  timestamps.
* **Deterministic counter view.**  ``counter_view()`` reduces the event
  stream to a sorted multiset of ``(phase, name, canonical-args) -> count``
  with every timestamp/duration/thread id dropped.  By convention event
  ``args`` carry only schedule-free values (split id, column, block index,
  attempt, host) — all timing lives in the ts/dur fields the view excludes
  — so the view is bit-identical serial vs ``n_workers=4``, extending the
  PR-6/8 determinism contract to traces.  Events whose *occurrence* is
  scheduler-dependent (which worker claimed a split, when a host-death
  trips) are emitted with ``cat="sched"`` and excluded from the view;
  everything else defaults to ``cat="det"`` and is covered by it.
* **Perfetto-loadable export.**  ``export_chrome()`` writes Chrome
  trace-event JSON (``{"traceEvents": [...]}``, "X"/"i"/"C" phases,
  microsecond timestamps) that loads directly in ui.perfetto.dev.
"""
from __future__ import annotations

import json
import threading
import time
from contextlib import contextmanager
from typing import Any, Callable, Dict, Iterator, List, Optional, Tuple

import numpy as np

__all__ = [
    "Tracer",
    "Histogram",
    "active",
    "live",
    "install",
    "span",
    "tracing",
]


def _now_us() -> int:
    return int(time.perf_counter() * 1e6)


class _NullSpan:
    """Shared no-op context manager returned by a disabled tracer."""

    __slots__ = ()

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc: Any) -> bool:
        return False


_NULL_SPAN = _NullSpan()


_annotation: Any = None  # jax.profiler.TraceAnnotation, imported on first use


def _profiled(name: str, args: Optional[dict]) -> Any:
    """The profiler's annotation for a span (entered by the caller)."""
    global _annotation
    if _annotation is None:
        from jax.profiler import TraceAnnotation

        _annotation = TraceAnnotation
    return _annotation(name, **args) if args else _annotation(name)


class _Span:
    """Live span: a profiler annotation, plus an "X" (complete) event in the
    tracer on exit."""

    __slots__ = ("_tr", "_name", "_args", "_cat", "_t0", "_tid", "_depth", "_ann")

    def __init__(self, tr: "Tracer", name: str, args: Optional[dict], cat: str):
        self._tr = tr
        self._name = name
        self._args = args
        self._cat = cat

    def __enter__(self) -> "_Span":
        self._ann = _profiled(self._name, self._args)
        self._ann.__enter__()
        self._tid = threading.get_ident()
        self._depth = self._tr._enter_span()
        self._t0 = _now_us()
        return self

    def __exit__(self, *exc: Any) -> bool:
        dur = _now_us() - self._t0
        self._tr._exit_span()
        self._tr._emit("X", self._name, self._t0, dur, self._tid,
                       self._args, self._cat, self._depth)
        self._ann.__exit__(*exc)
        return False

    def set_metadata(self, **args: Any) -> None:
        """Add args known only inside the span (``TraceAnnotation``'s API)."""
        self._args = dict(self._args or {}, **args)
        self._ann.set_metadata(**args)


class Tracer:
    """Thread-safe event collector.

    Events are stored as ``(ph, name, ts_us, dur_us, tid, args, cat,
    depth)`` tuples; ``ph`` is the Chrome trace-event phase ("X" complete
    span, "i" instant, "C" counter snapshot) and ``cat`` the determinism
    category ("det" by default, "sched" for scheduler-dependent events).
    """

    __slots__ = ("enabled", "_lock", "_events", "_depth")

    def __init__(self, enabled: bool = True):
        self.enabled = enabled
        self._lock = threading.Lock()
        self._events: List[tuple] = []
        self._depth = threading.local()

    # -- per-thread span nesting ---------------------------------------------

    def _enter_span(self) -> int:
        d = getattr(self._depth, "v", 0)
        self._depth.v = d + 1
        return d

    def _exit_span(self) -> None:
        self._depth.v = getattr(self._depth, "v", 1) - 1

    def _emit(self, ph: str, name: str, ts: int, dur: int, tid: int,
              args: Optional[dict], cat: str = "det", depth: int = 0) -> None:
        with self._lock:
            self._events.append((ph, name, ts, dur, tid, args, cat, depth))

    # -- emission API --------------------------------------------------------

    def span(self, name: str, args: Optional[dict] = None, cat: str = "det"):
        """Context manager timing a nested span (no-op singleton if disabled)."""
        if not self.enabled:
            return _NULL_SPAN
        return _Span(self, name, args, cat)

    def instant(self, name: str, args: Optional[dict] = None,
                cat: str = "det") -> None:
        if not self.enabled:
            return
        self._emit("i", name, _now_us(), 0, threading.get_ident(), args, cat)

    def counter(self, name: str, values: Dict[str, Any]) -> None:
        """Counter snapshot; ``values`` must be schedule-free numbers."""
        if not self.enabled:
            return
        self._emit("C", name, _now_us(), 0, threading.get_ident(), dict(values))

    def complete(self, name: str, t0_us: int, t1_us: int,
                 args: Optional[dict] = None, cat: str = "det") -> None:
        """Record an explicit-bounds span (for phases timed by the caller)."""
        if not self.enabled:
            return
        self._emit("X", name, t0_us, max(0, t1_us - t0_us),
                   threading.get_ident(), args, cat)

    # -- inspection ----------------------------------------------------------

    def events(self) -> List[tuple]:
        with self._lock:
            return list(self._events)

    def span_depths(self) -> List[Tuple[int, str, int]]:
        """(tid, name, depth) per complete span — nesting sans timestamps."""
        return [(e[4], e[1], e[7]) for e in self.events() if e[0] == "X"]

    # -- deterministic counter view ------------------------------------------

    def counter_view(self) -> str:
        """Schedule-free reduction: sorted multiset of (ph, name, args)->count.

        Timestamps, durations, and thread ids are dropped; args are
        canonicalised with sorted keys.  Two runs of the same job (serial
        vs concurrent, cache on either side of the PR-8 identity) must
        produce byte-identical views.
        """
        counts: Dict[Tuple[str, str, str], int] = {}
        for ph, name, _ts, _dur, _tid, args, cat, _depth in self.events():
            if cat != "det":
                continue
            key = (ph, name, json.dumps(args, sort_keys=True, default=str))
            counts[key] = counts.get(key, 0) + 1
        rows = [
            {"ph": ph, "name": name, "args": args_json, "count": n}
            for (ph, name, args_json), n in sorted(counts.items())
        ]
        return json.dumps(rows, sort_keys=True)

    # -- Chrome trace-event export -------------------------------------------

    def chrome_events(self) -> List[dict]:
        out = []
        for ph, name, ts, dur, tid, args, cat, _depth in self.events():
            ev: Dict[str, Any] = {
                "name": name, "ph": ph, "cat": cat, "ts": ts, "pid": 1,
                "tid": tid,
            }
            if ph == "X":
                ev["dur"] = dur
            if ph == "i":
                ev["s"] = "t"  # thread-scoped instant
            if args is not None:
                ev["args"] = args
            out.append(ev)
        return out

    def export_chrome(self, path: str) -> None:
        doc = {"traceEvents": self.chrome_events(),
               "displayTimeUnit": "ms"}
        with open(path, "w") as f:
            json.dump(doc, f, default=str)


# -- module-level active tracer ----------------------------------------------

_DISABLED = Tracer(enabled=False)
_active: Tracer = _DISABLED
_active_lock = threading.Lock()


def span(name: str, **args: Any) -> Any:
    """Time ``name`` in both sinks: the profiler's trace, always (recorded
    only while a profile is captured), and the installed tracer when there
    is one.  ``args`` are schedule-free (split id, column, bytes, batch
    ordinal), never a thread id or a time; ``set_metadata(**more)`` on the
    entered span adds args known only inside it."""
    tr = _active
    if tr.enabled:
        return _Span(tr, name, args or None, "det")
    return _profiled(name, args)


def active() -> Tracer:
    """The installed tracer (a disabled singleton by default)."""
    return _active


def live() -> Optional[Tracer]:
    """The installed tracer if enabled, else None — the hot-path capture."""
    tr = _active
    return tr if tr.enabled else None


def install(tracer: Optional[Tracer] = None) -> Tracer:
    """Install (and return) the active tracer; ``None`` installs a fresh one.

    Readers capture the tracer when they are constructed, so install
    before opening splits/engines you want traced.
    """
    global _active
    with _active_lock:
        _active = tracer if tracer is not None else Tracer()
        return _active


@contextmanager
def tracing(tracer: Optional[Tracer] = None) -> Iterator[Tracer]:
    """Scoped install: ``with trace.tracing() as tr: ... tr.export_chrome()``."""
    global _active
    prev = _active
    tr = install(tracer)
    try:
        yield tr
    finally:
        with _active_lock:
            _active = prev


# -- latency histogram --------------------------------------------------------


class Histogram:
    """Small exact-sample histogram shared by serving stats and benchmarks.

    Keeps raw samples (serving runs are bounded); percentiles match
    ``np.percentile``'s linear interpolation so callers that previously
    hand-rolled percentile math get bit-identical numbers.
    """

    __slots__ = ("values",)

    def __init__(self, values: Optional[List[float]] = None):
        self.values: List[float] = list(values) if values else []

    def record(self, v: float) -> None:
        self.values.append(float(v))

    def merge(self, other: "Histogram") -> "Histogram":
        self.values.extend(other.values)
        return self

    @property
    def count(self) -> int:
        return len(self.values)

    def mean(self) -> float:
        return float(np.mean(self.values)) if self.values else 0.0

    def percentile(self, p: float) -> float:
        if not self.values:
            return 0.0
        return float(np.percentile(self.values, p))

    @property
    def p50(self) -> float:
        return self.percentile(50)

    @property
    def p99(self) -> float:
        return self.percentile(99)

    def summary(self, scale: float = 1.0, unit: str = "s") -> str:
        return (f"n={self.count} mean={self.mean() * scale:.3f}{unit} "
                f"p50={self.p50 * scale:.3f}{unit} "
                f"p99={self.p99 * scale:.3f}{unit}")
