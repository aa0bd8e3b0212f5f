"""What every traffic loop shares: the measured window, host spans around
what the loop itself does, and the program's counters.

A loop (``bench/loops/<loop>.py``) warms up every shape its window uses,
opens the window, drives the timed path until ``seconds`` have passed, and
closes it; the window traces itself when the run is traced.
"""
from __future__ import annotations

import contextlib
import time
from typing import Callable, Dict, Optional

import numpy as np

from bench import tracing


class Spans:
    """Host spans in the profiler's trace; nothing when the run is not
    traced."""

    def __init__(self, on: bool):
        self.on = on

    def __call__(self, name: str, **args):
        import jax

        return (jax.profiler.TraceAnnotation(name, **args) if self.on
                else contextlib.nullcontext())


def counters(stats) -> Dict[str, int]:
    """The ``ScanStats`` counters the per-layer readers take."""
    return {k: getattr(stats, k) for k in ("bytes_io", "bytes_touched", "bytes_decoded",
                                           "files_opened", "cache_hits", "cache_misses",
                                           "cache_evictions")}


def reservoir_slot(rng: np.random.Generator, seen: int, k: int) -> Optional[int]:
    """Slot of a reservoir of ``k`` that item ``seen`` (0-based) takes, or None."""
    if seen < k:
        return seen
    j = int(rng.integers(seen + 1))
    return j if j < k else None


class Window:
    """The measured window: its start, its length, and the trace around it
    when the run is traced. ``on_open`` runs just before it starts."""

    def __init__(self, seconds: float, trace_dir: Optional[str]):
        self.seconds = seconds
        self.trace_dir = trace_dir
        self.spans = Spans(trace_dir is not None)
        self._span = None
        self.on_open: Optional[Callable[[], None]] = None

    def open(self) -> float:
        if self.on_open is not None:
            self.on_open()
        if self.trace_dir is not None:
            tracing.start(self.trace_dir)
            self._span = self.spans(tracing.WINDOW_SPAN)
            self._span.__enter__()
        self.t0 = time.perf_counter()
        return self.t0

    def close(self) -> None:
        if self._span is not None:
            self._span.__exit__(None, None, None)
            tracing.stop()
            self._span = None
