"""The program's own host spans in a traced window, as the per-layer readers
take them.

The program opens its spans through ``repro.core.trace.span``; they reach the
profile as host events under their plain names. A batch of the feed is one
``feed.batch`` span (``HostPipeline._make_batch``), and it counts for the
window when it ends inside it. A program without these spans gives no batch,
and every reader here then gives None.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

from bench import tracing

BATCH = "feed.batch"


def named(events: Sequence[Dict], name: str) -> List[Dict]:
    return [e for e in events if e["name"] == name and not tracing.is_device(e)]


def _window_batches(events: Optional[Sequence[Dict]]) -> Optional[Tuple[float, float, List[Dict]]]:
    """(window start, window end, the batches that end inside it), or None."""
    if not events:
        return None
    lo, hi = tracing.window(events)
    batches = [e for e in named(events, BATCH) if lo < e["t0"] + e["dur"] <= hi]
    return (lo, hi, batches) if batches else None


def batch_ms(events: Optional[Sequence[Dict]]) -> Optional[float]:
    """Mean duration of the batches that end inside the window, in ms."""
    got = _window_batches(events)
    if got is None:
        return None
    batches = got[2]
    return sum(e["dur"] for e in batches) / len(batches) / 1e6


def per_batch_ms(events: Optional[Sequence[Dict]], name: str) -> Optional[float]:
    """Time of the ``name`` spans, clipped to the window, over the batches
    that end inside it, in ms."""
    got = _window_batches(events)
    if got is None:
        return None
    lo, hi, batches = got
    ivs = tracing.clip(((e["t0"], e["t0"] + e["dur"]) for e in named(events, name)), lo, hi)
    return sum(b - a for a, b in ivs) / len(batches) / 1e6
