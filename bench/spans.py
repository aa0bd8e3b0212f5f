"""The program's own host spans in a traced window, as the per-layer readers
take them.

The program opens its spans through ``repro.core.trace.span``; they reach the
profile as host events under their plain names. A batch of the feed is one
``feed.batch`` span (``HostPipeline._make_batch``), and it counts for the
window when it ends inside it, and holds the loop's ``batch_tokens`` tokens.
A program without these spans gives no batch, and every reader here then
gives None.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

from bench import tracing

BATCH = "feed.batch"


def named(events: Sequence[Dict], name: str) -> List[Dict]:
    return [e for e in events if e["name"] == name and not tracing.is_device(e)]


def window_batches(events: Optional[Sequence[Dict]]) -> Optional[Tuple[float, float, List[Dict]]]:
    """(window start, window end, the batches that end inside it), or None."""
    if not events:
        return None
    lo, hi = tracing.window(events)
    batches = [e for e in named(events, BATCH) if lo < e["t0"] + e["dur"] <= hi]
    return (lo, hi, batches) if batches else None


def batch_ms(events: Optional[Sequence[Dict]]) -> Optional[float]:
    """Mean duration of the batches that end inside the window, in ms."""
    got = window_batches(events)
    if got is None:
        return None
    batches = got[2]
    return sum(e["dur"] for e in batches) / len(batches) / 1e6


def per_batch_ms(events: Optional[Sequence[Dict]], name: str) -> Optional[float]:
    """Time of the ``name`` spans, clipped to the window, over the batches
    that end inside it, in ms."""
    got = window_batches(events)
    if got is None:
        return None
    lo, hi, batches = got
    ivs = tracing.clip(((e["t0"], e["t0"] + e["dur"]) for e in named(events, name)), lo, hi)
    return sum(b - a for a, b in ivs) / len(batches) / 1e6


def bytes_per_token(events: Optional[Sequence[Dict]], names: Sequence[str],
                    batch_tokens: int) -> Optional[float]:
    """The ``bytes`` args of the ``names`` spans that end inside the window,
    over the tokens of the batches that end inside it; None where no such
    span carries ``bytes``."""
    got = window_batches(events)
    if got is None:
        return None
    lo, hi, batches = got
    ends_in = [e for n in names for e in named(events, n)
               if "bytes" in e and lo < e["t0"] + e["dur"] <= hi]
    if not ends_in:
        return None
    return sum(e["bytes"] for e in ends_in) / (len(batches) * batch_tokens)
