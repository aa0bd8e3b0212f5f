"""The profiler trace of a window, reduced to what the per-layer metrics read.

A trace is read into plain event records (``load_events``); everything after
that works on those records alone, so the reduction is checked on a small
fixture cut from a chip trace (``tests/fixtures``). Times are nanoseconds on
the trace's clock. The window is the host span ``WINDOW_SPAN`` that the
harness opens around the traced part of its window.

- Device operations are the events of the ``XLA Ops`` line of each device
  plane (``/device:TPU:<n>``). Each is named by its HLO text
  (``%dict_decode.1 = s32[512,128]... custom-call(...)``) and belongs to
  the jitted module (``jit_dict_decode(<id>)``) of the ``XLA Modules``
  event that encloses it on the same plane.
- Busy time is the union of their intervals inside the window, averaged over
  the devices used; idle is the rest of the window.
- A kernel's time is the sum of the durations of its operations, found by
  the jitted function's module name (``jit_<name>``) and the operation's
  name or category, as its reader states them, on every device plane.
- A host span keeps its ``bytes`` arg, where it has one.
- Each idle gap is put down to the shortest host event that covers half of
  it or more (the innermost thing the host was doing), else to the one that
  overlaps it most, and gaps are summed by that event's name.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

WINDOW_SPAN = "bench.window"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HLO_NAME = re.compile(r"^%?(?P<name>\S+) = .*?\s(?P<op>[a-z][\w-]*)\(")
_STATS = ("hlo_module", "hlo_category", "bytes")  # ``bytes``: an arg of a host span


def start(log_dir: str) -> None:
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0  # Python call tracing would slow the host
    opts.host_tracer_level = 2
    jax.profiler.start_trace(log_dir, profiler_options=opts)


def stop() -> None:
    import jax

    jax.profiler.stop_trace()


def load_events(log_dir: str) -> List[Dict]:
    """Every event of the newest ``.xplane.pb`` under ``log_dir``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True),
                   key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    data = ProfileData.from_file(paths[-1])
    out = []
    for plane in data.planes:
        for line in plane.lines:
            for ev in line.events:
                rec = {"plane": plane.name, "line": line.name, "name": ev.name,
                       "t0": float(ev.start_ns), "dur": float(ev.duration_ns)}
                for k, v in ev.stats:
                    if k in _STATS:
                        rec[k] = v if isinstance(v, (int, float)) else str(v)
                out.append(rec)
    return out


def window(events: Iterable[Dict]) -> Tuple[float, float]:
    spans = [e for e in events if e["name"] == WINDOW_SPAN and not is_device(e)]
    if not spans:
        raise ValueError(f"the trace holds no {WINDOW_SPAN!r} span")
    w = max(spans, key=lambda e: e["dur"])
    return w["t0"], w["t0"] + w["dur"]


def is_device(e: Dict) -> bool:
    return DEVICE_PLANE.match(e["plane"]) is not None


def device_ops(events: Iterable[Dict]) -> List[Dict]:
    """The device operations, each with the ``module`` that encloses it."""
    events = list(events)
    mods: Dict[str, List[Dict]] = {}
    for e in events:
        if is_device(e) and e["line"] == MODULES_LINE:
            mods.setdefault(e["plane"], []).append(e)
    starts = {}
    for p, ms in mods.items():
        ms.sort(key=lambda e: e["t0"])
        starts[p] = [m["t0"] for m in ms]
    out = []
    for e in events:
        if not (is_device(e) and e["line"] == OPS_LINE):
            continue
        mod = e.get("hlo_module", "")
        if not mod and e["plane"] in mods:
            i = bisect.bisect_right(starts[e["plane"]], e["t0"]) - 1
            if i >= 0:
                m = mods[e["plane"]][i]
                if e["t0"] < m["t0"] + m["dur"]:  # begins inside it
                    mod = m["name"]
        out.append(dict(e, module=re.sub(r"\(\d+\)$", "", str(mod))))
    return out


def devices(ops: Iterable[Dict]) -> List[str]:
    return sorted({e["plane"] for e in ops})


def clip(ivs: Iterable[Tuple[float, float]], lo: float, hi: float) -> List[Tuple[float, float]]:
    return [(max(a, lo), min(b, hi)) for a, b in ivs if b > lo and a < hi]


def union(ivs: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    merged: List[List[float]] = []
    for a, b in sorted(ivs):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def busy_ns(ops: Sequence[Dict], lo: float, hi: float, plane: str) -> float:
    ivs = union(clip(((e["t0"], e["t0"] + e["dur"]) for e in ops if e["plane"] == plane),
                     lo, hi))
    return sum(b - a for a, b in ivs)


def busy_seconds(events: Sequence[Dict], n_devices: int) -> Tuple[float, float]:
    """(busy seconds averaged over ``n_devices``, window seconds)."""
    lo, hi = window(events)
    ops = device_ops(events)
    planes = devices(ops)[:n_devices]
    total = sum(busy_ns(ops, lo, hi, p) for p in planes)
    return total / max(n_devices, 1) / 1e9, (hi - lo) / 1e9


def gaps(ops: Sequence[Dict], lo: float, hi: float, plane: str) -> List[Tuple[float, float]]:
    ivs = union(clip(((e["t0"], e["t0"] + e["dur"]) for e in ops if e["plane"] == plane),
                     lo, hi))
    out, t = [], lo
    for a, b in ivs:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


def op_label(e: Dict) -> str:
    """``<module>:<instruction> <opcode>`` of a device operation."""
    m = HLO_NAME.match(e["name"])
    name = f"{m['name']} {m['op']}" if m else e["name"]
    return f"{e['module']}:{name}" if e.get("module") else name


def top_ops(events: Sequence[Dict], n: int = 10) -> List[List]:
    """The device operations that took most time in the window, summed by
    module and name, over every device plane."""
    lo, hi = window(events)
    tot: Dict[str, float] = {}
    for e in device_ops(events):
        for a, b in clip([(e["t0"], e["t0"] + e["dur"])], lo, hi):
            tot[op_label(e)] = tot.get(op_label(e), 0.0) + (b - a)
    top = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / 1e9] for k, v in top]


def idle_by_host(events: Sequence[Dict], n: int = 10) -> List[List]:
    """Idle seconds of the first device, summed by what the host was doing
    in each gap (see the module's docstring)."""
    lo, hi = window(events)
    ops = device_ops(events)
    planes = devices(ops)
    if not planes:
        return []
    host = [e for e in events if not is_device(e) and e["dur"] > 0
            and e["name"] != WINDOW_SPAN and e["t0"] < hi and e["t0"] + e["dur"] > lo]
    host.sort(key=lambda e: e["t0"])
    tot: Dict[str, float] = {}
    active: List[Dict] = []  # host events begun before the gap's end
    i = 0
    for a, b in gaps(ops, lo, hi, planes[0]):
        while i < len(host) and host[i]["t0"] < b:
            active.append(host[i])
            i += 1
        active = [e for e in active if e["t0"] + e["dur"] > a]
        over = [(min(b, e["t0"] + e["dur"]) - max(a, e["t0"]), e) for e in active]
        over = [(ov, e) for ov, e in over if ov > 0]
        half = [e for ov, e in over if ov >= 0.5 * (b - a)]
        best: Optional[Dict] = (min(half, key=lambda e: e["dur"]) if half else
                                max(over, key=lambda oe: oe[0])[1] if over else None)
        name = best["name"] if best is not None else "(no host event)"
        tot[name] = tot.get(name, 0.0) + (b - a)
    top = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / 1e9] for k, v in top]


def kernel_ops(events: Sequence[Dict], module: str, op: str) -> List[Dict]:
    """Device operations in the window of the jitted function ``module``
    (``jit_<module>``) whose name or category matches the regex ``op``."""
    lo, hi = window(events)
    want = re.compile(op)
    out = []
    for e in device_ops(events):
        if e["module"] != f"jit_{module}":
            continue
        if not (want.search(e["name"]) or want.search(str(e.get("hlo_category", "")))):
            continue
        if e["t0"] >= lo and e["t0"] + e["dur"] <= hi:
            out.append(e)
    return out
