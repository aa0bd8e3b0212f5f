"""The reduction from a profiler trace to busy time, kernel time and idle
gaps: on hand-made events, and on a fixture cut from a chip trace."""
import gzip
import json
import os

import numpy as np
import pytest

import bench_tiny  # noqa: F401
from bench import tracing

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures", "olmoe_feed_trace.json.gz")
DEV = "/device:TPU:0"


def ev(name, t0, dur, plane=DEV, line=tracing.OPS_LINE, **kw):
    return dict(plane=plane, line=line, name=name, t0=float(t0), dur=float(dur), **kw)


def test_union_clip_and_gaps():
    assert tracing.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert tracing.clip([(0, 3), (5, 8), (9, 10)], 1, 6) == [(1, 3), (5, 6)]
    ops = [ev("a", 10, 10), ev("b", 15, 10), ev("c", 40, 5)]
    assert tracing.gaps(ops, 0, 50, DEV) == [(0, 10), (25, 40), (45, 50)]
    assert tracing.busy_ns(ops, 0, 50, DEV) == 20


def test_busy_is_averaged_over_devices_and_kept_in_the_window():
    events = [ev(tracing.WINDOW_SPAN, 100, 1000, plane="/host:CPU", line="python"),
              ev("k", 0, 200), ev("k", 500, 100),
              ev("k", 100, 1000, plane="/device:TPU:1")]
    busy, window = tracing.busy_seconds(events, 2)
    assert window == pytest.approx(1000e-9)
    assert busy == pytest.approx((200e-9 + 1000e-9) / 2)


def test_idle_gaps_go_to_the_host_event_that_overlaps_most():
    host = dict(plane="/host:CPU", line="python")
    events = [ev(tracing.WINDOW_SPAN, 0, 100, **host),
              ev("op", 10, 10), ev("op", 60, 10),
              ev("tokens.open_split", 20, 30, **host),   # 30 of the gap 20-60
              ev("bench.next_batch", 15, 50, **host),    # all of it, but longer
              ev("bench.place_batch", 90, 5, **host),    # a sixth of 70-100
              ev("pjit", 72, 3, **host)]                  # a tenth of it
    got = dict(map(tuple, tracing.idle_by_host(events)))
    assert got == {"tokens.open_split": pytest.approx(40e-9),
                   "bench.place_batch": pytest.approx(30e-9),
                   "(no host event)": pytest.approx(10e-9)}


def test_kernel_ops_by_module_and_name():
    events = [ev(tracing.WINDOW_SPAN, 0, 100, plane="/host:CPU", line="python"),
              ev("_scalar_kernel", 10, 5, hlo_module="jit_dict_decode(3)"),
              ev("fusion.1", 20, 5, hlo_module="jit_dict_decode(3)"),
              ev("_kernel", 30, 5, hlo_module="jit_bitunpack(9)"),
              ev("_scalar_kernel", 95, 10, hlo_module="jit_dict_decode(3)")]  # past the end
    assert [e["t0"] for e in tracing.kernel_ops(events, "dict_decode", "_scalar_kernel")] == [10]
    assert len(tracing.kernel_ops(events, "bitunpack", "_kernel")) == 1
    top = tracing.top_ops(events)
    assert top[0] == ["jit_dict_decode:_scalar_kernel", pytest.approx(10e-9)]


@pytest.fixture(scope="module")
def chip_events():
    with gzip.open(FIXTURE, "rt") as f:
        return json.load(f)


def test_chip_trace_busy_matches_a_timeline(chip_events):
    """Busy time by interval union agrees with a 1 us occupancy grid."""
    lo, hi = tracing.window(chip_events)
    busy, window = tracing.busy_seconds(chip_events, 1)
    grid = np.zeros(int((hi - lo) / 1e3) + 1, bool)
    for e in tracing.device_ops(chip_events):
        a, b = max(e["t0"], lo), min(e["t0"] + e["dur"], hi)
        if b > a:
            grid[int((a - lo) / 1e3):int(np.ceil((b - lo) / 1e3))] = True
    assert window == pytest.approx((hi - lo) / 1e9)
    assert 0 < busy < window
    assert busy == pytest.approx(grid.sum() * 1e-6, rel=0.05)


def test_chip_trace_kernels_and_breakdown(chip_events):
    dd = tracing.kernel_ops(chip_events, "dict_decode", r"custom-call|_scalar_kernel")
    bu = tracing.kernel_ops(chip_events, "bitunpack", r"custom-call|_kernel")
    assert dd and bu
    # one bitunpack and one dict_decode kernel per batch
    assert abs(len(dd) - len(bu)) <= 1
    top = tracing.top_ops(chip_events)
    assert len(top) <= 10 and top == sorted(top, key=lambda kv: -kv[1])
    gaps = tracing.idle_by_host(chip_events, n=1000)
    busy, window = tracing.busy_seconds(chip_events, 1)
    assert sum(s for _, s in gaps) == pytest.approx(window - busy, rel=1e-6)
