"""The per-chip feed's cell at a tiny size, run through ``run_cell`` on four
CPU devices.

    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \
        python3 bench/tests/feed_chips_cell.py <scratch dir>

The checkout is ``bench_tiny``'s, with a four-chip cell that is
``olmoe-tokens.feed-4chip`` over a tiny copy of its configuration. The cell runs
untraced, traced, and untraced with a stand-in in which each chip decodes
chip 0's rows instead of its own (the exchange between chips left out), all
in this one process. The last line of standard output is a JSON object with
the three results and the program's spans of the traced window.
"""
from __future__ import annotations

import json
import os
import sys
from unittest import mock

import numpy as np

import bench_tiny

CELL = "olmoe-tokens.feed-4chip"
TINY_CELL = "tiny.feed-4chip"
TINY_CONFIG = "tiny-4chip"


def make_chips_root(tmp: str) -> str:
    root = bench_tiny.make_root(tmp)
    with open(os.path.join(bench_tiny.ROOT, "BENCHMARK.json")) as f:
        real = json.load(f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    w = next(w for w in real["workloads"] if w["name"] == CELL)
    c = next(c for c in real["configs"] if c["name"] == w["config"])
    with open(os.path.join(bench_tiny.ROOT, c["file"])) as f:
        cfg = dict(json.load(f), name=TINY_CONFIG, **bench_tiny.TINY)
    with open(os.path.join(root, "bench", "configs", TINY_CONFIG + ".json"), "w") as f:
        json.dump(cfg, f)
    bench["configs"].append(dict(c, name=TINY_CONFIG,
                                 file=f"bench/configs/{TINY_CONFIG}.json"))
    bench["workloads"] = [dict(w, name=TINY_CELL, config=TINY_CONFIG)]
    for kind in ("end_to_end", "per_layer"):
        listed = {m["name"] for m in real[kind] if CELL in m.get("workloads", [CELL])}
        for m in bench[kind]:
            if "workloads" in m:
                m["workloads"] = [TINY_CELL] if m["name"] in listed else []
    with open(path, "w") as f:
        json.dump(bench, f)
    return root


def chip0_rows(decode):
    """``chips_decode_batch`` with every chip given chip 0's rows."""
    def standin(parts, rows, sharding):
        r = len(rows) // len(sharding.device_set)
        return decode(parts, np.tile(rows[:r], (len(rows) // r, 1)), sharding)
    return standin


def main(tmp: str) -> None:
    from bench import tracing
    from repro.data import pipeline

    spans = []
    load_events = tracing.load_events

    def kept(log_dir):
        events = load_events(log_dir)
        spans.extend({"name": e["name"], "bytes": e.get("bytes")} for e in events
                     if not tracing.is_device(e) and e["name"].startswith(("decode.", "bench.")))
        return events

    tracing.load_events = kept
    root = make_chips_root(tmp)
    out = {"untraced": bench_tiny.run(root, TINY_CELL),
           "traced": bench_tiny.run(root, TINY_CELL, trace=True), "spans": spans}
    with mock.patch.object(pipeline, "chips_decode_batch",
                           chip0_rows(pipeline.chips_decode_batch)):
        out["chip0_rows"] = bench_tiny.run(root, TINY_CELL)
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main(sys.argv[1])
