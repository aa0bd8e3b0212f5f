"""A checkout of the benchmark at a tiny size, for the tests on the CPU.

``make_root(tmp)`` writes a ``BENCHMARK.json`` whose cell is the real cells'
traffic, loop and metric files over a tiny copy of ``olmoe-tokens``, so a run
goes through every layer of the real path in seconds.
"""
from __future__ import annotations

import json
import os
import shutil
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY = {"vocab_size": 50304, "seq_len": 256, "split_records": 64, "batch_per_host": 8,
        "n_splits": 3}
FEED = "tiny.feed-stream"


def make_root(tmp: str) -> str:
    root = os.path.join(tmp, "checkout")
    os.makedirs(os.path.join(root, "bench", "configs"))
    for d in ("metrics", "traffic", "loops"):
        shutil.copytree(os.path.join(BENCH, d), os.path.join(root, "bench", d))
    with open(os.path.join(BENCH, "configs", "olmoe-tokens.json")) as f:
        cfg = dict(json.load(f), name="tiny", **TINY)
    with open(os.path.join(root, "bench", "configs", "tiny.json"), "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"] = [dict(bench["configs"][0], name="tiny", file="bench/configs/tiny.json")]
    bench["workloads"] = [
        {"name": FEED, "config": "tiny", "traffic": "feed-stream", "chips": 1, "why": "test"}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [FEED] if "olmoe-tokens.feed-stream" in m["workloads"] else []
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root


def run(root: str, workload: str = FEED, seed: int = 2**31 + 7, seconds: float = 1.0,
        trace: bool = False, **kw):
    from bench import run as run_mod

    return run_mod.run_cell(workload, seed, seconds, trace, require_chip=False, root=root,
                            compile_cache=False, **kw)
