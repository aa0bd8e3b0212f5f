"""The control: the plain reference put in the program's place, with token
ids carried as int16, the narrower integer a later change could be tempted to
ship (it halves the bytes of a batch). Ids of 32,768 and above wrap, which
breaks the configuration's guarantee that every id comes back bit-exact, so
the comparison has to find the run not correct.

    python3 bench/control.py --workload <name> --seeds <n> [<n> ...] [--seconds <s>]

Each seed is one run of the cell in this one process, through the harness and
the cell's own loop, with the control in the timed path; it prints one line of
the numbers compared for each seed. The benchmark's own runs never run it.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Dict

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import corpus as corpus_mod  # noqa: E402
from bench import spec  # noqa: E402
from bench.reference import expected_batch  # noqa: E402


def narrow(x: np.ndarray) -> np.ndarray:
    return x.astype(np.int16).astype(np.int32)


class _Counters:
    def __getattr__(self, name):
        return 0


class ReferenceFeed:
    """Batches of the reference corpus, split after split, with narrowed ids."""

    def __init__(self, cfg: Dict, seed: int):
        self.splits = corpus_mod.reference_corpus(cfg, seed)
        self.b = cfg["batch_per_host"]
        self.stats = _Counters()

    def __iter__(self):
        while True:
            for arr in self.splits:
                for i in range(0, arr.shape[0] - self.b + 1, self.b):
                    yield expected_batch(narrow(arr[i:i + self.b]))

    def stop(self) -> None:
        pass


def control_loop(root: str = ROOT):
    """The ``feed`` loop's ``run`` with the control in the pipeline's place."""
    feed = spec.load_loop("feed", root)
    return lambda cfg, traffic, corpus, seed, win, devices: feed.run(
        cfg, traffic, corpus, seed, win, devices,
        make_pipeline=lambda c, cf, t, s: ReferenceFeed(cf, s))


def main() -> int:
    ap = argparse.ArgumentParser(description="Run the control of one cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args()
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from bench import run

    if spec.find_cell(args.workload).traffic["loop"] != "feed":
        raise spec.SpecError("the control stands in for a feed")
    for seed in args.seeds:
        out = run.run_cell(args.workload, seed, args.seconds, False, loop=control_loop())
        print(json.dumps({"seed": seed, "correct": out["correct"], "checks": out["checks"],
                          "device": out["device"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
