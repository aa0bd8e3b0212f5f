"""A tiny four-chip cell, run through ``run_cell`` on four CPU devices.

    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \
        python3 bench/tests/four_chip_cell.py <scratch dir>

The checkout is ``bench_tiny``'s with its one workload on 4 chips. The cell
runs untraced and then traced, in this one process, and the last line of
standard output is a JSON object with both results and the device counts
that each traced run's busy time was averaged over.
"""
from __future__ import annotations

import json
import os
import sys

import bench_tiny


def make_four_chip_root(tmp: str) -> str:
    root = bench_tiny.make_root(tmp)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    for w in bench["workloads"]:
        w["chips"] = 4
    with open(path, "w") as f:
        json.dump(bench, f)
    return root


def main(tmp: str) -> None:
    from bench import tracing

    averaged_over = []
    busy_seconds = tracing.busy_seconds

    def counted(events, n_devices):
        averaged_over.append(n_devices)
        return busy_seconds(events, n_devices)

    tracing.busy_seconds = counted
    root = make_four_chip_root(tmp)
    out = {"untraced": bench_tiny.run(root), "traced": bench_tiny.run(root, trace=True),
           "averaged_over": averaged_over}
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main(sys.argv[1])
