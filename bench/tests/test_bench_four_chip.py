"""A four-chip cell runs end to end on four CPU devices: today's program
decodes on one device and the loop places each batch over all four."""
import json
import os
import subprocess
import sys

import pytest

import bench_tiny

HERE = os.path.dirname(os.path.abspath(__file__))
NOTHING_ON_CPU = "A CPU trace has no device plane"  # as the reader's docstring says


@pytest.fixture(scope="module")
def four(tmp_path_factory):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, os.path.join(HERE, "four_chip_cell.py"),
                        str(tmp_path_factory.mktemp("four"))],
                       env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("run", ["untraced", "traced"])
def test_four_chip_cell_is_correct_on_four_devices(four, run):
    out = four[run]
    assert out["correct"], out["checks"]
    assert out["device"]["count"] == 4 and out["attempted"] > 0
    assert out["checks"]["misplaced"]["value"] == 0


def test_four_chip_cell_reads_every_per_layer_metric(four):
    out = four["traced"]
    with open(os.path.join(bench_tiny.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = "olmoe-tokens.feed-stream"
    for m in bench["per_layer"]:
        if cell not in m.get("workloads", [cell]):
            continue
        if m["name"] in out["metrics"]:
            assert out["metrics"][m["name"]]["value"] >= 0
        else:
            with open(os.path.join(bench_tiny.BENCH, "metrics", m["name"] + ".py")) as f:
                assert NOTHING_ON_CPU in " ".join(f.read().split()), m["name"]
    # the idle share is averaged over the four devices the cell holds
    assert four["averaged_over"] == [4]
    dev = out["device"]
    assert out["metrics"]["idle_pct.feed"]["value"] == pytest.approx(
        100 * (1 - dev["busy_s"] / dev["window_s"]))
