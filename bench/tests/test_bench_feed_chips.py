"""The four-chip cell ``olmoe-tokens.feed-4chip`` at a tiny size on four CPU
devices: each chip decodes its own rows and the batch stays on the chips
(``feed_chips_cell.py``, one subprocess). A stand-in in which each chip
decodes chip 0's rows is found not correct, and a program without the
per-chip decode cannot load the cell's loop."""
import json
import os
import subprocess
import sys

import pytest

import bench_tiny
from bench import spec

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "olmoe-tokens.feed-4chip"
NOTHING_ON_CPU = "A CPU trace has no device plane"  # as the reader's docstring says


@pytest.fixture(scope="module")
def four(tmp_path_factory):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, os.path.join(HERE, "feed_chips_cell.py"),
                        str(tmp_path_factory.mktemp("chips"))],
                       env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("run", ["untraced", "traced"])
def test_chips_cell_is_correct_on_four_devices(four, run):
    out = four[run]
    assert out["correct"], out["checks"]
    assert out["device"]["count"] == 4 and out["attempted"] > 0
    assert all(c["value"] == 0 for c in out["checks"].values()), out["checks"]


def test_chips_cell_reads_its_per_layer_metrics(four):
    out = four["traced"]
    with open(os.path.join(bench_tiny.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    listed = [m["name"] for m in bench["per_layer"] if CELL in m.get("workloads", [CELL])]
    assert {"chips_decode_ms.feed", "d2h_bytes_per_token.feed"} <= set(listed)
    for name in listed:
        if name in out["metrics"]:
            assert out["metrics"][name]["value"] >= 0
        else:
            with open(os.path.join(bench_tiny.BENCH, "metrics", name + ".py")) as f:
                assert NOTHING_ON_CPU in " ".join(f.read().split()), name
    assert out["metrics"]["chips_decode_ms.feed"]["value"] > 0
    assert out["metrics"]["d2h_bytes_per_token.feed"]["value"] == 0  # nothing comes back
    assert out["metrics"]["chips_decode_ms.feed"]["value"] < out["metrics"]["batch_ms.feed"]["value"]


def test_chips_cell_copies_nothing_back_and_places_nothing(four):
    spans = four["spans"]
    names = {s["name"] for s in spans}
    assert {"decode.chips", "decode.dispatch", "decode.d2h", "bench.place_batch"} <= names
    assert all(s["bytes"] == 0 for s in spans if s["name"] in ("decode.d2h", "bench.place_batch"))
    # the copies to the chips are the decode's: the same bytes in both spans
    chips = sorted(s["bytes"] for s in spans if s["name"] == "decode.chips")
    dispatch = sorted(s["bytes"] for s in spans if s["name"] == "decode.dispatch")
    assert chips and set(chips) == set(dispatch) and min(chips) > 0


def test_each_chip_decoding_chip_0s_rows_is_not_correct(four):
    out = four["chip0_rows"]
    assert not out["correct"]
    c = out["checks"]
    assert c["token_mismatch"]["value"] + c["unexplained_repeats"]["value"] > 0


def test_a_program_without_the_per_chip_decode_cannot_load_the_loop(monkeypatch):
    from repro.data import tokens

    assert spec.find_cell(CELL).traffic["loop"] == "feed_chips"
    monkeypatch.delattr(tokens, "chips_decode_batch")
    with pytest.raises(ImportError, match="chips_decode_batch"):
        spec.load_loop("feed_chips")


def test_the_cell_runs_its_configurations_host():
    cell = spec.find_cell(CELL)
    assert cell.config["chips_per_host"] == cell.chips == 4
    assert cell.config["batch_per_host"] % cell.chips == 0


def test_the_loop_refuses_another_number_of_chips():
    import jax

    cfg = dict(spec.find_cell(CELL).config)
    with pytest.raises(ValueError, match="4 chips a host, run on 1"):
        spec.load_loop("feed_chips").run(cfg, {}, None, 0, None, jax.devices()[:1])
