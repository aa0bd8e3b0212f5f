"""Cells are found by name: a new configuration, traffic, loop and metric file
make a new cell with no edit. And ``BENCHMARK.json`` keeps to its contract."""
import json
import os
import re

import pytest

import bench_tiny
from bench import spec

# a new loop: the feed, with one more end-to-end metric of its own
NEW_LOOP = """
from bench import spec

_feed = spec.load_loop("feed")
LIMITS = _feed.LIMITS
END_TO_END = dict(_feed.END_TO_END, batches_per_s=lambda r: r["attempted"] / r["window_s"])
run = _feed.run
check = _feed.check
"""
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_new_files_make_a_new_cell(tmp_path):
    root = bench_tiny.make_root(str(tmp_path))
    with open(os.path.join(root, "bench", "configs", "tiny.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(root, "bench", "configs", "tiny-wide.json"), "w") as f:
        json.dump(dict(cfg, name="tiny-wide", batch_per_host=4), f)
    with open(os.path.join(root, "bench", "traffic", "feed-slow.json"), "w") as f:
        json.dump({"loop": "feed-counted", "decode": "np", "prefetch": 0,
                   "warmup_batches": 1, "sample_batches": 2}, f)
    with open(os.path.join(root, "bench", "loops", "feed-counted.py"), "w") as f:
        f.write(NEW_LOOP)
    with open(os.path.join(root, "bench", "metrics", "rows_seen.feed.py"), "w") as f:
        f.write("def read(ctx):\n    return ctx['tokens_made'] / 4\n")
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "tiny-wide", "source": "test",
                             "file": "bench/configs/tiny-wide.json", "reduced": [],
                             "why": "test"})
    bench["workloads"].append({"name": "tiny-wide.feed-slow", "config": "tiny-wide",
                               "traffic": "feed-slow", "chips": 1, "why": "test"})
    bench["per_layer"].append({"name": "rows_seen.feed", "unit": "rows", "better": "higher",
                               "source": "program_counter", "layer": "column read",
                               "moves": "feed_tokens_per_s",
                               "workloads": ["tiny-wide.feed-slow"]})
    bench["end_to_end"][0]["workloads"].append("tiny-wide.feed-slow")
    bench["end_to_end"].append({"name": "batches_per_s", "unit": "1/s", "better": "higher",
                                "bound": 0.05, "source": "host_clock",
                                "workloads": ["tiny-wide.feed-slow"]})
    with open(path, "w") as f:
        json.dump(bench, f)

    cell = spec.find_cell("tiny-wide.feed-slow", root=root)
    assert cell.config["batch_per_host"] == 4 and cell.traffic["prefetch"] == 0
    assert [m.name for m in cell.end_to_end] == ["feed_tokens_per_s", "setup_s",
                                                 "batches_per_s"]
    assert [m.name for m in cell.per_layer] == ["rows_seen.feed"]
    assert spec.load_reader("rows_seen.feed", root)({"tokens_made": 8}) == 2
    out = bench_tiny.run(root, "tiny-wide.feed-slow", trace=True)
    assert out["correct"] and out["metrics"]["rows_seen.feed"]["value"] > 0
    out = bench_tiny.run(root, "tiny-wide.feed-slow")
    assert out["correct"] and out["metrics"]["batches_per_s"]["value"] > 0

    with pytest.raises(spec.SpecError):
        spec.find_cell("tiny-wide.no-such", root=root)
    with pytest.raises(spec.SpecError):
        spec.load_reader("no_such.feed", root)


def test_unknown_loop_or_metric_is_an_error(tmp_path):
    root = bench_tiny.make_root(str(tmp_path))
    path = os.path.join(root, "bench", "traffic", "feed-stream.json")
    with open(path) as f:
        traffic = json.load(f)
    with open(path, "w") as f:
        json.dump(dict(traffic, loop="no-such-loop"), f)
    with pytest.raises(spec.SpecError, match="no-such-loop"):
        bench_tiny.run(root)
    with open(path, "w") as f:
        json.dump(traffic, f)
    bench_path = os.path.join(root, "BENCHMARK.json")
    with open(bench_path) as f:
        bench = json.load(f)
    bench["end_to_end"].append({"name": "fetch_p95_ms", "unit": "ms", "better": "lower",
                                "bound": 0.1, "source": "host_clock"})
    with open(bench_path, "w") as f:
        json.dump(bench, f)
    with pytest.raises(spec.SpecError, match="fetch_p95_ms"):
        bench_tiny.run(root)


BENCH = spec.load_benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]


def _for(cell, kind):
    return [m for m in BENCH[kind] if "workloads" not in m or cell in m["workloads"]]


def test_contract_keys_and_names():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["bench"] and BENCH["command"] == ["python3", "bench/run.py"]
    assert 1 <= BENCH["run_seconds"] <= 51
    names = [x["name"] for k in ("configs", "workloads", "end_to_end", "per_layer")
             for x in BENCH[k]]
    assert all(NAME.match(n) for n in names) and len(set(names)) == len(names)
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert os.path.exists(os.path.join(spec.ROOT, c["file"]))
        with open(os.path.join(spec.ROOT, c["file"])) as f:
            body = json.load(f)
        assert set(c["reduced"]) == set(body["reduced"]) and body["assumed"]
        assert body["name"] == c["name"] and body["source"] == c["source"]
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert len(w["why"]) <= 200
        assert os.path.exists(os.path.join(spec.BENCH_DIR, "traffic", w["traffic"] + ".json"))
    assert spec.four_chip_ok([w["chips"] for w in BENCH["workloads"]])
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert all(w in CELLS for w in m.get("workloads", []))
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace") and 0.01 <= m["bound"] <= 0.25
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e and "bound" not in m
        spec.load_reader(m["name"])


@pytest.mark.parametrize("chips, ok", [
    ([4], True), ([1, 1, 4], True), ([1, 4, 1, 4], True), ([1, 4, 4, 4], False),
    ([4, 1, 4, 1, 4], False), ([1, 1], True), ([4, 4], False), ([2], False),
    ([1, 8], False), ([1, 1, 1, 0], False),
])
def test_four_chip_rule(chips, ok):
    """1 or 4 chips a cell; at most half of the cells, rounded down, on 4, and
    one always."""
    assert spec.four_chip_ok(chips) is ok


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_reports_what_it_must(cell):
    e2e = {m["name"] for m in _for(cell, "end_to_end")}
    assert "setup_s" in e2e and len(e2e) >= 2
    layer = _for(cell, "per_layer")
    assert layer and all(m["moves"] in e2e for m in layer)
    c = spec.find_cell(cell)
    assert c.config["vocab_size"] > 0
    loop = spec.load_loop(c.traffic["loop"])
    assert e2e - {"setup_s"} <= set(loop.END_TO_END)
