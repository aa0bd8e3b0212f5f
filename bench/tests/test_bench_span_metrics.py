"""The per-layer readers of the program's own spans: on hand-made events, and
on a tiny traced run of the feed on the CPU."""
import json
import os

import pytest

import bench_tiny
from bench import spec, tracing

HOST = dict(plane="/host:CPU", line="python")
NEW = ("batch_ms.feed", "read_ms.feed", "verify_ms.feed")
LINK = ("h2d_bytes_per_token.feed", "d2h_bytes_per_token.feed")


def ev(name, t0, dur, **kw):
    return dict(HOST, name=name, t0=float(t0), dur=float(dur), **kw)


def read(metric, events):
    return spec.load_reader(metric)({"events": events})


def window_events():
    """A window 1-11 ms; three batches end inside it, one before, one after."""
    ms = 1e6
    return [ev(tracing.WINDOW_SPAN, 1 * ms, 10 * ms),
            ev("feed.batch", 0, 0.5 * ms),             # ends before the window
            ev("feed.batch", 0.5 * ms, 2 * ms),        # straddles the start: counts
            ev("feed.batch", 3 * ms, 3 * ms),
            ev("feed.batch", 7 * ms, 4 * ms),          # ends on the edge: counts
            ev("feed.batch", 10 * ms, 2 * ms),         # straddles the end: does not
            ev("storage.read", 0.5 * ms, 1 * ms),      # half inside
            ev("storage.read", 4 * ms, 1 * ms),
            ev("storage.read", 10.5 * ms, 1 * ms),     # half inside
            ev("colfile.verify", 5 * ms, 0.3 * ms),
            ev("colfile.verify", 5 * ms, 0.3 * ms, plane="/device:TPU:0")]  # not a host span


def test_batch_ms_is_the_mean_of_batches_ending_in_the_window():
    assert read("batch_ms.feed", window_events()) == pytest.approx((2 + 3 + 4) / 3)


def test_read_and_verify_are_clipped_and_per_batch():
    events = window_events()
    assert read("read_ms.feed", events) == pytest.approx((0.5 + 1 + 0.5) / 3)
    assert read("verify_ms.feed", events) == pytest.approx(0.3 / 3)


@pytest.mark.parametrize("metric", NEW)
def test_no_events_or_no_batch_reads_nothing(metric):
    assert read(metric, None) is None and read(metric, []) is None
    no_batch = [e for e in window_events() if e["name"] != "feed.batch"]
    assert read(metric, no_batch) is None
    # batches that all end outside the window count as none
    outside = [e for e in window_events() if e["name"] != "feed.batch" or e["t0"] in (0, 1e7)]
    assert read(metric, outside) is None


def test_a_span_free_program_reads_nothing():
    events = [e for e in window_events() if e["name"] == tracing.WINDOW_SPAN]
    events.append(ev("tokens.open_split", 2e6, 1e6))
    assert all(read(m, events) is None for m in NEW)


def test_new_entries_load_and_read_spans():
    with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = [w["name"] for w in bench["workloads"]]
    entries = {m["name"]: m for m in bench["per_layer"] if m["name"] in NEW}
    assert set(entries) == set(NEW)
    for m in entries.values():
        assert m["source"] == "program_span" and m["moves"] == "feed_tokens_per_s"
        assert m["workloads"] == cells and m["unit"] == "ms" and m["better"] == "lower"
        assert spec.load_reader(m["name"])({"events": window_events()}) > 0


def link_events():
    """``window_events()`` (3 batches end inside the window) with the spans
    that carry bytes: three of each name end inside it, one before, one after."""
    ms = 1e6
    ev_ = window_events()
    for end in (0.4, 2.4, 5.9, 10.9, 11.5):
        ev_.append(ev("decode.dispatch", (end - 0.2) * ms, 0.2 * ms, bytes=1000))
        ev_.append(ev("decode.d2h", (end - 0.1) * ms, 0.1 * ms, bytes=2000))
    for end in (0.9, 3, 7, 11, 12):  # 11: ends on the edge, counts
        ev_.append(ev("bench.place_batch", (end - 0.5) * ms, 0.5 * ms, bytes=3000))
    ev_.append(ev("decode.d2h", 5 * ms, 0.1 * ms, plane="/device:TPU:0", bytes=9))  # not a host span
    return ev_


def read_link(metric, events, batch_tokens=500):
    return spec.load_reader(metric)({"events": events, "batch_tokens": batch_tokens})


def test_link_bytes_per_token_count_the_spans_ending_in_the_window():
    events = link_events()
    assert read_link("h2d_bytes_per_token.feed", events) == pytest.approx(
        (3 * 1000 + 3 * 3000) / (3 * 500))
    assert read_link("d2h_bytes_per_token.feed", events) == pytest.approx(3 * 2000 / (3 * 500))
    # placement of arrays already on the chips copies nothing: it counts 0
    on_chip = [dict(e, bytes=0) if e["name"] == "bench.place_batch" else e for e in events]
    assert read_link("h2d_bytes_per_token.feed", on_chip) == pytest.approx(2.0)


@pytest.mark.parametrize("metric", LINK)
def test_link_without_its_spans_reads_nothing(metric):
    assert read_link(metric, None) is None and read_link(metric, []) is None
    events = link_events()
    assert read_link(metric, [e for e in events if e["name"] != "feed.batch"]) is None
    kept = {"h2d_bytes_per_token.feed": ("decode.dispatch", "bench.place_batch"),
            "d2h_bytes_per_token.feed": ("decode.d2h",)}[metric]
    assert read_link(metric, [e for e in events if e["name"] not in kept]) is None
    no_bytes = [{k: v for k, v in e.items() if k != "bytes"} for e in events]
    assert read_link(metric, no_bytes) is None


@pytest.mark.parametrize("metric", LINK)
def test_link_entries_load_and_read_spans(metric):
    bench = spec.load_benchmark()
    m = next(m for m in bench["per_layer"] if m["name"] == metric)
    assert m["source"] == "program_span" and m["moves"] == "feed_tokens_per_s"
    assert m["layer"] == "host-device link" and m["unit"] == "B/token"
    assert m["better"] == "lower" and m["workloads"] == [w["name"] for w in bench["workloads"]]
    assert read_link(metric, link_events()) > 0


def test_d2h_reads_nothing_for_a_feed_that_keeps_its_batch_on_the_chips():
    events = [e for e in link_events() if e["name"] != "decode.d2h"]
    assert read_link("d2h_bytes_per_token.feed", events) is None
    assert read_link("h2d_bytes_per_token.feed", events) == pytest.approx(8.0)


def test_tiny_traced_run_reads_the_spans(tmp_path):
    out = bench_tiny.run(bench_tiny.make_root(str(tmp_path)), trace=True)
    assert out["correct"]
    got = {m: out["metrics"][m]["value"] for m in NEW + LINK}
    assert all(v > 0 for v in got.values())
    assert got["read_ms.feed"] + got["verify_ms.feed"] < got["batch_ms.feed"]
    # int32 ids back, and the batch's three 4-byte arrays placed, a token;
    # at the window's edges a span may count without its batch, or not
    assert got["d2h_bytes_per_token.feed"] == pytest.approx(4.0, rel=0.1)
    assert got["h2d_bytes_per_token.feed"] > 12.0
