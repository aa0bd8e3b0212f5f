"""Program spans of the token feed: in the profiler's trace with no tracer
installed, nested as the feed runs, in an installed tracer too, and free of
any effect on the batches."""
import queue

import numpy as np
import pytest

from conftest import profiled_host_events
from repro.core import trace
from repro.data.pipeline import HostPipeline
from repro.data.tokens import TokenCorpus, TokenCorpusWriter
from repro.launch.load_data import synth_token_docs

FEED_SPANS = {"feed.batch", "feed.assemble", "feed.wait", "split.open", "storage.read",
              "colfile.verify", "split.read_packed", "decode.dispatch", "decode.wait",
              "decode.d2h"}


@pytest.fixture(scope="module")
def corpus(tmp_path_factory):
    root = tmp_path_factory.mktemp("corpus")
    w = TokenCorpusWriter(str(root), seq_len=64, split_records=32)
    for toks, meta in synth_token_docs(150, vocab=2000):
        w.add_document(toks, meta)
    w.close()
    return TokenCorpus(str(root))


def _take(corpus, n, prefetch):
    pipe = HostPipeline(corpus, batch_per_host=4, seed=7, prefetch=prefetch, decode="device")
    it = iter(pipe)
    got = [{k: v.copy() for k, v in next(it).items()} for _ in range(n)]
    pipe.stop()
    # the prefetch thread finishes the batch it is making: take it, so the
    # thread ends before the caller's profile or tracer scope closes
    while pipe._thread is not None and pipe._thread.is_alive():
        try:
            pipe._q.get(timeout=0.05)
        except queue.Empty:
            pass
    return got


def _inside(inner, outers):
    return any(o["line"] == inner["line"] and o["t0"] <= inner["t0"]
               and inner["t1"] <= o["t1"] for o in outers)


@pytest.fixture(scope="module")
def profiled_feed(corpus, tmp_path_factory):
    assert trace.live() is None  # the profiler sink needs no tracer
    _take(corpus, 1, prefetch=0)  # compile the kernels outside the profile
    return profiled_host_events(tmp_path_factory.mktemp("profile"), lambda: _take(corpus, 3, prefetch=2))


def test_profile_holds_every_feed_span(profiled_feed):
    _, events = profiled_feed
    names = {e["name"] for e in events}
    assert FEED_SPANS <= names, FEED_SPANS - names


def test_feed_spans_nest(profiled_feed):
    _, events = profiled_feed
    by = {n: [e for e in events if e["name"] == n] for n in FEED_SPANS}
    for e in by["storage.read"]:
        assert _inside(e, by["split.open"])
    # the dict page is verified at open; mask blocks on first touch
    assert any(_inside(e, by["split.open"]) for e in by["colfile.verify"])
    for e in by["colfile.verify"]:
        assert _inside(e, by["split.open"] + by["split.read_packed"])
    for n in ("split.open", "split.read_packed", "feed.assemble",
              "decode.dispatch", "decode.wait", "decode.d2h"):
        assert by[n] and all(_inside(e, by["feed.batch"]) for e in by[n]), n
    # the consumer waits on its own thread, outside the batches it waits for
    assert not any(_inside(e, by["feed.batch"]) for e in by["feed.wait"])


def test_installed_tracer_records_the_same_spans(corpus):
    def traced():
        with trace.tracing() as tr:
            batches = _take(corpus, 2, prefetch=0)
        return tr, batches

    tr, _ = traced()
    spans = [(name, args) for ph, name, _ts, _dur, _tid, args, _cat, _d in tr.events()
             if ph == "X"]
    assert FEED_SPANS <= {n for n, _ in spans}
    # the first batch opens its split; the second reads on from the held one
    assert [a for n, a in spans if n == "feed.batch"] == [{"batch": 0, "opened": 1},
                                                          {"batch": 1, "opened": 0}]
    reads = [a for n, a in spans if n == "storage.read"]
    assert reads and all(set(a) == {"column", "bytes"} and a["bytes"] > 0 for a in reads)
    assert {a["column"] for a in reads} == {"tokens", "n_tokens", "loss_mask"}
    assert all(a["bytes"] > 0 for n, a in spans if n == "colfile.verify")
    # args are schedule-free: a second run reduces to the same view
    assert traced()[0].counter_view() == tr.counter_view()


def test_batches_identical_with_the_profiler_on(corpus, tmp_path):
    off = _take(corpus, 3, prefetch=2)
    on, _ = profiled_host_events(tmp_path, lambda: _take(corpus, 3, prefetch=2))
    for a, b in zip(off, on):
        assert a.keys() == b.keys()
        for k in a:
            np.testing.assert_array_equal(a[k], b[k])


def test_tracer_span_reaches_the_profile(tmp_path):
    def run():
        with trace.tracing() as tr:
            with tr.span("outer", {"split": 3}):
                with trace.span("inner", column="tokens") as sp:
                    sp.set_metadata(bytes=5)
        return tr

    tr, events = profiled_host_events(tmp_path, run)
    assert {"outer", "inner"} <= {e["name"] for e in events}
    assert [(e[1], e[5]) for e in tr.events()] == [
        ("inner", {"column": "tokens", "bytes": 5}), ("outer", {"split": 3})]
