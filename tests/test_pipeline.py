"""Data pipeline: determinism, resumability, projection pushdown, sharding."""
import os

import numpy as np
import pytest

from repro.core import trace
from repro.core.blockcache import BlockCache
from repro.core.cif import ScanStats
from repro.core.placement import Placement
from repro.data.pipeline import HostPipeline, PipelineState
from repro.data.sampler import SamplerState, ShardedSampler
from repro.data.tokens import TokenCorpus, TokenCorpusWriter
from repro.launch.load_data import synth_token_docs


@pytest.fixture(scope="module")
def corpus(tmp_path_factory):
    root = tmp_path_factory.mktemp("corpus")
    w = TokenCorpusWriter(str(root), seq_len=64, split_records=32)
    for toks, meta in synth_token_docs(150, vocab=2000):
        w.add_document(toks, meta)
    w.close()
    return TokenCorpus(str(root))


def test_corpus_roundtrip_decode_paths(corpus):
    sp = corpus.open_split(corpus.split_ids()[0])
    t_np, m = sp.record(0, decode="np")
    sp2 = corpus.open_split(corpus.split_ids()[0])
    t_py, m2 = sp2.record(0, decode="py")
    np.testing.assert_array_equal(t_np, t_py)
    np.testing.assert_array_equal(m, m2)
    assert t_np.shape == (64,) and t_np.dtype == np.int32


def test_pipeline_deterministic(corpus):
    def take(n):
        pipe = HostPipeline(corpus, batch_per_host=4, prefetch=0, seed=7)
        it = iter(pipe)
        return [next(it)["tokens"].copy() for _ in range(n)]

    a, b = take(6), take(6)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)


def test_pipeline_resume_matches_uninterrupted(corpus):
    pipe = HostPipeline(corpus, batch_per_host=4, prefetch=0, seed=7)
    it = iter(pipe)
    full = [next(it)["tokens"].copy() for _ in range(8)]
    # run 4, snapshot state, restore into a new pipeline
    pipe2 = HostPipeline(corpus, batch_per_host=4, prefetch=0, seed=7)
    it2 = iter(pipe2)
    for _ in range(4):
        next(it2)
    st = pipe2.state()
    pipe3 = HostPipeline(corpus, batch_per_host=4, prefetch=0, seed=7, state=st)
    it3 = iter(pipe3)
    for i in range(4, 8):
        np.testing.assert_array_equal(next(it3)["tokens"], full[i])


def test_pipeline_hosts_disjoint(corpus):
    seen = {}
    for host in range(3):
        s = ShardedSampler(
            {sid: len(corpus.open_split(sid)) for sid in corpus.split_ids()},
            Placement(len(corpus.split_ids()), 3),
            host,
        )
        it = iter(s)
        mine = set()
        # one full epoch for this host
        start_epoch = s.state.epoch
        while s.state.epoch == start_epoch:
            sid, rid = next(it)
            if s.state.epoch != start_epoch:
                break
            mine.add((sid, rid))
        seen[host] = mine
    all_pairs = set().union(*seen.values())
    assert sum(len(v) for v in seen.values()) == len(all_pairs)  # disjoint


def test_projection_pushdown_never_opens_meta(corpus):
    sid = corpus.split_ids()[0]
    sp = corpus.open_split(sid)
    assert set(sp.reader.readers) == {"tokens", "n_tokens", "loss_mask"}


def test_labels_are_shifted(corpus):
    pipe = HostPipeline(corpus, batch_per_host=2, prefetch=0)
    b = next(iter(pipe))
    np.testing.assert_array_equal(b["labels"][:, :-1], b["tokens"][:, 1:])
    assert (b["loss_mask"][:, -1] == 0).all()


def test_prefetch_thread_equivalent(corpus):
    p0 = HostPipeline(corpus, batch_per_host=4, prefetch=0, seed=3)
    p2 = HostPipeline(corpus, batch_per_host=4, prefetch=2, seed=3)
    it0, it2 = iter(p0), iter(p2)
    for _ in range(5):
        np.testing.assert_array_equal(next(it0)["tokens"], next(it2)["tokens"])
    p2.stop()


# -- the held split -----------------------------------------------------------
# The sampler walks one split's records in order, so one split serves many
# batches in a row; the pipeline holds it open for all of them.

# host decode, device decode back to the host, and device decode kept on a
# one-device batch sharding (the per-chip path: ``read_packed`` per group)
MODES = ["np", "device", "chips"]


def _pipe(corpus, mode, batch=4, seed=7, **kw):
    if mode != "chips":
        return HostPipeline(corpus, batch_per_host=batch, prefetch=0, seed=seed, decode=mode, **kw)
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    sharding = NamedSharding(Mesh(np.asarray(jax.devices()[:1]), ("data",)), P("data", None))
    return HostPipeline(corpus, batch_per_host=batch, prefetch=0, seed=seed, decode="device",
                        sharding=sharding, **kw)


@pytest.fixture(scope="module")
def small_corpus(tmp_path_factory):
    """Three splits of 8 records: an epoch is six batches of 4."""
    root = tmp_path_factory.mktemp("small-corpus")
    w = TokenCorpusWriter(str(root), seq_len=16, split_records=8)
    rng = np.random.default_rng(0)
    w.add_document(rng.integers(0, 300, 24 * 16).astype(np.int32))
    w.close()
    return TokenCorpus(str(root))


def _traced(pipe, n):
    """``n`` batches from ``pipe`` under a tracer, with the ``opened`` arg of
    each batch's ``feed.batch`` span and the number of ``split.open`` spans."""
    with trace.tracing() as tr:
        it = iter(pipe)
        batches = [{k: np.asarray(v).copy() for k, v in next(it).items()} for _ in range(n)]
    spans = [(name, args) for ph, name, _ts, _dur, _tid, args, _cat, _d in tr.events()
             if ph == "X"]
    opened = [a["opened"] for name, a in spans if name == "feed.batch"]
    return batches, opened, sum(name == "split.open" for name, _ in spans)


def _by_hand(corpus, batch, n, seed, hold, state=None):
    """``n`` batches built by hand from the sampler's draws, each group of a
    batch's rows that lie in one split read with one ``record_batch`` call.
    The split is opened afresh for every group, or (``hold``) once for each
    run of groups that read it forward. Returns the batches, the counters of
    every reader folded once with ``ScanStats.absorb``, and the opens."""
    sizes = corpus.split_sizes()
    sampler = ShardedSampler(sizes, Placement(len(sizes), 1), 0, seed=seed,
                             state=state.sampler if state else None)
    draws = iter(sampler)
    cache = BlockCache(HostPipeline.DEFAULT_CACHE_BYTES)
    stats, opens, batches, held = ScanStats(), 0, [], None  # held: [sid, split, last id]

    def retire(sp):
        for r in sp.reader.readers.values():
            stats.absorb(r.counters, r.file_bytes)

    for _ in range(n):
        by_split = {}
        for slot in range(batch):
            sid, rid = next(draws)
            by_split.setdefault(sid, []).append((rid, slot))
        rows = [None] * batch  # (tokens, mask) of each slot
        for sid, rid_slots in by_split.items():
            uniq = sorted({r for r, _ in rid_slots})
            if not (hold and held and held[0] == sid and uniq[0] > held[2]):
                if held:
                    retire(held[1])
                held = [sid, corpus.open_split(sid, cache=cache), -1]
                opens += 1
            held[2] = uniq[-1]
            t, m = held[1].record_batch(uniq)
            for rid, slot in rid_slots:
                rows[slot] = t[uniq.index(rid)], m[uniq.index(rid)]
        tokens, mask = (np.stack(x) for x in zip(*rows))
        labels = np.concatenate([tokens[:, 1:], np.zeros((batch, 1), np.int32)], axis=1)
        lm = mask.astype(np.float32)
        lm[:, -1] = 0.0
        batches.append({"tokens": tokens, "labels": labels, "loss_mask": lm})
    retire(held[1])
    return batches, stats, opens


def _assert_same(got, want):
    assert len(got) == len(want)
    for a, b in zip(got, want):
        assert a.keys() == b.keys()
        for k in a:
            np.testing.assert_array_equal(a[k], b[k], err_msg=k)


@pytest.mark.parametrize("mode", MODES)
def test_one_open_per_split(corpus, mode):
    # 32-record splits, batches of 4: two whole splits are 16 batches
    pipe = _pipe(corpus, mode)
    _, opened, opens = _traced(pipe, 16)
    assert opened == [1] + [0] * 7 + [1] + [0] * 7
    assert opens == 2
    assert pipe.stats.files_opened == 2 * 3  # tokens, n_tokens, loss_mask


@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("batch", [4, 5])  # 5 runs batches across splits
def test_batches_match_a_reopen_per_batch(corpus, mode, batch):
    n = 14
    got, opened, opens = _traced(_pipe(corpus, mode, batch), n)
    want, _, reopens = _by_hand(corpus, batch, n, 7, hold=False)
    _assert_same(got, want)
    assert sum(opened) == opens < reopens


@pytest.mark.parametrize("batch", [4, 5])
def test_counters_count_each_open_once(corpus, batch):
    n = 14
    pipe = HostPipeline(corpus, batch_per_host=batch, prefetch=0, seed=7)
    it = iter(pipe)
    for i in range(1, n + 1):
        next(it)
        # whole after every batch: the held split's reads are folded in
        _, held, opens = _by_hand(corpus, batch, i, 7, hold=True)
        assert vars(pipe.stats) == vars(held)
    _, reopened, reopens = _by_hand(corpus, batch, n, 7, hold=False)
    # one reader a split read forward does what one reader a batch does, less
    # the per-open costs: the files read and opened, the dict page touched and
    # the records skipped up to each batch's first, and the cache lookups
    per_open = {"bytes_io", "files_opened", "bytes_touched", "cells_skipped", "cache_hits",
                "cache_misses", "cache_evictions", "bytes_served_from_cache"}
    for k, v in vars(reopened).items():
        if k not in per_open:
            assert getattr(pipe.stats, k) == v, k
    assert pipe.stats.files_opened == 3 * opens < reopened.files_opened == 3 * reopens
    assert pipe.stats.bytes_io < reopened.bytes_io


@pytest.mark.parametrize("mode", MODES)
def test_epoch_wrap_onto_the_held_split_reopens_it(small_corpus, mode):
    def order(seed, epoch):
        sizes = small_corpus.split_sizes()
        return ShardedSampler(sizes, Placement(len(sizes), 1), 0, seed=seed)._host_splits(epoch)

    # a seed whose first epoch ends on the split its second starts with
    seed = next(s for s in range(100) if order(s, 0)[-1] == order(s, 1)[0])
    pipe = _pipe(small_corpus, mode, seed=seed)
    got, opened, opens = _traced(pipe, 8)
    assert opened == [1, 0, 1, 0, 1, 0, 1, 0]
    assert opens == 4
    want, held, _ = _by_hand(small_corpus, 4, 8, seed, hold=True)
    _assert_same(got, want)
    assert vars(pipe.stats) == vars(held)
    # the split opened again comes back from the cache
    assert pipe.stats.cache_hits > 0


@pytest.mark.parametrize("mode", MODES)
def test_resume_mid_split_matches_uninterrupted(corpus, mode):
    want, _, _ = _traced(_pipe(corpus, mode), 6)
    first = _pipe(corpus, mode)
    _traced(first, 3)
    st = first.state().to_json()
    assert st["sampler"]["record"] == 12  # mid-split
    resumed = _pipe(corpus, mode, state=PipelineState.from_json(st))
    got, opened, opens = _traced(resumed, 3)
    _assert_same(got, want[3:])
    assert opened == [1, 0, 0] and opens == 1
    _, held, _ = _by_hand(corpus, 4, 3, 7, hold=True, state=PipelineState.from_json(st))
    assert vars(resumed.stats) == vars(held)


def test_cache_off_gives_the_same_batches_and_counters(small_corpus):
    # over an epoch wrap, where a split is opened again
    def run(cache):
        pipe = HostPipeline(small_corpus, batch_per_host=4, prefetch=0, seed=1, cache=cache)
        it = iter(pipe)
        return [{k: v.copy() for k, v in next(it).items()} for _ in range(12)], vars(pipe.stats)

    on_batches, on = run(None)
    off_batches, off = run(BlockCache(0))
    _assert_same(on_batches, off_batches)
    assert on["bytes_served_from_cache"] > 0
    assert off["bytes_decoded"] == on["bytes_decoded"] + on["bytes_served_from_cache"]
    cache = {"bytes_decoded", "cache_hits", "cache_misses", "cache_evictions",
             "bytes_served_from_cache"}
    assert {k: v for k, v in on.items() if k not in cache} == \
        {k: v for k, v in off.items() if k not in cache}
