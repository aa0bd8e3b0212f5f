"""The reference generator against the program's writer and reader."""
import json
import os

import numpy as np
import pytest

import bench_tiny  # noqa: F401  (puts the checkout and src on sys.path)
from bench import corpus
from bench_tiny import TINY

CFG = dict(TINY, zipf_s=1.0)


def _config(name):
    with open(os.path.join(bench_tiny.BENCH, "configs", name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("decode", ["np", "packed"])
def test_program_reads_back_the_reference(tmp_path, decode):
    from repro.core.encodings import unpack_codes_batch
    from repro.data.tokens import TokenCorpus

    seed = 2**31 + 99
    wrote = corpus.write_corpus(CFG, seed, str(tmp_path / "c"))
    assert wrote["n_sequences"] == CFG["n_splits"] * CFG["split_records"]
    tc = TokenCorpus(str(tmp_path / "c"))
    ref = corpus.reference_corpus(CFG, seed)
    assert tc.split_ids() == list(range(CFG["n_splits"]))
    for sid in tc.split_ids():
        sp = tc.open_split(sid)
        toks, mask = sp.record_batch(range(CFG["split_records"]), decode=decode)
        if decode == "packed":
            toks = sp.dictionary[unpack_codes_batch(toks, sp.bits, CFG["seq_len"])]
        np.testing.assert_array_equal(toks, ref[sid])
        assert (mask == 1).all()


def test_seed_fixes_the_corpus():
    a = corpus.reference_corpus(CFG, 5)
    b = corpus.reference_corpus(CFG, 5)
    c = corpus.reference_corpus(CFG, 2**40 + 5)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert not np.array_equal(a[0], c[0])
    assert all(x.shape == (CFG["split_records"], CFG["seq_len"]) for x in a + c)


@pytest.mark.parametrize("s", [1.0, 1.2])
def test_zipf_ranks_fall_with_rank(s):
    rng = np.random.default_rng(0)
    r = corpus.zipf_ranks(rng.random(1 << 22), 5000, s)
    assert r.min() == 0 and r.max() == 4999
    counts = np.bincount(r, minlength=5000)
    assert (np.diff(counts[:20]) < 0).all()
    # p(rank) ~ rank^-s away from the head: ten times the rank, 10^s fewer
    ratio = counts[100:110].sum() / counts[1000:1010].sum()
    assert 0.9 * 10**s < ratio < 1.1 * 10**s


@pytest.mark.parametrize("name, wide", [("olmoe-tokens", False), ("stablelm2-tokens", True)])
def test_real_split_takes_its_code_width(name, wide):
    """At the real split size a Zipf split holds nearly every id: 16-bit
    codes at a 50k vocabulary, more than 65,536 entries (32-bit codes) at
    100k."""
    from repro.core.encodings import bits_for

    cfg = _config(name)
    perm = corpus.rank_to_id(cfg, 1)
    distinct = len(np.unique(corpus.split_tokens(cfg, 1, 0, perm)))
    assert distinct > 0.95 * cfg["vocab_size"]
    assert bits_for(distinct) == (32 if wide else 16)
