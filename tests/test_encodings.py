"""Encoding layer (dict / RLE / delta-bitpack between varcodec and colfile):

* encode -> decode identity for every encoding x column kind combination,
  with batch ``read_range``/``read_many`` values AND ``ReadCounters``
  bit-identical to a scalar ``value_at`` loop (the Table-1 accounting
  contract extended to every encoding);
* automatic per-block selection from write-time stats, plus the forced
  ``ColumnFormat(encoding=...)`` knob that makes each path deterministic;
* ``DictRaggedColumn`` predicate pushdown on codes (contains/eq evaluate on
  the dictionary, broadcast through codes, survive slicing/concat);
* dict-encoded token pages feeding the Pallas device-decode path with no
  private dictionary sidecars;
* backward compatibility: version-1 files written by the pre-encoding
  writer (checked-in fixtures) still read bit-for-bit.
"""
import json
import os

import numpy as np
import pytest

from repro.core import (
    ARRAY, BYTES, DictRaggedColumn, INT32, INT64, MAP, RaggedColumn, STRING,
    CIFReader, COFWriter, storage_report, urlinfo_schema,
)
from repro.core.colfile import (
    ColumnFileReader, ColumnFileWriter, ColumnFormat, SKIPLIST_DICT_BLOCK,
)
from repro.core.encodings import ENCODINGS, candidates, encode_block, plain_size

FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures")

N = 2600  # spans multiple encoded blocks, skip groups, and cblocks


def _values_for(encoding, typ, rnd, n=N):
    """Data distributions that make ``encoding`` the natural choice."""
    k = typ.kind
    if encoding == "dict":
        if k == "string":
            return [rnd.choice(["text/html", "app/pdf", "img/png", "text/xml"])
                    for _ in range(n)]
        if k == "bytes":
            return [rnd.choice([b"alpha", b"beta", b"gamma-long-payload"])
                    for _ in range(n)]
        if k == "array":
            return [[rnd.randint(0, 400) for _ in range(16)] for _ in range(n)]
        return [rnd.choice([3, 77, 1024, -5]) for _ in range(n)]
    if encoding == "rle":
        if k == "string":
            vals = []
            while len(vals) < n:
                vals.extend([f"run{rnd.randint(0, 5)}"] * rnd.randint(1, 40))
            return vals[:n]
        base = [rnd.randint(0, 9) for _ in range(n // 20 + 1)]
        return [v for v in base for _ in range(20)][:n]
    if encoding == "delta":
        out, cur = [], rnd.randint(0, 1000)
        for _ in range(n):
            cur += rnd.randint(0, 30)
            out.append(cur)
        return out
    # plain: high-entropy data no lightweight encoding should beat
    if k == "string":
        return ["x" * rnd.randint(0, 60) + str(rnd.random()) for _ in range(n)]
    if k == "bytes":
        return [bytes([rnd.randrange(256) for _ in range(rnd.randint(0, 40))])
                for _ in range(n)]
    if k == "map":
        return [{f"k{rnd.randint(0, 9)}": rnd.randint(-99, 99)
                 for _ in range(rnd.randint(0, 5))} for _ in range(n)]
    return [rnd.randint(-(2**40), 2**40) for _ in range(n)]


def _build(typ, fmt, vals):
    w = ColumnFileWriter(typ, fmt)
    for v in vals:
        w.append(v)
    return w.finish(), w


def _as_list(v):
    return v.tolist() if hasattr(v, "tolist") else v


# every encoding x kind combination each path can express.  skiplist keeps
# cells individually skippable, so only plain/dict apply; dcsl IS a dict
# encoding already and stays plain.
COMBOS = [
    ("plain", "plain", INT64()), ("plain", "dict", INT64()),
    ("plain", "rle", INT64()), ("plain", "delta", INT64()),
    ("plain", "plain", STRING()), ("plain", "dict", STRING()),
    ("plain", "rle", STRING()), ("plain", "dict", BYTES()),
    ("plain", "dict", ARRAY(INT32())),
    ("cblock", "plain", INT64()), ("cblock", "dict", INT64()),
    ("cblock", "rle", INT64()), ("cblock", "delta", INT64()),
    ("cblock", "dict", STRING()), ("cblock", "rle", STRING()),
    ("skiplist", "plain", STRING()), ("skiplist", "dict", STRING()),
    ("skiplist", "dict", INT64()), ("skiplist", "dict", BYTES()),
    ("dcsl", "plain", MAP(STRING())),
]


@pytest.mark.parametrize(
    "kind,encoding,typ", COMBOS,
    ids=[f"{k}-{e}-{t.kind}" for k, e, t in COMBOS],
)
def test_forced_encoding_batch_matches_scalar(kind, encoding, typ, rnd):
    """The forced-encoding knob makes every path reachable deterministically;
    on each, batch reads return the same values AND the same counters as a
    scalar ``value_at`` loop (gappy ``read_many`` included)."""
    if kind == "dcsl":
        vals = [{f"key{rnd.randint(0, 9)}": f"v{rnd.randint(0, 50)}"
                 for _ in range(4)} for _ in range(N)]
    else:
        vals = _values_for(encoding, typ, rnd)
    fmt = ColumnFormat(kind, codec="zlib" if kind == "cblock" else "none",
                       encoding=encoding)
    raw, w = _build(typ, fmt, vals)
    if kind in ("plain", "cblock"):
        assert set(w.encoding_stats()["blocks"]) == {encoding}
    elif kind == "skiplist":
        assert ColumnFileReader(raw, typ).encoding == encoding
    scalar, batch = ColumnFileReader(raw, typ), ColumnFileReader(raw, typ)
    expect = [scalar.value_at(i) for i in range(len(vals))]
    got = _as_list(batch.read_range(0, len(vals)))
    assert got == expect == list(vals)
    assert vars(batch.counters) == vars(scalar.counters)
    # gappy monotone access
    idx = sorted(rnd.sample(range(len(vals)), 211))
    s2, b2 = ColumnFileReader(raw, typ), ColumnFileReader(raw, typ)
    assert _as_list(b2.read_many(idx)) == [s2.value_at(i) for i in idx]
    assert vars(b2.counters) == vars(s2.counters)


def test_auto_selection_from_write_stats(rnd):
    """Per-block stats pick the right encoding without user input."""
    cases = [
        (INT64(), _values_for("delta", INT64(), rnd), "delta"),
        (INT64(), _values_for("dict", INT64(), rnd), "dict"),
        (INT64(), _values_for("plain", INT64(), rnd), "plain"),
        (STRING(), _values_for("dict", STRING(), rnd), "dict"),
        (STRING(), _values_for("rle", STRING(), rnd), "rle"),
        (STRING(), _values_for("plain", STRING(), rnd), "plain"),
    ]
    for typ, vals, expect in cases:
        raw, w = _build(typ, ColumnFormat("plain"), vals)
        blocks = w.encoding_stats()["blocks"]
        assert set(blocks) == {expect}, (typ.kind, expect, blocks)
        # and the chosen payload really is smaller than plain (or is plain)
        st = w.encoding_stats()
        if expect != "plain":
            assert st["encoded_bytes"] < st["raw_bytes"]
        assert _as_list(ColumnFileReader(raw, typ).read_range(0, len(vals))) == vals


def test_auto_selection_varies_per_block(rnd):
    """A column whose blocks differ picks encodings PER BLOCK."""
    sorted_block = _values_for("delta", INT64(), rnd, 2048)
    random_block = _values_for("plain", INT64(), rnd, 2048)
    vals = sorted_block + random_block
    raw, w = _build(INT64(), ColumnFormat("plain"), vals)
    assert w.encoding_stats()["blocks"] == {"delta": 1, "plain": 1}
    assert _as_list(ColumnFileReader(INT64(), raw) if False else
                    ColumnFileReader(raw, INT64()).read_range(0, len(vals))) == vals


def test_encode_block_margin():
    """Selection needs a real win: a marginal dict candidate loses to plain."""
    # two distinct long strings, each once: dict == plain payload + overhead
    name, payload, raw = encode_block(STRING(), ["a" * 50, "b" * 50])
    assert name == "plain"


def test_invalid_forced_encodings_rejected():
    with pytest.raises(AssertionError):
        ColumnFileWriter(STRING(), ColumnFormat("plain", encoding="delta"))
    with pytest.raises(AssertionError):
        ColumnFileWriter(STRING(), ColumnFormat("skiplist", encoding="rle"))
    with pytest.raises(AssertionError):
        ColumnFileWriter(MAP(STRING()), ColumnFormat("dcsl", encoding="dict"))
    with pytest.raises(AssertionError):
        ColumnFileWriter(MAP(STRING()), ColumnFormat("skiplist", encoding="dict"))


def test_skiplist_dict_keeps_skipping_cheap(rnd):
    """Dict-mode skip lists still jump: sparse access touches a small
    fraction of what a dense scan touches (the §5.2 property survives the
    encoding layer)."""
    vals = [rnd.choice(["en", "jp", "de", "fr"]) for _ in range(5000)]
    raw, _ = _build(STRING(), ColumnFormat("skiplist"), vals)
    r = ColumnFileReader(raw, STRING())
    assert r.encoding == "dict"  # auto resolved: low cardinality
    for i in range(0, 5000, 1000):
        assert r.value_at(i) == vals[i]
    sparse_touched = r.counters.bytes_touched
    r2 = ColumnFileReader(raw, STRING())
    assert _as_list(r2.read_range(0, 5000)) == vals
    assert sparse_touched < r2.counters.bytes_touched / 5


def test_dict_ragged_column_pushdown(rnd):
    """contains/eq evaluate once per DICTIONARY entry and broadcast through
    codes; views preserve the codes."""
    vals = [rnd.choice(["text/html", "app/pdf", "img/png"]) for _ in range(1500)]
    raw, _ = _build(
        STRING(), ColumnFormat("plain", encoding="dict", enc_block=2048), vals
    )
    col = ColumnFileReader(raw, STRING()).read_range(0, len(vals))
    assert isinstance(col, DictRaggedColumn)
    assert len(col.dict_starts) == 3  # one offset per DISTINCT value
    np.testing.assert_array_equal(
        col.contains("pdf"), np.array(["pdf" in v for v in vals]))
    np.testing.assert_array_equal(
        col.eq("img/png"), np.array([v == "img/png" for v in vals]))
    view = col[100:700]
    assert isinstance(view, DictRaggedColumn) and view == vals[100:700]
    np.testing.assert_array_equal(
        view.eq("text/html"), np.array([v == "text/html" for v in vals[100:700]]))
    picked = col[np.array([5, 5, 1400])]
    assert isinstance(picked, DictRaggedColumn)
    assert picked == [vals[5], vals[5], vals[1400]]
    assert col.tolist() == vals


def test_plain_ragged_eq(rnd):
    vals = ["x" * rnd.randint(0, 20) + str(i % 7) for i in range(400)]
    raw, _ = _build(STRING(), ColumnFormat("plain", encoding="plain"), vals)
    col = ColumnFileReader(raw, STRING()).read_range(0, len(vals))
    assert isinstance(col, RaggedColumn)
    np.testing.assert_array_equal(
        col.eq(vals[13]), np.array([v == vals[13] for v in vals]))


def test_block_skipping_never_decodes_untouched_blocks(rnd):
    """The encoded-block plain kind gains cblock-style block skipping: a
    sparse read leaves far-away blocks untouched (bytes_touched ~ headers +
    the two visited blocks only)."""
    vals = _values_for("plain", STRING(), rnd, 8192)
    raw, _ = _build(STRING(), ColumnFormat("plain"), vals)
    r = ColumnFileReader(raw, STRING())
    r.read_many([5, 8000])  # first and last block only
    dense = ColumnFileReader(raw, STRING())
    dense.read_range(0, len(vals))
    assert r.counters.bytes_touched < dense.counters.bytes_touched / 1.8
    assert r.counters.blocks_skipped >= 2


def test_meta_json_records_encoding_stats(tmp_path, rnd):
    root = str(tmp_path / "d")
    schema = urlinfo_schema()
    from repro.launch.load_data import synth_crawl_records

    w = COFWriter(root, schema, split_records=256)
    w.append_all(synth_crawl_records(512))
    w.close()
    with open(os.path.join(root, "split-00000", "_meta.json")) as f:
        meta = json.load(f)
    assert "encodings" in meta
    ft = meta["encodings"]["fetchTime"]
    assert ft["blocks"] == {"delta": 1}  # fetchTime is monotone in the synth
    assert 0 < ft["encoded_bytes"] < ft["raw_bytes"]
    rep = storage_report(root)
    assert rep["fetchTime"]["blocks"] == {"delta": 2}  # both splits
    assert rep["fetchTime"]["ratio"] < 0.5
    # the report never opens a column file — only _meta.json
    assert set(rep) == set(schema.names())


def test_reads_pre_encoding_fixtures():
    """Version-1 files written by the pre-encoding-layer writer (checked-in
    fixtures) still read: scalar, batch, and gappy access."""
    with open(os.path.join(FIXTURES, "prepr_expected.json")) as f:
        exp = json.load(f)
    types = {
        "plain_int64": INT64(), "skiplist_string": STRING(),
        "cblock_zlib_string": STRING(), "dcsl_map": MAP(STRING()),
    }
    for name, typ in types.items():
        with open(os.path.join(FIXTURES, f"prepr_{name}.col"), "rb") as f:
            raw = f.read()
        r = ColumnFileReader(raw, typ)
        assert r.version == 1 and r.encoding == "legacy"
        assert _as_list(r.read_range(0, r.n)) == exp[name]
        r2 = ColumnFileReader(raw, typ)
        assert [r2.value_at(i) for i in range(0, r2.n, 13)] == exp[name][::13]
        if name == "dcsl_map":
            r3 = ColumnFileReader(raw, typ)
            assert r3.lookup_many([3, 700, 1200], "k5") == [
                exp[name][i].get("k5") for i in (3, 700, 1200)
            ]


def test_tokens_have_no_private_dictionary(tmp_path):
    """The token corpus rides the generic dict encoding: no sidecar files,
    dictionary read from the column's dict page, packed words identical to
    what unpack expects, device decode == np decode (interpret mode)."""
    from repro.data.tokens import TokenCorpus, TokenCorpusWriter
    from repro.launch.load_data import synth_token_docs

    root = str(tmp_path / "corpus")
    w = TokenCorpusWriter(root, seq_len=64, split_records=32)
    for toks, meta in synth_token_docs(60, vocab=500):
        w.add_document(toks, meta)
    w.close()
    corpus = TokenCorpus(root)
    sid = corpus.split_ids()[0]
    sdir = dict(corpus.splits)[sid]
    assert not os.path.exists(os.path.join(sdir, "tokens.dict.npy"))
    assert not os.path.exists(os.path.join(sdir, "tokens.meta.json"))
    sp = corpus.open_split(sid)
    page = sp.reader.readers["tokens"].dict_page()
    np.testing.assert_array_equal(sp.dictionary, np.asarray(page.values, np.int32))
    # the dictionary is the sorted unique token set of the split
    assert (np.diff(sp.dictionary) > 0).all()
    # generic batch read of the array column == decoded records
    sp2 = corpus.open_split(sid)
    toks, _ = sp2.record_batch(list(range(8)), decode="np")
    generic = corpus.open_split(sid).reader.readers["tokens"].read_range(0, 8)
    np.testing.assert_array_equal(toks, np.asarray(generic, np.int32))
    # device decode consumes the page words through the Pallas kernels
    sp_d = corpus.open_split(sid)
    td, md = sp_d.record_batch([1, 5, 9], decode="device")
    sp_n = corpus.open_split(sid)
    tn, mn = sp_n.record_batch([1, 5, 9], decode="np")
    np.testing.assert_array_equal(td, tn)
    np.testing.assert_array_equal(md, mn)


def test_legacy_token_corpus_still_reads(tmp_path, rnd):
    """Pre-encoding-layer corpora (BYTES token cells + tokens.dict.npy /
    tokens.meta.json sidecars, exactly what the old TokenCorpusWriter
    produced) still read through TokenSplit's legacy branch, all decode
    modes included."""
    from repro.data.tokens import (
        TokenCorpus, bits_for, legacy_token_schema, pack_bits, pack_codes,
        unpack_codes_batch,
    )

    root = str(tmp_path / "legacy")
    seq_len, n_seq = 32, 20
    seqs = [np.asarray([rnd.randint(0, 199) for _ in range(seq_len)], np.int32)
            for _ in range(n_seq)]
    dictionary = np.unique(np.concatenate(seqs))
    bits = bits_for(len(dictionary))
    code_of = {int(t): i for i, t in enumerate(dictionary)}
    # write the split exactly as the pre-PR writer did
    w = COFWriter(root, legacy_token_schema(),
                  formats={"meta": ColumnFormat("dcsl"),
                           # legacy tokens were RAW packed bytes, not v2
                           # dict pages: force plain to mimic the old cells
                           "tokens": ColumnFormat("plain", encoding="plain"),
                           "loss_mask": ColumnFormat("plain", encoding="plain")},
                  split_records=n_seq)
    for seq in seqs:
        codes = np.asarray([code_of[int(t)] for t in seq], np.uint32)
        w.append({"tokens": pack_codes(codes, bits), "n_tokens": seq_len,
                  "loss_mask": pack_bits(np.ones(seq_len, np.int32)),
                  "meta": {"doc": "legacy"}})
    w.close()
    sdir = os.path.join(root, "split-00000")
    np.save(os.path.join(sdir, "tokens.dict.npy"), dictionary.astype(np.int32))
    with open(os.path.join(sdir, "tokens.meta.json"), "w") as f:
        json.dump({"bits": bits, "seq_len": seq_len}, f)
    with open(os.path.join(root, "corpus.json"), "w") as f:
        json.dump({"seq_len": seq_len, "n_sequences": n_seq, "vocab_size": 200}, f)

    corpus = TokenCorpus(root)
    sp = corpus.open_split(0)
    assert sp.legacy
    ids = [0, 3, 4, 11]
    t_np, m = sp.record_batch(ids, decode="np")
    np.testing.assert_array_equal(t_np, np.stack([seqs[i] for i in ids]))
    assert m.shape == (len(ids), seq_len) and (m == 1).all()
    t_py, _ = corpus.open_split(0).record_batch(ids, decode="py")
    np.testing.assert_array_equal(t_py, t_np)
    t1, _ = corpus.open_split(0).record(2, decode="np")
    np.testing.assert_array_equal(t1, seqs[2])
    t_dev, m_dev = corpus.open_split(0).record_batch(ids, decode="device")
    np.testing.assert_array_equal(t_dev, t_np)
    np.testing.assert_array_equal(m_dev, m)
    words, _ = corpus.open_split(0).record_batch(ids, decode="packed")
    np.testing.assert_array_equal(
        dictionary[unpack_codes_batch(words, bits, seq_len)], t_np)
    with pytest.raises(ValueError, match="unknown decode"):
        corpus.open_split(0).record_batch(ids, decode="gpu")


def test_forced_delta_falls_back_per_block_when_inapplicable():
    """A forced delta encoding on a block whose deltas exceed 32 bits falls
    back to plain for THAT block instead of aborting the write."""
    vals = [100, 50, -3000, 7, 7, 10**12, 3, 2**61, -(2**60), 12]
    raw, w = _build(INT64(), ColumnFormat("plain", encoding="delta"), vals)
    assert w.encoding_stats()["blocks"] == {"plain": 1}
    assert _as_list(ColumnFileReader(raw, INT64()).read_range(0, len(vals))) == vals


def test_dcsl_lane_walk_matches_chain_walk(rnd):
    """The lockstep-lane in-group walker is bit-identical to the scalar
    chain walk — values, every counter, and the reader end state — at sizes
    above the lane threshold, including continuation calls."""
    from repro.core.schema import MAP

    typ = MAP(STRING())
    vals = [{f"k{rnd.randint(0, 15)}": f"v{rnd.randint(0, 99)}"
             for _ in range(rnd.randint(0, 6))} for _ in range(2600)]
    w = ColumnFileWriter(typ, ColumnFormat("dcsl"))
    for v in vals:
        w.append(v)
    raw = w.finish()
    idx1 = sorted(rnd.sample(range(1300), 600))
    idx2 = sorted(rnd.sample(range(max(idx1) + 1, 2600), 550))
    lanes, chain = ColumnFileReader(raw, typ), ColumnFileReader(raw, typ)
    assert lanes._dcsl._ensure_chain()
    out_l = lanes._dcsl._lookup_many_lanes(idx1, "k5") + \
        lanes._dcsl._lookup_many_lanes(idx2, "k5")
    assert chain._dcsl._ensure_chain()
    out_c = chain._dcsl._lookup_many_chain(idx1, "k5") + \
        chain._dcsl._lookup_many_chain(idx2, "k5")
    lanes._sync_dcsl_counters()
    chain._sync_dcsl_counters()
    assert out_l == out_c == [vals[i].get("k5") for i in idx1 + idx2]
    assert vars(lanes.counters) == vars(chain.counters)
    # and the public entry point picks the lane path at this size
    pub = ColumnFileReader(raw, typ)
    assert pub.lookup_many(idx1, "k5") == out_l[: len(idx1)]


def test_read_packed_counters_match_read_many(tmp_path):
    """The raw-page fast path reports exactly the work read_many would."""
    from repro.data.tokens import TokenCorpus, TokenCorpusWriter
    from repro.launch.load_data import synth_token_docs

    root = str(tmp_path / "corpus")
    w = TokenCorpusWriter(root, seq_len=32, split_records=64)
    for toks, meta in synth_token_docs(80, vocab=300):
        w.add_document(toks, meta)
    w.close()
    corpus = TokenCorpus(root)
    sp_a, sp_b = corpus.open_split(0), corpus.open_split(0)
    ids = [2, 3, 4, 17, 40]
    sp_a.reader.readers["tokens"].read_packed(ids)
    sp_b.reader.readers["tokens"].read_many(ids)
    assert vars(sp_a.reader.readers["tokens"].counters) == vars(
        sp_b.reader.readers["tokens"].counters
    )
    # mixing the two access styles on ONE reader neither crashes nor
    # recounts the page bytes, in either order
    rd_m = corpus.open_split(0).reader.readers["tokens"]
    rd_m.read_packed([0, 1])
    assert len(rd_m.read_range(2, 4)) == 2
    rd_n = corpus.open_split(0).reader.readers["tokens"]
    rd_n.value_at(0)
    rd_n.read_packed([2, 3])
    rd_ref = corpus.open_split(0).reader.readers["tokens"]
    rd_ref.read_many([0, 2, 3])
    assert vars(rd_n.counters) == vars(rd_ref.counters)


# -- property tests (hypothesis is an optional dep; only these skip without
# it — the deterministic tests above always run) ------------------------------

try:
    from hypothesis import given, settings, strategies as st
    _HAVE_HYPOTHESIS = True
except ImportError:
    _HAVE_HYPOTHESIS = False

if _HAVE_HYPOTHESIS:

    @given(st.lists(st.integers(-(2**62), 2**62), min_size=1, max_size=300))
    @settings(max_examples=60, deadline=None)
    def test_property_int_roundtrip_every_encoding(vals):
        for enc in ("plain", "dict", "rle", "delta"):
            payload = ENCODINGS[enc].encode(INT64(), vals)
            if payload is None:  # delta: deltas too wide to pack
                continue
            got = ENCODINGS[enc].decode_all(INT64(), payload, 0, len(payload), len(vals))
            assert _as_list(got) == vals, enc

    @given(st.lists(st.text(max_size=12), min_size=1, max_size=200))
    @settings(max_examples=60, deadline=None)
    def test_property_string_roundtrip_every_encoding(vals):
        for enc in ("plain", "dict", "rle"):
            payload = ENCODINGS[enc].encode(STRING(), vals)
            got = ENCODINGS[enc].decode_all(STRING(), payload, 0, len(payload), len(vals))
            assert _as_list(got) == vals, enc

    @given(st.lists(st.lists(st.integers(0, 5000), min_size=0, max_size=9),
                    min_size=1, max_size=80))
    @settings(max_examples=40, deadline=None)
    def test_property_array_dict_roundtrip(vals):
        payload = ENCODINGS["dict"].encode(ARRAY(INT32()), vals)
        got = ENCODINGS["dict"].decode_all(ARRAY(INT32()), payload, 0, len(payload), len(vals))
        assert got == vals

    @given(st.lists(st.integers(-(2**40), 2**40), min_size=1, max_size=300))
    @settings(max_examples=40, deadline=None)
    def test_property_plain_size_is_exact(vals):
        assert plain_size(INT64(), vals) == len(ENCODINGS["plain"].encode(INT64(), vals))

    @given(st.data())
    @settings(max_examples=40, deadline=None)
    def test_property_auto_never_loses_data(data):
        typ = data.draw(st.sampled_from([INT64(), STRING(), BYTES()]))
        if typ.kind == "int64":
            vals = data.draw(st.lists(st.integers(-(2**62), 2**62), min_size=1, max_size=200))
        elif typ.kind == "string":
            vals = data.draw(st.lists(st.text(max_size=10), min_size=1, max_size=200))
        else:
            vals = data.draw(st.lists(st.binary(max_size=12), min_size=1, max_size=200))
        name, payload, raw = encode_block(typ, vals)
        assert name in candidates(typ)
        got = ENCODINGS[name].decode_all(typ, payload, 0, len(payload), len(vals))
        assert _as_list(got) == vals
