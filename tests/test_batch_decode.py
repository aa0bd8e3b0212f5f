"""Batch decode fast path: `read_range`/`read_many`/`read_batch` must be
observationally identical to a scalar `value_at` loop — same values AND the
same `ReadCounters` (cells_decoded, bytes_decoded, bytes_touched, ...) — for
every column kind (plain/skiplist/cblock/dcsl) and every cell type, so the
paper's Table-1 accounting holds on the vectorized path.  Randomized with
fixed seeds (hypothesis is an optional dep; these run everywhere)."""
import random

import numpy as np
import pytest

from repro.core import ARRAY, BOOL, BYTES, FLOAT32, FLOAT64, INT32, INT64, MAP, STRING
from repro.core.cif import CIFReader
from repro.core.colfile import ColumnFileReader, ColumnFileWriter, ColumnFormat
from repro.core.cof import COFWriter
from repro.core.schema import Schema, urlinfo_schema
from repro.core.varcodec import (
    decode_range,
    decode_ragged_range,
    decode_varint_range,
    encode_cell,
    skip_range,
)
from repro.data.tokens import TokenCorpus, TokenCorpusWriter
from repro.data.pipeline import HostPipeline
from conftest import make_crawl_records

N = 2600  # spans multiple skip groups, dict blocks, and cblocks

KINDS = [
    ColumnFormat("plain"),
    ColumnFormat("skiplist"),
    ColumnFormat("cblock", codec="lzo"),
    ColumnFormat("cblock", codec="zlib"),
]


def _values(typ, rnd, n=N):
    k = typ.kind
    if k == "int32":
        return [rnd.randint(-(2**31), 2**31 - 1) for _ in range(n)]
    if k == "int64":
        return [rnd.randint(-(2**63), 2**63 - 1) for _ in range(n)]
    if k == "float32":
        return [float(np.float32(rnd.uniform(-1e6, 1e6))) for _ in range(n)]
    if k == "float64":
        return [rnd.uniform(-1e12, 1e12) for _ in range(n)]
    if k == "bool":
        return [rnd.random() < 0.5 for _ in range(n)]
    if k == "string":
        return ["x" * rnd.randint(0, 200) + str(i) for i in range(n)]
    if k == "bytes":
        return [bytes([i % 251]) * rnd.randint(0, 64) for i in range(n)]
    if k == "map":
        return [
            {f"k{rnd.randint(0, 15)}": rnd.randint(-99, 99) for _ in range(rnd.randint(0, 6))}
            for _ in range(n)
        ]
    if k == "array":
        return [
            [_values(typ.elem, rnd, 1)[0] for _ in range(rnd.randint(0, 5))]
            for _ in range(n)
        ]
    raise AssertionError(k)


def _build(typ, fmt, vals):
    w = ColumnFileWriter(typ, fmt)
    for v in vals:
        w.append(v)
    return w.finish()


def _as_list(v):
    return v.tolist() if isinstance(v, np.ndarray) else v


CELL_TYPES = [
    INT32(), INT64(), FLOAT32(), FLOAT64(), BOOL(), STRING(), BYTES(),
    MAP(INT32()), ARRAY(STRING()),
]


@pytest.mark.parametrize("fmt", KINDS, ids=lambda f: f"{f.kind}-{f.codec}")
@pytest.mark.parametrize("typ", CELL_TYPES, ids=lambda t: t.kind)
def test_read_range_matches_value_at(fmt, typ, rnd):
    vals = _values(typ, rnd)
    raw = _build(typ, ColumnFormat(fmt.kind, codec=fmt.codec), vals)
    scalar = ColumnFileReader(raw, typ)
    batch = ColumnFileReader(raw, typ)
    expect = [scalar.value_at(i) for i in range(len(vals))]
    got = _as_list(batch.read_range(0, len(vals)))
    assert got == expect == vals
    assert vars(batch.counters) == vars(scalar.counters)


@pytest.mark.parametrize("fmt", KINDS, ids=lambda f: f"{f.kind}-{f.codec}")
@pytest.mark.parametrize("typ", [INT64(), STRING(), FLOAT32()], ids=lambda t: t.kind)
def test_read_many_matches_sparse_value_at(fmt, typ, rnd):
    """Gappy monotone access: identical values and identical counters,
    including skip accounting (cells_skipped / bytes_touched)."""
    vals = _values(typ, rnd)
    raw = _build(typ, ColumnFormat(fmt.kind, codec=fmt.codec), vals)
    idx = sorted(rnd.sample(range(len(vals)), 211))
    scalar = ColumnFileReader(raw, typ)
    batch = ColumnFileReader(raw, typ)
    expect = [scalar.value_at(i) for i in idx]
    got = _as_list(batch.read_many(idx))
    assert got == expect
    assert vars(batch.counters) == vars(scalar.counters)


def test_dcsl_read_range_matches_value_at(rnd):
    typ = MAP(STRING())
    vals = [
        {f"key{rnd.randint(0, 15)}": f"v{rnd.randint(0, 99)}" for _ in range(5)}
        for _ in range(N)
    ]
    raw = _build(typ, ColumnFormat("dcsl"), vals)
    scalar = ColumnFileReader(raw, typ)
    batch = ColumnFileReader(raw, typ)
    expect = [scalar.value_at(i) for i in range(len(vals))]
    assert batch.read_range(0, len(vals)) == expect == vals
    assert vars(batch.counters) == vars(scalar.counters)
    # sparse across dictionary blocks
    idx = sorted(rnd.sample(range(len(vals)), 97))
    s2, b2 = ColumnFileReader(raw, typ), ColumnFileReader(raw, typ)
    assert b2.read_many(idx) == [s2.value_at(i) for i in idx]
    assert vars(b2.counters) == vars(s2.counters)


def test_read_range_chunked_equals_whole(rnd):
    """Monotone chunked reads compose: sum of ranges == one range."""
    vals = _values(INT64(), rnd)
    for fmt in KINDS:
        raw = _build(INT64(), ColumnFormat(fmt.kind, codec=fmt.codec), vals)
        whole = ColumnFileReader(raw, INT64()).read_range(0, len(vals))
        r = ColumnFileReader(raw, INT64())
        parts = []
        start = 0
        while start < len(vals):
            stop = min(len(vals), start + rnd.randint(1, 400))
            parts.append(r.read_range(start, stop))
            start = stop
        np.testing.assert_array_equal(np.concatenate(parts), whole)


def test_read_range_empty_and_bounds(rnd):
    vals = _values(INT32(), rnd, 50)
    raw = _build(INT32(), ColumnFormat("plain"), vals)
    r = ColumnFileReader(raw, INT32())
    assert len(r.read_range(5, 5)) == 0
    assert len(r.read_many([])) == 0
    assert r.counters.cells_decoded == 0
    assert r.read_range(10, 12).tolist() == vals[10:12]
    with pytest.raises(AssertionError):
        r.read_range(0, 5)  # monotone: reader already past 0


def test_varcodec_range_decoders_roundtrip(rnd):
    ints = [rnd.randint(-(2**63), 2**63 - 1) for _ in range(1000)]
    ints += [0, 1, -1, 2**63 - 1, -(2**63)]
    buf = bytearray()
    for v in ints:
        encode_cell(INT64(), v, buf)
    got, end = decode_varint_range(bytes(buf), 0, len(ints))
    assert got.tolist() == ints and end == len(buf)
    assert skip_range(INT64(), bytes(buf), 0, len(ints)) == len(buf)
    # ragged: offsets index the raw buffer payloads exactly
    blobs = [bytes([65 + i % 26]) * (i % 300) for i in range(400)]
    buf = bytearray()
    for v in blobs:
        encode_cell(BYTES(), v, buf)
    starts, lengths, end = decode_ragged_range(bytes(buf), 0, len(blobs))
    assert end == len(buf)
    data = bytes(buf)
    assert [data[s : s + l] for s, l in zip(starts.tolist(), lengths.tolist())] == blobs
    vals, end2 = decode_range(BYTES(), data, 0, len(blobs))
    assert vals == blobs and end2 == end


# -- split/CIF layer ---------------------------------------------------------


def test_split_read_batch_matches_scan(tmp_path):
    records = make_crawl_records(300)
    root = str(tmp_path / "d")
    w = COFWriter(
        root, urlinfo_schema(),
        formats={"metadata": ColumnFormat("dcsl"), "fetchTime": ColumnFormat("skiplist"),
                 "content": ColumnFormat("cblock", codec="zlib")},
        split_records=128,
    )
    w.append_all(records)
    w.close()
    cols = ["url", "fetchTime", "metadata", "content"]
    r = CIFReader(root, columns=cols)
    rows = []
    for batch in r.scan_batches(batch_size=50):
        vals = {n: _as_list(batch[n]) for n in cols}
        k = len(vals[cols[0]])
        rows.extend({n: vals[n][i] for n in cols} for i in range(k))
    assert rows == [{n: rec[n] for n in cols} for rec in records]
    # ScanStats parity with a record-at-a-time eager scan
    r2 = CIFReader(root, columns=cols, lazy=False)
    list(r2.scan())
    assert vars(r.stats) == vars(r2.stats)


def test_split_read_batch_sparse(tmp_path, rnd):
    records = make_crawl_records(200)
    root = str(tmp_path / "d")
    w = COFWriter(root, urlinfo_schema(), split_records=200)
    w.append_all(records)
    w.close()
    r = CIFReader(root, columns=["url", "fetchTime"])
    sr = r.open_split(r.splits()[0][1])
    idx = sorted(rnd.sample(range(200), 40))
    batch = sr.read_batch(idx)
    assert _as_list(batch["url"]) == [records[i]["url"] for i in idx]
    assert _as_list(batch["fetchTime"]) == [records[i]["fetchTime"] for i in idx]


# -- token / pipeline layer ---------------------------------------------------


@pytest.fixture(scope="module")
def corpus(tmp_path_factory):
    root = tmp_path_factory.mktemp("corpus-batch")
    w = TokenCorpusWriter(str(root), seq_len=64, split_records=32)
    from repro.launch.load_data import synth_token_docs

    for toks, meta in synth_token_docs(150, vocab=300):
        w.add_document(toks, meta)
    w.close()
    return TokenCorpus(str(root))


@pytest.mark.parametrize("decode", ["np", "py", "packed"])
def test_token_record_batch_matches_scalar(corpus, decode, rnd):
    sid = corpus.split_ids()[0]
    sp_b, sp_s = corpus.open_split(sid), corpus.open_split(sid)
    ids = sorted(rnd.sample(range(len(sp_b)), 12))
    tb, mb = sp_b.record_batch(ids, decode=decode)
    scalars = [sp_s.record(i, decode=decode) for i in ids]
    np.testing.assert_array_equal(tb, np.stack([t for t, _ in scalars]))
    np.testing.assert_array_equal(mb, np.stack([m for _, m in scalars]))
    # identical decode work reported by the column readers
    cb = {n: vars(r.counters) for n, r in sp_b.reader.readers.items()}
    cs = {n: vars(r.counters) for n, r in sp_s.reader.readers.items()}
    assert cb == cs


def test_token_record_batch_device_matches_np(corpus, rnd):
    sid = corpus.split_ids()[0]
    sp_d, sp_n = corpus.open_split(sid), corpus.open_split(sid)
    ids = sorted(rnd.sample(range(len(sp_d)), 8))
    td, md = sp_d.record_batch(ids, decode="device")
    tn, mn = sp_n.record_batch(ids, decode="np")
    np.testing.assert_array_equal(td, tn)
    np.testing.assert_array_equal(md, mn)


def test_pipeline_device_decode_matches_np(corpus):
    p_np = HostPipeline(corpus, batch_per_host=4, prefetch=0, seed=5, decode="np")
    p_dev = HostPipeline(corpus, batch_per_host=4, prefetch=0, seed=5, decode="device")
    it_np, it_dev = iter(p_np), iter(p_dev)
    for _ in range(3):
        a, b = next(it_np), next(it_dev)
        np.testing.assert_array_equal(a["tokens"], b["tokens"])
        np.testing.assert_array_equal(a["labels"], b["labels"])
        np.testing.assert_array_equal(a["loss_mask"], b["loss_mask"])


def test_pipeline_shared_block_cache_reuse(corpus):
    # a split opened again comes back from the shared BlockCache, which stays
    # within its byte budget and is metered.  The pipeline holds a split open
    # for all the batches that read it, so a split is opened again only once
    # the sampler comes back to it: draw one batch past an epoch, into the
    # next epoch's first split
    pipe = HostPipeline(corpus, batch_per_host=4, prefetch=0, seed=1)
    it = iter(pipe)
    for _ in range(sum(corpus.split_sizes().values()) // 4 + 1):
        next(it)
        assert pipe.cache.current_bytes <= pipe.cache.capacity_bytes
    assert pipe.cache.hits > 0  # revisited splits reuse decoded blocks
    assert pipe.stats.cache_hits == pipe.cache.hits
    assert pipe.stats.bytes_served_from_cache == pipe.cache.bytes_served
