"""Column-file container: one file per column per split-directory (§4.2).

Layout (version 3):
         [MAGIC "RCOL"][u8 version][kind str][codec str][encoding str]
         [uvarint n_records][uvarint body_len][body]
         [u8 has_stats][stats page]                       (v3 footer)

Version 1 files (written before the encoding layer existed) have no
``encoding`` field and raw per-cell bodies; version 2 files have no stats
footer.  The reader still reads both bit-for-bit (they simply "plan as
scan everything" — see tests/test_pushdown.py and
``tests/test_encodings.py::test_reads_pre_encoding_fixtures``).

Kinds (the paper's five metadata-column layouts from Table 1 map onto these):
  plain    — self-describing encoded blocks, codec "none"        (CIF)
  skiplist — cells interleaved with skip blocks                  (CIF-SL)
  cblock   — compressed encoded blocks, codec ∈ {lzo, zlib}      (CIF-LZO/-ZLIB)
  dcsl     — dictionary-compressed skip list (map columns)       (CIF-DCSL)

Encoding layer (v2): between cell serialization (varcodec) and this
container sits ``encodings.py`` — plain / dict / RLE / delta-bitpack chosen
automatically PER BLOCK from write-time stats (or forced via
``ColumnFormat.encoding``).  For the block-structured kinds (plain, cblock)
each block is ``[u8 tag][payload]`` inside the standard compressed-block
framing (codec "none" for plain), so a reader dispatches on the tag at
block granularity.  For skiplist the whole file resolves to either the
classic per-cell stream (encoding "plain", bit-identical to v1 bodies —
the pointer-walk/lane batch fast paths still apply) or dict mode: a
dictionary page at every ``SKIPLIST_DICT_BLOCK`` boundary (aligned with the
top skip level, like DCSL) and one uvarint code per cell, so per-cell
skip/jump semantics survive.  DCSL is already its own dictionary encoding
and records encoding "plain".

Every reader exposes monotone ``value_at(index)`` plus instrumentation
counters.  ``bytes_touched`` models the paper's "Data Read" column: bytes the
reader actually traverses (skip-list jumps, undecompressed blocks, and
never-visited encoded blocks are NOT touched).

Batch fast path: ``read_range(start, stop)``/``read_many(sorted_ids)``
decode spans vectorized.  Scalar and batch access share one code path per
kind, so ``ReadCounters`` are bit-identical between a ``value_at`` loop and
the batch calls over the same records — for every encoding (enforced by
tests/test_encodings.py).

Predicate pushdown (v3): the writer emits one zone map per value block
(stats.py) into the footer, and ``ColumnFileReader`` exposes
``block_stats()`` plus ``prune(pred)`` — the surviving block/row-range set
computed WITHOUT decoding any cell and without moving any counter (pruning
is advisory; exact evaluation on the survivors has the final word).
Dict-encoded plain-kind blocks additionally resolve ``eq``/``isin``/
``contains`` leaves against their dictionary page, so whole blocks are
skipped when no dictionary entry matches — this works even on v2 files
that predate zone maps.

v3.1 (complex types + per-block filters): the stats page grows trailing
sections a v3 reader ignores bit-compatibly (the header version byte stays
3).  Each zone-map block gains a *stats-tag* — a per-block bloom filter or
the exact distinct value set for string/bytes blocks (so COMPRESSED cblock
blocks prune ``eq``/``isin``/``contains`` without an inflate call, HAIL
style), or the exact key-presence set for map columns (so a map-key
predicate ``col("metadata")["content-type"] == v`` prunes every block that
lacks the key, and surviving rows fetch just that key via the DCSL
single-key path).  See stats.py for the wire format.
"""
from __future__ import annotations

import struct
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from . import trace
from .checksum import ChecksumPage, algo_name, best_algo, crc_of
from .compression import CODECS, compress_block, decompress_block, read_block_header
from .dcsl import DICT_BLOCK, DCSLColumnReader, DCSLColumnWriter
from .errors import (
    BlockCorruptionError,
    CorruptFileError,
    FailureStats,
    SplitRetryExhausted,
)
from .encodings import (
    ENC_TAGS,
    ENCODINGS,
    TAG_NAMES,
    DictPage,
    decode_block,
    encode_block,
    plain_size,
)
from .predicate import ColumnInfo, Expr, TRI_NONE
from .schema import ColumnType
from .skiplist import SkipListReader, SkipListWriter
from .stats import (
    PruneResult,
    StatsCollector,
    ZoneMap,
    decode_stats_page,
    merge_zone_maps,
)
from .varcodec import (
    DictRaggedColumn,
    RaggedColumn,
    concat_values,
    decode_cell,
    decode_range,
    decode_ragged_lanes,
    decode_ragged_range,
    decode_uvarint_range,
    decode_varint_range,
    empty_values,
    encode_cell,
    read_uvarint,
    skip_cell,
    skip_range,
    write_uvarint,
)

MAGIC = b"RCOL"
VERSION = 3  # v1 (pre-encoding) and v2 (pre-zone-map) files remain readable

CBLOCK_RECORDS = 256  # records per compressed block (load-time knob, §5.3)
PLAIN_BLOCK_RECORDS = 2048  # records per encoded block for the plain kind
# fixed-width kinds have no per-value decode cost to amortize and only RLE as
# an alternative encoding, so they use much larger blocks — a full-column
# scan stays within a few frombuffer passes of the pre-encoding layout
FIXED_BLOCK_RECORDS = 16384
SKIPLIST_DICT_BLOCK = 1000  # dict page cadence; aligned with max skip level

# skiplist dict mode keeps cells individually skippable (one uvarint code),
# so only these per-cell-codeable kinds are eligible
SL_DICT_KINDS = ("int32", "int64", "string", "bytes")


@dataclass
class ColumnFormat:
    """Per-column storage choice, set at load time by COF."""

    kind: str = "plain"  # plain | skiplist | cblock | dcsl
    codec: str = "none"  # for cblock: lzo | zlib
    block_records: int = CBLOCK_RECORDS
    # encoding policy: "auto" selects per block from write-time stats;
    # "plain"/"dict"/"rle"/"delta" force one (the deterministic test knob)
    encoding: str = "auto"
    # records per encoded block for the plain kind (0 = PLAIN_BLOCK_RECORDS);
    # the token corpus sets this to split_records so each split is ONE
    # dict page whose packed words ship straight to the device kernels
    enc_block: int = 0

    def blocks_of(self) -> int:
        if self.kind == "cblock":
            return self.block_records
        return self.enc_block or PLAIN_BLOCK_RECORDS

    def validate(self, typ: ColumnType) -> None:
        assert self.kind in ("plain", "skiplist", "cblock", "dcsl"), self.kind
        if self.kind == "dcsl":
            assert typ.kind == "map", "dcsl requires a map column"
            assert self.encoding in ("auto", "plain"), (
                "dcsl is already dictionary-encoded; encoding must stay plain"
            )
        if self.kind == "cblock":
            assert self.codec in ("lzo", "zlib"), self.codec
        if self.kind == "skiplist":
            assert self.encoding in ("auto", "plain", "dict"), (
                f"skiplist cells must stay individually skippable; "
                f"encoding {self.encoding!r} is block-oriented"
            )
            if self.encoding == "dict":
                assert typ.kind in SL_DICT_KINDS, (
                    f"skiplist dict mode unsupported for {typ.kind}"
                )
        if self.kind in ("plain", "cblock") and self.encoding not in ("auto", "plain"):
            assert ENCODINGS[self.encoding].supports(typ), (
                f"encoding {self.encoding!r} unsupported for {typ.kind}"
            )


@dataclass
class ReadCounters:
    bytes_touched: int = 0
    bytes_decoded: int = 0
    cells_decoded: int = 0
    cells_skipped: int = 0
    blocks_decompressed: int = 0
    blocks_skipped: int = 0
    # shared block cache (blockcache.py; zero without one).  A hit advances
    # every counter above exactly as the decode would EXCEPT bytes_decoded/
    # blocks_decompressed; bytes_served_from_cache records exactly the
    # bytes_decoded the hit avoided, so
    # off.bytes_decoded == on.bytes_decoded + on.bytes_served_from_cache.
    cache_hits: int = 0
    cache_misses: int = 0
    cache_evictions: int = 0
    bytes_served_from_cache: int = 0


def _write_str(buf: bytearray, s: str) -> None:
    raw = s.encode()
    write_uvarint(buf, len(raw))
    buf += raw


def _read_str(data: bytes, off: int) -> Tuple[str, int]:
    n, off = read_uvarint(data, off)
    return data[off : off + n].decode(), off + n


def _scan_frames(body: bytes) -> List[Tuple[int, int]]:
    """Byte spans of the compressed-block frames tiling ``body`` — each
    span starts at its frame HEADER (so a frame's CRC covers the header
    bytes too) and ends where its payload ends."""
    spans: List[Tuple[int, int]] = []
    o = 0
    while o < len(body):
        _, plen, poff = read_block_header(body, o)
        spans.append((o, poff + plen))
        o = poff + plen
    return spans


def _body_block_spans(kind: str, body: bytes) -> List[Tuple[int, int]]:
    """The checksum-block grid of a body (offsets relative to the body):
    one span per compressed-block frame for the block-structured kinds,
    one whole-body span for the monolithic kinds, none for an empty body."""
    if kind in ("plain", "cblock"):
        return _scan_frames(body)
    return [(0, len(body))] if body else []


def container_block_spans(raw: bytes) -> Tuple[int, List[Tuple[int, int]]]:
    """``(body_start, spans)`` of a column file, spans ABSOLUTE into
    ``raw`` — the grid ``core.faults`` keys block-level corruption on
    (identical to the grid the writer checksums)."""
    assert raw[:4] == MAGIC, "bad column file magic"
    version = raw[4]
    off = 5
    kind, off = _read_str(raw, off)
    _, off = _read_str(raw, off)  # codec
    if version >= 2:
        _, off = _read_str(raw, off)  # encoding
    _, off = read_uvarint(raw, off)  # n_records
    body_len, off = read_uvarint(raw, off)
    if version < 2 and kind == "plain":  # v1 plain: raw per-cell body
        spans = [(0, body_len)] if body_len else []
    else:
        spans = _body_block_spans(kind, raw[off : off + body_len])
    return off, [(off + a, off + b) for a, b in spans]


# ===========================================================================
# Writers
# ===========================================================================


class ColumnFileWriter:
    def __init__(self, typ: ColumnType, fmt: ColumnFormat):
        fmt.validate(typ)
        self.typ = typ
        self.fmt = fmt
        self.n = 0
        # per-column encoding stats, persisted by COF into _meta.json
        self._stats: Dict[str, Any] = {"blocks": {}, "raw_bytes": 0, "encoded_bytes": 0}
        # zone-map collector (v3 footer); one add_block per value block.
        # _zflushed tracks how many records have already been fed to it.
        self._zone = StatsCollector(typ)
        self._zflushed = 0
        self._zwin: List[Any] = []  # streaming window (skiplist scalar kinds)
        k = fmt.kind
        if k in ("plain", "cblock"):
            self._body = bytearray()
            self._pending: List[Any] = []
            self._block_cap = fmt.blocks_of()
            if (k == "plain" and not fmt.enc_block
                    and typ.kind in ("float32", "float64", "bool")):
                self._block_cap = FIXED_BLOCK_RECORDS
        elif k == "skiplist":
            self._sl_dict_eligible = (
                fmt.encoding in ("auto", "dict") and typ.kind in SL_DICT_KINDS
            )
            if self._sl_dict_eligible:
                self._values: List[Any] = []  # resolved dict-vs-plain at finish
            else:
                self._slw = SkipListWriter(lambda v, b: encode_cell(typ, v, b))
        elif k == "dcsl":
            self._dcsl = DCSLColumnWriter(typ, block=DICT_BLOCK)

    def append(self, v: Any) -> None:
        k = self.fmt.kind
        if k in ("plain", "cblock"):
            self._pending.append(v)
            if len(self._pending) == self._block_cap:
                self._flush_block()
        elif k == "skiplist":
            if self._sl_dict_eligible:
                self._values.append(v)
            else:
                # stream stats windows (values are not retained on this path)
                if self._zone.enabled:
                    self._zwin.append(v)
                    if len(self._zwin) == SKIPLIST_DICT_BLOCK:
                        self._zone.add_block(self._zflushed, self._zwin)
                        self._zflushed += len(self._zwin)
                        self._zwin = []
                self._slw.append(v)
        elif k == "dcsl":
            # stream key-presence windows on the DICT_BLOCK grid, so the
            # stats-page blocks line up with the per-block key dictionaries
            if self._zone.enabled:
                self._zwin.append(v)
                if len(self._zwin) == DICT_BLOCK:
                    self._zone.add_block(self._zflushed, self._zwin)
                    self._zflushed += len(self._zwin)
                    self._zwin = []
            self._dcsl.append(v)
        self.n += 1

    def _flush_block(self) -> None:
        name, payload, raw = encode_block(self.typ, self._pending, self.fmt.encoding)
        codec = self.fmt.codec if self.fmt.kind == "cblock" else "none"
        # the collector sees the CHOSEN encoding: a plain-kind dict block's
        # value set is peekable in-band, so it skips the redundant stats-tag
        self._zone.add_block(self._zflushed, self._pending, enc=name, codec=codec)
        self._zflushed += len(self._pending)
        self._body += compress_block(
            codec, len(self._pending), bytes([ENC_TAGS[name]]) + payload
        )
        s = self._stats
        s["blocks"][name] = s["blocks"].get(name, 0) + 1
        s["raw_bytes"] += raw
        s["encoded_bytes"] += len(payload) + 1
        self._pending = []

    # -- skiplist resolution -------------------------------------------------
    def _sl_dict_wins(self) -> bool:
        if self.fmt.encoding == "dict":
            return True
        from .encodings import MARGIN, _uvarint_sizes  # sibling internals

        total_plain = total_dict = 0
        for i in range(0, len(self._values), SKIPLIST_DICT_BLOCK):
            block = self._values[i : i + SKIPLIST_DICT_BLOCK]
            uniq, inv = np.unique(np.asarray(block, object), return_inverse=True)
            total_plain += plain_size(self.typ, block)
            total_dict += (
                plain_size(self.typ, uniq.tolist())
                + int(_uvarint_sizes(inv.astype(np.uint64)).sum())
                + 2
            )
        return total_dict < total_plain * MARGIN

    def _finish_skiplist(self) -> Tuple[bytes, str]:
        if not self._sl_dict_eligible:
            body = self._slw.finish()
            self._stats = {"blocks": {"plain": 1}, "raw_bytes": len(body),
                           "encoded_bytes": len(body)}
            return body, "plain"
        values = self._values
        if not self._sl_dict_wins():
            slw = SkipListWriter(lambda v, b: encode_cell(self.typ, v, b))
            for v in values:
                slw.append(v)
            body = slw.finish()
            self._stats = {"blocks": {"plain": 1}, "raw_bytes": len(body),
                           "encoded_bytes": len(body)}
            return body, "plain"
        code_of: Dict[Any, int] = {}

        def hook(i: int, buf: bytearray) -> None:
            if i % SKIPLIST_DICT_BLOCK == 0:
                nonlocal code_of
                uniq = sorted(set(values[i : i + SKIPLIST_DICT_BLOCK]))
                code_of = {v: c for c, v in enumerate(uniq)}
                write_uvarint(buf, len(uniq))
                for u in uniq:
                    encode_cell(self.typ, u, buf)

        slw = SkipListWriter(
            lambda v, b: write_uvarint(b, code_of[v]), boundary_hook=hook
        )
        for v in values:
            slw.append(v)
        body = slw.finish()
        n_blocks = (len(values) + SKIPLIST_DICT_BLOCK - 1) // SKIPLIST_DICT_BLOCK
        self._stats = {
            "blocks": {"dict": n_blocks},
            "raw_bytes": plain_size(self.typ, values),
            "encoded_bytes": len(body),
        }
        return body, "dict"

    def finish(self) -> bytes:
        k = self.fmt.kind
        if k in ("plain", "cblock"):
            if self._pending:
                self._flush_block()
            body, encoding = bytes(self._body), self.fmt.encoding
        elif k == "skiplist":
            body, encoding = self._finish_skiplist()
            if self._sl_dict_eligible:
                # values were retained: feed stats windows on the same
                # grid the dict pages use (aligned with the top skip level)
                for i in range(0, len(self._values), SKIPLIST_DICT_BLOCK):
                    self._zone.add_block(i, self._values[i:i + SKIPLIST_DICT_BLOCK])
            elif self._zwin:  # streaming remainder
                self._zone.add_block(self._zflushed, self._zwin)
                self._zflushed += len(self._zwin)
                self._zwin = []
        elif k == "dcsl":
            body, encoding = self._dcsl.finish(), "plain"
            if self._zwin:  # streaming key-presence remainder
                self._zone.add_block(self._zflushed, self._zwin)
                self._zflushed += len(self._zwin)
                self._zwin = []
            self._stats = {"blocks": {"dcsl": 1}, "raw_bytes": len(body),
                           "encoded_bytes": len(body)}
        out = bytearray()
        out += MAGIC
        out.append(VERSION)
        _write_str(out, self.fmt.kind)
        _write_str(out, self.fmt.codec)
        _write_str(out, encoding)
        write_uvarint(out, self.n)
        write_uvarint(out, len(body))
        body_start = len(out)
        out += body
        # v3.2 integrity section: one CRC per checksum block (the
        # compressed-block frames for the block-structured kinds, the whole
        # body for the monolithic ones), written with zeroed meta/file CRC
        # fields and patched below once the file is byte-final.
        algo = best_algo()
        spans = _body_block_spans(self.fmt.kind, body)
        checks = ChecksumPage(
            algo, [crc_of(algo, body[a:b]) for a, b in spans]
        )
        # stats page (never empty now: it carries the checksums even for
        # kinds without zone maps)
        page = self._zone.finish(checksums=checks)
        out.append(1 if page else 0)
        out += page
        # patch pass: meta_crc covers header + stats page minus the final
        # 8 bytes (the CRC fields themselves); file_crc covers everything
        # up to its own field.  SEC_CHECKSUMS is the last section, so both
        # fields sit at the file's tail.
        fields_off = len(out) - 8
        body_end = body_start + len(body)
        meta_crc = crc_of(
            algo, bytes(out[:body_start]) + bytes(out[body_end:fields_off])
        )
        struct.pack_into("<I", out, fields_off, meta_crc)
        struct.pack_into("<I", out, fields_off + 4, crc_of(algo, out[:-4]))
        return bytes(out)

    def encoding_stats(self) -> Dict[str, Any]:
        """Per-block encoding histogram + raw-vs-encoded byte totals (the
        write-time selection made observable; COF persists this), plus the
        zone-map coverage summary when the column carries stats."""
        s = dict(self._stats)
        zone = self._zone.summary()
        if zone:
            s["zone"] = zone
        return s


# ===========================================================================
# Readers
# ===========================================================================


class ColumnFileReader:
    """Monotone reader over one column file; dispatches on the stored kind
    and, within block-structured kinds, on each block's encoding tag.

    Integrity + recovery (v3.2): when the stats page carries checksums
    (``checksum.py``), the header/stats bytes verify once at open and each
    checksum block verifies lazily on FIRST touch — before any counter
    moves, so a verified scan reports the same ``ReadCounters`` as an
    unverified one, and skipped blocks pay nothing.  A mismatch raises
    ``BlockCorruptionError`` — unless a ``fetch`` callable was supplied
    (the replica-failover seam: each call returns the next replica
    attempt's raw bytes, raising ``SplitRetryExhausted`` past the retry
    policy's cap), in which case the reader re-fetches, accepts a copy
    whose whole-file CRC verifies, and swaps the body in place (replicas
    are byte-identical, so offsets and already-decoded caches stay valid).
    ``fail`` collects checksum/retry counters shared across a split's
    readers; ``verify=False`` skips all CRC checks (the benchmark knob).
    Files without checksums (v3.1 and older) read exactly as before and
    report ``checksum == "absent"``.
    """

    def __init__(
        self,
        raw: bytes,
        typ: ColumnType,
        *,
        path: str = "<memory>",
        fail: Optional[FailureStats] = None,
        fetch: Optional[Callable[[], bytes]] = None,
        verify: bool = True,
        on_corrupt: Optional[Callable[[], None]] = None,
        cache: Optional[Any] = None,
        cache_key: Optional[Any] = None,
    ):
        self.path = path
        # tracer captured at construction (PR 9): block decode / cache-hit
        # instants identify the file by its ``split-dir/name.col`` tail,
        # which is stable across replicas and reopens
        self._tr = trace.live()
        self._tr_file = "/".join(path.replace("\\", "/").split("/")[-2:])
        # shared decoded-block cache (core.blockcache.BlockCache): consulted
        # before any block decode, keyed on (file identity, artifact, block).
        # The default file identity is the path — stable across reopens and
        # byte-identical replicas; in-memory readers must name their own key.
        self._cache = cache
        self._ckey = cache_key if cache_key is not None else path
        assert cache is None or self._ckey != "<memory>", (
            "a shared cache needs a stable identity: pass path= or cache_key="
        )
        self._fail = fail if fail is not None else FailureStats()
        self._fetch = fetch
        self._verify = verify
        # read-repair seam (PR 7): fired on EVERY checksum mismatch this
        # reader observes, at the moment the current bytes are known bad —
        # the caller (SplitReader) still knows which replica host served
        # them, so it can queue that copy for post-job healing
        self._on_corrupt = on_corrupt
        try:
            if raw[:4] != MAGIC:
                raise CorruptFileError(path, 0, "bad column file magic")
            self.version = raw[4]
            if self.version not in (1, 2, VERSION):
                raise CorruptFileError(
                    path, 4, f"unknown column file version {raw[4]}"
                )
            off = 5
            self.kind, off = _read_str(raw, off)
            self.codec, off = _read_str(raw, off)
            if self.version >= 2:
                self.encoding, off = _read_str(raw, off)
            else:
                self.encoding = "legacy"  # raw per-cell bodies, pre-encoding
            self.n, off = read_uvarint(raw, off)
            body_len, off = read_uvarint(raw, off)
        except (IndexError, struct.error, UnicodeDecodeError) as e:
            raise CorruptFileError(
                path, min(len(raw), 5), f"truncated header ({e})"
            ) from e
        self.body = raw[off : off + body_len]
        if len(self.body) != body_len:
            raise CorruptFileError(
                path, len(raw),
                f"body truncated: header promises {body_len} bytes, "
                f"{len(self.body)} present",
            )
        self._body_start = off
        self._body_len = body_len
        self.typ = typ
        self.counters = ReadCounters()
        self.file_bytes = len(raw)
        # v3 footer: advisory zone maps + optional bloom + v3.1 per-block
        # stats-tags + v3.2 checksums.  Parsing moves NO counter — stats
        # are metadata, not data read.
        self.zone_maps: Optional[List[ZoneMap]] = None
        self.bloom = None
        self.block_extras = None  # v3.1 stats-tags (None on v3-and-older)
        self._checks: Optional[ChecksumPage] = None
        soff = off + body_len
        if self.version >= 3 and soff < len(raw) and raw[soff]:
            try:
                zone_maps, self.bloom, self.block_extras, self._checks = (
                    decode_stats_page(typ, raw, soff + 1)
                )
            except (IndexError, struct.error, ValueError, UnicodeDecodeError) as e:
                raise CorruptFileError(
                    path, soff, f"unreadable stats page ({e})"
                ) from e
            # a checksums-only page decodes zero zone maps; keep the
            # "no zone maps" contract as None, like pre-v3.2 files
            self.zone_maps = zone_maps or None
        self._raw = raw if self._checks is not None else None
        self._ck_ok: set = set()
        if self._checks is not None and verify:
            self._verify_meta(raw)
        # v2+ block-structured kinds carry per-block encoding tags
        self._enc = self.version >= 2 and self.kind in ("plain", "cblock")
        self._sl_dict = self.kind == "skiplist" and self.encoding == "dict"
        if not (self._enc or self.kind == "cblock"):
            # monolithic kinds (skiplist / dcsl / v1 plain): ONE checksum
            # block spanning the whole body, verified up front — their
            # sub-readers hold views into the body, so it must be known
            # good (or replica-recovered) before _init_kind builds them.
            self._spans = [(0, len(self.body))] if self.body else []
            if self._checks is not None and verify:
                if len(self._checks.block_crcs) != len(self._spans):
                    raise CorruptFileError(
                        path, self._body_start,
                        f"{len(self._spans)} checksum block(s) expected, "
                        f"page carries {len(self._checks.block_crcs)}",
                    )
                if self._spans:
                    self._verify_block(0)
        self._init_kind()

    def _init_kind(self) -> None:
        k = self.kind
        if k == "plain" and not self._enc:
            self._pos = 0
            self._off = 0
        elif k in ("plain", "cblock") and self._enc:
            self._init_blocks()
        elif k == "skiplist":
            if self._sl_dict:
                self._sld_index = -1
                self._sld_end: Dict[int, int] = {}
                self._sld_arr: Optional[np.ndarray] = None
                self._sld_starts = self._sld_lengths = None
                self._slr = SkipListReader(
                    self.body, self.n, self._sld_decode, self._sld_skip,
                    boundary_hook=self._sld_hook,
                )
            else:
                self._slr = SkipListReader(
                    self.body,
                    self.n,
                    lambda d, o: decode_cell(self.typ, d, o),
                    lambda d, o: skip_cell(self.typ, d, o),
                )
        elif k == "cblock":  # v1 legacy: per-cell payloads
            self._init_legacy_cblock()
        elif k == "dcsl":
            self._dcsl = DCSLColumnReader(self.body, self.n, self.typ)
        else:
            raise ValueError(k)

    def _compute_blocks(
        self,
    ) -> Tuple[List[Tuple[int, int, int, int]], List[Tuple[int, int]]]:
        """Parse the compressed-block framing of the current body into
        ``(blocks, spans)`` — (n_records, payload_off, payload_len,
        first_idx) per block plus each block's (frame_start, frame_end)
        byte span (the checksum grid).  Raises ``CorruptFileError`` when
        the framing does not parse or does not tile the body."""
        blocks: List[Tuple[int, int, int, int]] = []
        spans: List[Tuple[int, int]] = []
        o, idx = 0, 0
        try:
            while o < len(self.body):
                nrec, plen, poff = read_block_header(self.body, o)
                blocks.append((nrec, poff, plen, idx))
                spans.append((o, poff + plen))
                idx += nrec
                o = poff + plen
        except (IndexError, struct.error) as e:
            raise CorruptFileError(
                self.path, self._body_start + o, f"unreadable block header ({e})"
            ) from e
        if self._checks is not None and self._verify:
            # structural guard: a damaged header could misalign every
            # following frame before any CRC gets a chance to object
            if o != len(self.body) or (
                blocks and spans[-1][1] > len(self.body)
            ):
                raise CorruptFileError(
                    self.path, self._body_start + o,
                    "block framing does not tile the body",
                )
            if len(blocks) != len(self._checks.block_crcs):
                raise CorruptFileError(
                    self.path, self._body_start,
                    f"{len(blocks)} blocks framed, page carries "
                    f"{len(self._checks.block_crcs)} checksums",
                )
        return blocks, spans

    def _scan_block_headers(self) -> None:
        """Header-only scan of the compressed-block framing (shared by the
        v2 encoded reader and the v1 legacy cblock reader): fills
        ``_blocks`` with (n_records, payload_off, payload_len, first_idx)
        and counts the header bytes as touched."""
        self._blocks, self._spans = self._compute_blocks()
        self._cur_block = -1
        self._decompress = CODECS[self.codec][1]  # resolved once per reader
        self.counters.bytes_touched += sum(
            (b - a) for a, b in self._spans
        ) - sum(b[2] for b in self._blocks)

    # -- integrity: lazy CRC verification + replica recovery ------------------
    def _note_corruption(self) -> None:
        """Count a checksum mismatch and fire the read-repair seam: the
        bytes CURRENTLY held came from a replica copy now known bad."""
        self._fail.checksum_failures += 1
        if self._on_corrupt is not None:
            self._on_corrupt()

    def _verify_meta(self, raw: bytes) -> None:
        """Verify the header+stats checksum once at open (the CRC fields
        themselves — the file's trailing 8 bytes — are excluded)."""
        ck = self._checks
        end = ck.fields_off
        body_end = self._body_start + self._body_len
        if end < body_end or end + 8 != len(raw):
            raise CorruptFileError(
                self.path, end, "checksum fields are not the file's tail"
            )
        meta = raw[: self._body_start] + raw[body_end:end]
        with trace.span("colfile.verify", bytes=len(meta)):
            got = crc_of(ck.algo, meta)
        if got != ck.meta_crc:
            self._note_corruption()
            raise BlockCorruptionError(
                self.path, 0,
                f"header/stats checksum mismatch "
                f"(stored {ck.meta_crc:#010x}, computed {got:#010x})",
            )

    def _verify_block(self, bi: int) -> None:
        """Verify checksum block ``bi`` on first touch — BEFORE any counter
        moves, so verified and unverified scans report identical
        ``ReadCounters``.  On mismatch: count it, then either recover the
        body from the next replica (``fetch`` seam) or raise."""
        ck = self._checks
        if ck is None or not self._verify or bi in self._ck_ok:
            return
        a, b = self._spans[bi]
        with trace.span("colfile.verify", bytes=b - a):
            ok = crc_of(ck.algo, self.body[a:b]) == ck.block_crcs[bi]
        if ok:
            self._ck_ok.add(bi)
            return
        self._note_corruption()
        if not self._recover_body():
            raise BlockCorruptionError(
                self.path, self._body_start + a,
                f"block {bi} checksum mismatch over bytes "
                f"[{self._body_start + a}, {self._body_start + b})",
            )

    def _recover_body(self) -> bool:
        """Replica failover: pull fresh copies through ``fetch`` until one
        whole file verifies, then swap the body in place.  Replicas are
        byte-identical, so every offset, decoded cache, and reader position
        stays valid; the block grid is re-derived in case the ORIGINAL
        copy's framing bytes were what was damaged.  Returns False when no
        fetch seam exists or the retry policy is exhausted (the caller
        raises ``BlockCorruptionError``)."""
        if self._fetch is None:
            return False
        ck = self._checks
        while True:
            try:
                raw = self._fetch()  # raises SplitRetryExhausted at the cap
            except SplitRetryExhausted:
                return False
            except OSError:
                continue  # injected/real IO error: costs one attempt
            if len(raw) != self.file_bytes:
                self._note_corruption()
                continue
            (file_crc,) = struct.unpack_from("<I", raw, len(raw) - 4)
            if crc_of(ck.algo, raw[:-4]) != file_crc:
                self._note_corruption()
                continue
            self.body = raw[self._body_start : self._body_start + self._body_len]
            self._raw = raw
            if self._enc or (self.kind == "cblock" and hasattr(self, "_blocks")):
                # rebuild the framing WITHOUT recounting header bytes; the
                # recovered copy verified whole, so every block is good
                self._blocks, self._spans = self._compute_blocks()
                if hasattr(self, "_firsts"):
                    self._firsts = np.array(
                        [blk[3] for blk in self._blocks] or [0], np.int64
                    )
            else:
                self._spans = [(0, len(self.body))] if self.body else []
            self._ck_ok = set(range(len(ck.block_crcs)))
            return True

    @property
    def checksum(self) -> str:
        """``"crc32c"``/``"crc32"`` when the file carries a v3.2 checksum
        section, ``"absent"`` for older files."""
        return algo_name(self._checks.algo) if self._checks else "absent"

    def verify_checksums(self) -> str:
        """Full integrity audit: header/stats, every block, and the
        whole-file CRC — regardless of what has been read so far.  Raises
        ``BlockCorruptionError`` on the first mismatch; returns the
        algorithm name (``"absent"`` when the file carries no checksums).
        """
        ck = self._checks
        if ck is None:
            return "absent"
        raw = self._raw
        self._verify_meta(raw)
        for bi in range(len(self._spans)):
            a, b = self._spans[bi]
            if crc_of(ck.algo, self.body[a:b]) != ck.block_crcs[bi]:
                self._note_corruption()
                raise BlockCorruptionError(
                    self.path, self._body_start + a,
                    f"block {bi} checksum mismatch",
                )
        (file_crc,) = struct.unpack_from("<I", raw, len(raw) - 4)
        if crc_of(ck.algo, raw[:-4]) != file_crc:
            self._note_corruption()
            raise BlockCorruptionError(
                self.path, len(raw) - 4, "whole-file checksum mismatch"
            )
        return algo_name(ck.algo)

    # -- v2 encoded blocks (plain + cblock share this machinery) -------------
    def _init_blocks(self) -> None:
        self._scan_block_headers()
        self._firsts = np.array([b[3] for b in self._blocks] or [0], np.int64)
        self._vals: Any = None
        self._first = 0
        self._pos = 0
        self._page: Optional[DictPage] = None
        self._page_touched = False
        # tri-state: None = no cache consulted, True/False = the parsed dict
        # page came from / missed the shared cache (read_packed charges the
        # hit-vs-decode accounting at its first-touch point)
        self._page_from_cache: Optional[bool] = None

    def _enc_load(self, bi: int) -> None:
        if bi != self._cur_block:
            # first touch of this block: CRC-check (and possibly replica-
            # recover) BEFORE any counter moves
            self._verify_block(bi)
        nrec, poff, plen, first = self._blocks[bi]
        c = self.counters
        # re-decoding the current block (read_packed touched it raw, see
        # below) must not recount its bytes
        fresh = bi != self._cur_block
        if fresh:
            c.blocks_skipped += bi - self._cur_block - 1 if self._cur_block >= 0 else bi
            c.bytes_touched += plen
            self._page_touched = True  # read_packed must not recount either
        if self._cache is not None:
            # a hit serves the decoded values without touching varcodec:
            # bytes_decoded / blocks_decompressed stay put, the avoided
            # decode bytes land in bytes_served_from_cache instead.  Only a
            # FRESH touch counts as hit/miss (uncounted re-serves of the
            # current block stay uncounted, matching the cache-off path).
            cached = self._cache.get((self._ckey, "blk", bi), c if fresh else None)
            if cached is not None:
                if fresh and self._tr is not None:
                    self._tr.instant("cache.hit",
                                     {"file": self._tr_file, "block": bi})
                self._vals = cached
                self._cur_block = bi
                self._first = first
                return
        if self.codec == "none":
            data, off, end = self.body, poff + 1, poff + plen
            tag = self.body[poff]
        else:
            payload = self._decompress(self.body[poff : poff + plen])
            if fresh:
                c.blocks_decompressed += 1
            data, off, end = payload, 1, len(payload)
            tag = payload[0]
        if fresh:
            c.bytes_decoded += end - off
            if self._tr is not None:
                # mirrors the counter: fresh decodes only, so summing the
                # "bytes" args reproduces bytes_decoded for this reader
                self._tr.instant("block.decode", {
                    "file": self._tr_file, "block": bi, "bytes": end - off,
                    "cached": self._cache is not None,
                })
        self._vals = decode_block(self.typ, tag, data, off, end, nrec)
        if self._cache is not None:
            self._cache.put((self._ckey, "blk", bi), self._vals, end - off, c)
        self._cur_block = bi
        self._first = first

    def _enc_range(self, start: int, stop: int) -> List[Any]:
        """Serve cells ``[start, stop)`` from decoded block caches.  ONE code
        path for scalar and batch access: a block is decoded (vectorized) on
        first touch and counted once; cells are counted as served/skipped —
        so a ``value_at`` loop and ``read_range`` report identical counters."""
        assert start >= self._pos, "encoded-block reader is forward-only"
        c = self.counters
        chunks: List[Any] = []
        i = start
        while i < stop:
            bi = int(np.searchsorted(self._firsts, i, side="right") - 1)
            if bi != self._cur_block or self._vals is None:
                # _vals is None when read_packed served this block raw
                self._enc_load(bi)
                nb = int(np.searchsorted(self._firsts, i, side="right") - 1)
                if nb != bi:
                    # replica recovery rebuilt the block grid (the damaged
                    # copy's framing had misplaced the boundaries): re-aim
                    self._enc_load(nb)
                    bi = nb
            nrec, _, _, first = self._blocks[bi]
            gap_from = max(self._pos, first)
            if i > gap_from:
                c.cells_skipped += i - gap_from
            k = min(stop, first + nrec) - i
            lo = i - first
            chunks.append(self._vals[lo : lo + k])
            c.cells_decoded += k
            i += k
        self._pos = stop
        return chunks

    # -- raw dict-page access (the device-decode path) ------------------------
    def _ensure_page(self) -> DictPage:
        assert self._enc and self.kind == "plain" and self.codec == "none", (
            "packed-code access needs an uncompressed plain-kind column"
        )
        assert len(self._blocks) == 1, "packed-code access needs the one-block layout"
        if self._page is None:
            self._verify_block(0)
            nrec, poff, plen, _ = self._blocks[0]
            if self._cache is not None:
                # the parsed page is the decoded artifact a reopened split
                # (PromptStore / HostPipeline) re-needs; hit-vs-decode
                # accounting is deferred to read_packed's first-touch point,
                # where bytes_decoded is normally charged
                cached = self._cache.get((self._ckey, "page", 0))
                if cached is not None:
                    self._page = cached
                    self._page_from_cache = True
                    return self._page
            tag = self.body[poff]
            assert TAG_NAMES[tag] == "dict", (
                f"packed-code access needs a dict-encoded block, got {TAG_NAMES[tag]!r}"
            )
            self._page = DictPage(self.typ, self.body, poff + 1, poff + plen, nrec)
            if self._cache is not None:
                self._page_from_cache = False
                self._cache.put(
                    (self._ckey, "page", 0), self._page, plen - 1, self.counters
                )
        return self._page

    def dict_page(self) -> DictPage:
        """Parse (and cache) the file's dictionary page WITHOUT decoding any
        cells or advancing counters — metadata access (vocab size, bits)."""
        return self._ensure_page()

    def read_packed(self, ids: Sequence[int]) -> Tuple[np.ndarray, np.ndarray, int, int]:
        """Packed code WORDS of array-dict cells ``ids`` (sorted, strictly
        increasing) -> ``(words (B, W) uint32, dictionary, bits, cell_len)``.

        This is the device-decode fast path: the words ship to the
        ``bitunpack``/``dict_decode`` Pallas kernels as-is, no host unpack.
        Counters advance exactly as ``read_many(ids)`` would (the page is
        "decoded" once on first touch; cells count as served/skipped).
        """
        page = self._ensure_page()
        assert self.typ.kind == "array", "read_packed needs array-of-int cells"
        nrec, _, plen, _ = self._blocks[0]
        c = self.counters
        if not self._page_touched:
            c.bytes_touched += plen
            if self._page_from_cache:
                # hit: the parse was skipped, so the page bytes a cache-off
                # reader decodes here are served from cache instead
                c.cache_hits += 1
                c.bytes_served_from_cache += plen - 1
            else:
                if self._page_from_cache is False:  # counted miss (cache on)
                    c.cache_misses += 1
                c.bytes_decoded += plen - 1
            self._page_touched = True
            self._cur_block = 0
        wpc = page.words_per_cell()
        cell_len = int(page.cell_lens[0]) if nrec else 0
        assert nrec == 0 or (
            (page.cell_lens == cell_len).all()
        ), "read_packed needs equal-length cells"
        w0 = int(wpc[0]) if nrec else 0
        ids = [int(i) for i in ids]
        if not ids:
            return np.empty((0, w0), np.uint32), page.values, page.bits, cell_len
        assert all(b > a for a, b in zip(ids, ids[1:])), "ids must be increasing"
        assert ids[0] >= self._pos, "encoded-block reader is forward-only"
        c.cells_skipped += (ids[-1] + 1 - self._pos) - len(ids)
        c.cells_decoded += len(ids)
        self._pos = ids[-1] + 1
        words = page.words.reshape(nrec, w0)[np.asarray(ids, np.int64)]
        return words, page.values, page.bits, cell_len

    # -- skiplist dict mode ----------------------------------------------------
    def _sld_hook(self, i: int, data: bytes, off: int) -> int:
        if i % SKIPLIST_DICT_BLOCK != 0:
            return off
        if i == self._sld_index:  # idempotent revisit
            return self._sld_end[i]
        if self._cache is not None:
            # skiplist dict pages are the kind's one block-granular decoded
            # artifact (cell spans decode exact, so caching them would skew
            # counters).  The SkipListReader's own byte counters never cover
            # hook bytes, so a hit changes NO pre-existing counter: saved=0
            # keeps the bytes_served_from_cache == avoided-bytes_decoded
            # invariant exact.
            ent = self._cache.get((self._ckey, "sld", i), self.counters)
            if ent is not None:
                self._sld_starts, self._sld_lengths, self._sld_arr, end = ent
                self._sld_index = i
                self._sld_end[i] = end
                return end
        v, o = read_uvarint(data, off)
        if self.typ.kind in ("string", "bytes"):
            self._sld_starts, self._sld_lengths, o = decode_ragged_range(data, o, v)
        else:
            arr, o = decode_varint_range(data, o, v)
            self._sld_arr = arr.astype(np.int32) if self.typ.kind == "int32" else arr
        if self._cache is not None:
            self._cache.put(
                (self._ckey, "sld", i),
                (self._sld_starts, self._sld_lengths, self._sld_arr, o),
                o - off, self.counters, saved=0,
            )
        self._sld_index = i
        self._sld_end[i] = o
        return o

    def _sld_decode(self, data: bytes, off: int) -> Tuple[Any, int]:
        code, end = read_uvarint(data, off)
        if self.typ.kind in ("string", "bytes"):
            a = int(self._sld_starts[code])
            raw = data[a : a + int(self._sld_lengths[code])]
            v = raw.decode("utf-8") if self.typ.kind == "string" else bytes(raw)
        else:
            v = int(self._sld_arr[code])
        return v, end

    @staticmethod
    def _sld_skip(data: bytes, off: int) -> int:
        while data[off] & 0x80:
            off += 1
        return off + 1

    def _sld_range_fn(self, d: bytes, o: int, cnt: int) -> Tuple[Any, int]:
        codes, end = decode_uvarint_range(d, o, cnt)
        codes = codes.astype(np.int64)
        if self.typ.kind in ("string", "bytes"):
            vals: Any = DictRaggedColumn(
                self.body, self._sld_starts, self._sld_lengths, codes, self.typ.kind
            )
        else:
            vals = self._sld_arr[codes]
        return vals, end

    # -- v1 legacy plain -------------------------------------------------------
    def _plain_at(self, index: int) -> Any:
        assert index >= self._pos, "plain reader is forward-only"
        while self._pos < index:
            new = skip_cell(self.typ, self.body, self._off)
            self.counters.bytes_touched += new - self._off
            self.counters.cells_skipped += 1
            self._off = new
            self._pos += 1
        v, end = decode_cell(self.typ, self.body, self._off)
        self.counters.bytes_touched += end - self._off
        self.counters.bytes_decoded += end - self._off
        self.counters.cells_decoded += 1
        self._off = end
        self._pos += 1
        return v

    def _plain_range(self, start: int, stop: int) -> Any:
        assert start >= self._pos, "plain reader is forward-only"
        c = self.counters
        if start > self._pos:
            new = skip_range(self.typ, self.body, self._off, start - self._pos)
            c.bytes_touched += new - self._off
            c.cells_skipped += start - self._pos
            self._off = new
            self._pos = start
        vals, end = decode_range(self.typ, self.body, self._off, stop - start)
        span = end - self._off
        c.bytes_touched += span
        c.bytes_decoded += span
        c.cells_decoded += stop - start
        self._off = end
        self._pos = stop
        return vals

    # -- v1 legacy cblock ------------------------------------------------------
    def _init_legacy_cblock(self) -> None:
        self._scan_block_headers()
        self._payload = b""
        self._intra_pos = 0
        self._intra_off = 0

    def _load_block(self, index: int) -> None:
        """Ensure the block containing ``index`` is decompressed (monotone:
        linear scan forward from the current block is fine)."""
        bi = self._cur_block
        if bi >= 0:
            nrec, _, _, first = self._blocks[bi]
            if first <= index < first + nrec:
                return
        for j in range(max(bi, 0), len(self._blocks)):
            nrec, poff, plen, first = self._blocks[j]
            if first <= index < first + nrec:
                self._verify_block(j)
                if j != bi:
                    self.counters.blocks_skipped += len(range(max(bi + 1, 0), j))
                self._payload = self._decompress(self.body[poff : poff + plen])
                self.counters.blocks_decompressed += 1
                self.counters.bytes_touched += plen
                self._cur_block = j
                self._intra_pos = first
                self._intra_off = 0
                return
        raise IndexError(index)

    def _cblock_at(self, index: int) -> Any:
        self._load_block(index)
        assert self._intra_pos <= index, "cblock reader is forward-only within block"
        while self._intra_pos < index:
            self._intra_off = skip_cell(self.typ, self._payload, self._intra_off)
            self.counters.cells_skipped += 1
            self._intra_pos += 1
        v, end = decode_cell(self.typ, self._payload, self._intra_off)
        self.counters.bytes_decoded += end - self._intra_off
        self.counters.cells_decoded += 1
        self._intra_off = end
        self._intra_pos += 1
        return v

    def _cblock_range(self, start: int, stop: int) -> List[Any]:
        """Each overlapping block is decompressed exactly once; its in-range
        payload span is bulk-decoded in one pass."""
        c = self.counters
        chunks: List[Any] = []
        i = start
        while i < stop:
            self._load_block(i)
            nrec, _, _, first = self._blocks[self._cur_block]
            assert self._intra_pos <= i, "cblock reader is forward-only within block"
            if self._intra_pos < i:
                gap = i - self._intra_pos
                self._intra_off = skip_range(self.typ, self._payload, self._intra_off, gap)
                c.cells_skipped += gap
                self._intra_pos = i
            k = min(stop, first + nrec) - i
            vals, end = decode_range(self.typ, self._payload, self._intra_off, k)
            c.bytes_decoded += end - self._intra_off
            c.cells_decoded += k
            self._intra_off = end
            self._intra_pos += k
            chunks.append(vals)
            i += k
        return chunks

    # -- predicate pushdown (advisory planning; never decodes, never counts) --
    @property
    def format_version(self) -> str:
        """Human-readable format version: ``"1"``/``"2"``/``"3"``, ``"3.1"``
        when the stats page carries per-block stats-tags, or ``"3.2"`` when
        it also carries checksums (the header version byte stays 3 — v3
        readers ignore the trailing sections bit-compatibly)."""
        if self.version == 3 and self._checks is not None:
            return "3.2"
        if self.version == 3 and self.block_extras is not None:
            return "3.1"
        return str(self.version)

    def block_stats(self) -> Optional[List[ZoneMap]]:
        """The file's zone maps, or None when it carries none (v1/v2 files,
        unsupported kinds).  Map columns carry bounds-free zone maps (the
        block grid for key-presence pruning).  Pure metadata access: no
        counter moves."""
        return self.zone_maps

    def _plan_blocks(self) -> Optional[List[Tuple[int, int]]]:
        """The (first, count) grid the planner prunes on: zone maps when
        present, else the encoded-block grid (dict-page pruning works even
        without stats).  None = no plannable structure (scan everything)."""
        if self.zone_maps:
            grid = [(z.first, z.count) for z in self.zone_maps]
        elif self._enc:
            grid = [(first, nrec) for nrec, _, _, first in self._blocks]
        else:
            return None
        if sum(c for _, c in grid) != self.n:  # defensive: grid must tile
            return None
        return grid

    def _dict_block_values(self, bi: int) -> Optional[Any]:
        """The EXACT distinct-value set of encoded block ``bi`` when it is a
        dict block that can be peeked for free (plain kind, codec none):
        the dictionary page header parses without touching any cell."""
        if not (self._enc and self.kind == "plain" and self.codec == "none"):
            return None
        if self.typ.kind not in ("int32", "int64", "string", "bytes"):
            return None
        # pruning decisions read block bytes, so a damaged dictionary could
        # prune away live rows — verify first (moves no ReadCounters)
        self._verify_block(bi)
        nrec, poff, plen, _ = self._blocks[bi]
        if TAG_NAMES[self.body[poff]] != "dict":
            return None
        page = DictPage(self.typ, self.body, poff + 1, poff + plen, nrec)
        if self.typ.kind in ("string", "bytes"):
            return RaggedColumn(self.body, page.starts, page.lengths, self.typ.kind)
        return page.values

    def _block_pieces(self, bi: int) -> Tuple[Any, Optional[str], Any, Any]:
        """The prunable evidence of block ``bi`` beyond its zone map:
        ``(values, values_src, blk_bloom, map_keys)`` where ``values_src``
        labels where the exact value set came from ("stats-tag" for a v3.1
        per-block tag, "dict-page" for a free dictionary-page peek)."""
        values = blk_bloom = map_keys = None
        values_src = None
        extra = self.block_extras[bi] if self.block_extras else None
        if extra is not None:
            tag, payload = extra
            if tag == "values":
                values, values_src = payload, "stats-tag"
            elif tag == "bloom":
                blk_bloom = payload
            elif tag == "keys":
                map_keys = payload
        if values is None:
            # the block grid follows the zone maps when both exist, and the
            # writer emits those per encoded block — indices align
            dv = (
                self._dict_block_values(bi)
                if self.zone_maps is None or self._enc else None
            )
            if dv is not None:
                values, values_src = dv, "dict-page"
        return values, values_src, blk_bloom, map_keys

    def _attribute_block(
        self, pred: Expr, known: Callable[[str], bool], zm: Optional[ZoneMap],
        bi: int,
    ) -> str:
        """EXPLAIN-only: name the single stats source that alone proves
        block ``bi`` dead, re-evaluating ``pred.tri`` with each source in
        isolation ("combined" when only their conjunction prunes).  Pure
        metadata work — no counter moves, like prune itself."""
        values, values_src, blk_bloom, map_keys = self._block_pieces(bi)
        candidates: List[Tuple[str, ColumnInfo]] = []
        if zm is not None and (zm.vmin is not None or zm.vmax is not None):
            candidates.append(("zone-map", ColumnInfo(vmin=zm.vmin, vmax=zm.vmax)))
        if values is not None and values_src is not None:
            candidates.append((values_src, ColumnInfo(values=values)))
        if blk_bloom is not None:
            candidates.append(("stats-tag", ColumnInfo(bloom=blk_bloom)))
        if map_keys is not None:
            candidates.append(("stats-tag", ColumnInfo(map_keys=map_keys)))
        if self.bloom is not None:
            candidates.append(("bloom", ColumnInfo(bloom=self.bloom)))
        for label, ci in candidates:
            if pred.tri(lambda nm, ci=ci: ci if known(nm) else None) == TRI_NONE:
                return label
        return "combined"

    def prune(
        self,
        pred: Expr,
        column: Optional[str] = None,
        sources: Optional[Dict[str, int]] = None,
    ) -> PruneResult:
        """Advisory pruning: the row ranges that MAY contain matches.

        Evaluates ``pred`` three-valued against the file-level aggregate
        (bounds + bloom), then per block against zone maps and — for
        dict-encoded plain blocks — the exact dictionary value set.  A block
        survives unless some source proves no row can match; files without
        stats survive whole.  ``column`` names the column this file stores
        (refs to other columns evaluate as unknown); with ``column=None``
        every reference is treated as this column.  Nothing is decoded and
        no counter moves — pruning is advisory, evaluation is exact.

        ``sources`` (EXPLAIN only) is an out-param dict accumulating
        ``{source-label: blocks pruned by it}`` — "zone-map", "dict-page",
        "stats-tag", "bloom", or "combined"; file-level prunes are labeled
        by the same rule.  Passing it adds re-evaluation work but changes
        neither the result nor any counter.
        """
        if self.n == 0:
            return PruneResult([], 0, 0)
        full = PruneResult([(0, self.n)], 0, 0)
        blocks = self._plan_blocks()
        if blocks is None:
            return full

        def known(name: str) -> bool:
            return column is None or name == column

        agg = merge_zone_maps(self.zone_maps) if self.zone_maps else None
        if agg is not None or self.bloom is not None:
            def file_info(name: str) -> Optional[ColumnInfo]:
                if not known(name):
                    return None
                if agg is not None:
                    return agg.info(self.bloom)
                return ColumnInfo(bloom=self.bloom)

            if pred.tri(file_info) == TRI_NONE:
                if sources is not None:
                    label = "combined"
                    cands = []
                    if agg is not None and agg.vmin is not None:
                        cands.append(("zone-map",
                                      ColumnInfo(vmin=agg.vmin, vmax=agg.vmax)))
                    if self.bloom is not None:
                        cands.append(("bloom", ColumnInfo(bloom=self.bloom)))
                    for lab, ci in cands:
                        if pred.tri(lambda nm, ci=ci:
                                    ci if known(nm) else None) == TRI_NONE:
                            label = lab
                            break
                    sources[label] = sources.get(label, 0) + len(blocks)
                return PruneResult([], len(blocks), len(blocks))

        ranges: List[Tuple[int, int]] = []
        pruned = 0
        for bi, (first, count) in enumerate(blocks):
            zm = self.zone_maps[bi] if self.zone_maps else None

            def info(name: str, zm=zm, bi=bi) -> Optional[ColumnInfo]:
                if not known(name):
                    return None
                # v3.1 per-block stats-tag: exact value set / per-block
                # bloom / map-key presence — all readable without touching
                # (let alone decompressing) the block itself
                values, _src, blk_bloom, map_keys = self._block_pieces(bi)
                ci = ColumnInfo(
                    vmin=zm.vmin if zm else None,
                    vmax=zm.vmax if zm else None,
                    values=values,
                    bloom=blk_bloom if blk_bloom is not None else self.bloom,
                    map_keys=map_keys,
                )
                if (ci.vmin is None and ci.values is None and ci.bloom is None
                        and ci.map_keys is None):
                    return None
                return ci

            if pred.tri(info) == TRI_NONE:
                pruned += 1
                if sources is not None:
                    label = self._attribute_block(pred, known, zm, bi)
                    sources[label] = sources.get(label, 0) + 1
            elif ranges and ranges[-1][1] == first:
                ranges[-1] = (ranges[-1][0], first + count)
            else:
                ranges.append((first, first + count))
        if self._tr is not None:
            self._tr.instant("prune.file", {
                "file": self._tr_file, "blocks_total": len(blocks),
                "blocks_pruned": pruned,
            })
        return PruneResult(ranges, len(blocks), pruned)

    # -- public -------------------------------------------------------------------
    def value_at(self, index: int) -> Any:
        assert 0 <= index < self.n, (index, self.n)
        k = self.kind
        if self._enc:  # v2 plain/cblock: serve from the decoded block cache
            v = self._enc_range(index, index + 1)[0][0]
            return v.item() if isinstance(v, np.generic) else v
        if k == "plain":
            return self._plain_at(index)
        if k == "skiplist":
            v = self._slr.value_at(index)
            self._sync_sl_counters()
            return v
        if k == "cblock":
            return self._cblock_at(index)
        if k == "dcsl":
            v = self._dcsl.value_at(index)
            self._sync_dcsl_counters()
            return v
        raise ValueError(k)

    def read_range(self, start: int, stop: int) -> Any:
        """Bulk-decode records ``[start, stop)`` — the batch fast path.

        Values come back as a NumPy array for numeric/bool columns, a
        zero-copy ``RaggedColumn`` (or ``DictRaggedColumn`` for dict-encoded
        blocks) view for string/bytes columns, and a Python list otherwise.
        Access must be monotone, exactly like ``value_at``; counters advance
        by the same aggregate amounts a scalar loop over the span would.
        """
        assert 0 <= start <= stop <= self.n, (start, stop, self.n)
        if start == stop:
            return empty_values(self.typ)
        k = self.kind
        if self._enc:
            return concat_values(self.typ, self._enc_range(start, stop))
        if k == "plain":
            return self._plain_range(start, stop)
        if k == "skiplist":
            if self._sl_dict:
                chunks = self._slr.read_range(start, stop, self._sld_range_fn)
                self._sync_sl_counters()
                return concat_values(self.typ, chunks)
            lanes = None
            if self.typ.kind in ("string", "bytes"):
                kind = self.typ.kind

                def lanes(d, offs, counts):
                    s, l, ends = decode_ragged_lanes(d, offs, counts)
                    return RaggedColumn(d, s, l, kind), ends

            chunks = self._slr.read_range(
                start, stop,
                lambda d, o, n: decode_range(self.typ, d, o, n),
                range_decode_lanes=lanes,
            )
            self._sync_sl_counters()
            return concat_values(self.typ, chunks)
        if k == "cblock":
            return concat_values(self.typ, self._cblock_range(start, stop))
        if k == "dcsl":
            vals = self._dcsl.read_range(start, stop)
            self._sync_dcsl_counters()
            return vals
        raise ValueError(k)

    def read_many(self, indices: Sequence[int]) -> Any:
        """Batch-decode a sorted, strictly-increasing index set: contiguous
        runs become ``read_range`` calls; gaps are skipped exactly as a
        scalar monotone loop would skip them."""
        idx = list(indices)
        if not idx:
            return empty_values(self.typ)
        chunks: List[Any] = []
        i = 0
        while i < len(idx):
            j = i
            while j + 1 < len(idx) and idx[j + 1] == idx[j] + 1:
                j += 1
            chunks.append(self.read_range(idx[i], idx[j] + 1))
            i = j + 1
        return concat_values(self.typ, chunks)

    @property
    def position(self) -> int:
        """Lowest index still readable by this monotone reader."""
        k = self.kind
        if self._enc:
            return self._pos
        if k == "plain":
            return self._pos
        if k == "skiplist":
            return self._slr.pos
        if k == "cblock":
            return self._intra_pos if self._cur_block >= 0 else 0
        if k == "dcsl":
            return self._dcsl.position
        raise ValueError(k)

    def lookup(self, index: int, key: str) -> Optional[Any]:
        """Single-key access for map columns (DCSL fast path; others decode)."""
        if self.kind == "dcsl":
            v = self._dcsl.lookup(index, key)
            self._sync_dcsl_counters()
            return v
        m = self.value_at(index)
        return m.get(key) if isinstance(m, dict) else None

    def lookup_many(self, indices: Sequence[int], key: str) -> List[Optional[Any]]:
        """Batched sparse single-key access over a strictly-increasing index
        set.  DCSL hops its skip-pointer chain between groups and walks
        in-group cells in vectorized lockstep lanes; other kinds fall back
        to a lookup loop."""
        if self.kind == "dcsl":
            vals = self._dcsl.lookup_many(indices, key)
            self._sync_dcsl_counters()
            return vals
        return [self.lookup(i, key) for i in indices]

    def _sync_sl_counters(self, slr: Optional[SkipListReader] = None) -> None:
        s = slr if slr is not None else self._slr
        c = self.counters
        c.cells_decoded = s.cells_decoded
        c.cells_skipped = s.cells_skipped
        c.bytes_decoded = s.bytes_decoded
        # touched = decoded + single-step-skipped cell bytes + skip-entry bytes
        # actually visited; jumped-over regions are never touched (§5.2).
        c.bytes_touched = s.bytes_decoded + s.bytes_skipped + s.bytes_entries

    def _sync_dcsl_counters(self) -> None:
        self._sync_sl_counters(self._dcsl.counters)
