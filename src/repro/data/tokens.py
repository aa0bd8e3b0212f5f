"""Token corpora as columnar datasets (the paper's format, applied to LM data).

Documents are packed into fixed-length sequences and written via COF.  The
token column is an ``ARRAY(INT32)`` column FORCED to the generic ``dict``
encoding with one block per split, so the on-disk page is the standard
encoding-layer layout — ``[dictionary][bits][per-cell word-aligned packed
codes]`` — instead of the hand-rolled ``tokens.dict.npy`` sidecar +
packed-bytes cells earlier revisions maintained:

  split-NNNNN/
      tokens.col      ARRAY(INT32) cells, dict-encoded (one page per split)
      loss_mask.col   BYTES cells: 1 bit per position
      meta.col        MAP cells: per-sequence provenance (doc ids, source)

The dictionary and code width now live IN the column file and are read
through ``ColumnFileReader.dict_page()``; the packed code words ship to the
accelerator as-is through ``read_packed`` (the device-decode fast path).

Decode paths (Fig. 8's three worlds), ``DECODES``; any other name is a
``ValueError``:
  * decode="py"     — per-element Python loop      ("Java object churn")
  * decode="np"     — vectorized numpy shifts      ("C++ cast the buffer")
  * decode="packed" — raw packed words, caller decodes
  * decode="device" — kernels.bitunpack + dict_decode: on-device VPU unpack
    (beyond-paper: the compressed codes travel host->HBM, saving PCIe
    bandwidth; the gather runs as a Pallas kernel).  On one device
    (``device_decode_batch``) the ids come back to the host; over the chips
    of a batch sharding (``chips_decode_batch``) each chip decodes only its
    own rows and the batch stays there.

Batch fast path: ``TokenSplit.record_batch(ids)`` pulls the packed words of
the whole batch with ONE ``read_packed`` gather, then does ONE vectorized
unpack and ONE dictionary gather (or one kernel launch for
decode="device") — no per-record Python loop in front of the training step.
``TokenSplit.read_packed(ids)`` stops before the decode, for the per-chip
path.

Pre-encoding-layer corpora (tokens as BYTES + ``tokens.dict.npy`` sidecar)
still read: the root ``schema.json`` identifies them and ``TokenSplit``
keeps the legacy path.
"""
from __future__ import annotations

import json
import os
from typing import Dict, Iterator, List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

from ..core import BYTES, COFWriter, INT32, MAP, STRING, ARRAY, ColumnFormat, Schema
from ..core import trace
from ..core.cif import CIFReader, list_splits, read_schema
from ..core.encodings import (  # packing lives in the encoding layer now
    bits_for,
    pack_codes,
    unpack_codes,
    unpack_codes_batch,
)


def token_schema() -> Schema:
    return Schema([
        ("tokens", ARRAY(INT32())),
        ("n_tokens", INT32()),
        ("loss_mask", BYTES()),
        ("meta", MAP(STRING())),
    ])


def legacy_token_schema() -> Schema:
    """Pre-encoding-layer layout: packed-byte cells + dictionary sidecar."""
    return Schema([
        ("tokens", BYTES()),
        ("n_tokens", INT32()),
        ("loss_mask", BYTES()),
        ("meta", MAP(STRING())),
    ])


DECODES = ("py", "np", "packed", "device")


def check_decode(decode: str) -> str:
    """``decode`` if it names a decode world, else ``ValueError``: an unknown
    name must not fall through to the host path."""
    if decode not in DECODES:
        raise ValueError(f"unknown decode {decode!r}; known: {', '.join(DECODES)}")
    return decode


class PackedRows(NamedTuple):
    """Rows of one split as stored: packed code words ``(B, W)`` uint32, the
    split's dictionary and code width, the sequence length ``n``, and the
    loss mask's bits ``(B, ceil(n / 8))`` uint8, least significant first."""

    words: np.ndarray
    dictionary: np.ndarray
    bits: int
    n: int
    mask_bits: np.ndarray


def _device_table(dictionary: np.ndarray) -> np.ndarray:
    """The dictionary padded to a power of two, and to at least the kernel's
    ``MIN_TABLE``: every split has its own dictionary length, so the decode
    compiles once per size class, not once per split."""
    from ..kernels.dict_decode import MIN_TABLE

    size = max(MIN_TABLE, 1 << (len(dictionary) - 1).bit_length())
    table = np.zeros(size, np.int32)
    table[: len(dictionary)] = dictionary
    return table


def _decode_on(device, words: np.ndarray, bits: int, table: np.ndarray):
    """Copy a block of rows' packed words and the padded table to ``device``
    (the default device where None) and launch the kernels there:
    ``bitunpack`` (VPU shifts) and ``dict_decode``. Returns the ids, ``(rows,
    32 // bits * W)`` int32, not waited for."""
    import jax

    from ..kernels import ops

    interp = ops.interpret_mode()
    if bits == 32:  # giant dictionaries: the words already are the codes
        words = words.view(np.int32)
    codes, table = jax.device_put((words, table), device)
    if bits != 32:
        codes = ops.bitunpack(codes, bits, interpret=interp)
    return ops.dict_decode(codes, table, interpret=interp)


def device_decode_batch(words: np.ndarray, bits: int, n: int, dictionary: np.ndarray) -> np.ndarray:
    """decode="device" on one device: ship packed words to the accelerator
    as-is; the Pallas kernels bit-unpack and dictionary-gather there, and the
    ids come back. On the CPU backend the same kernels run in interpret mode.

    Spans: ``decode.dispatch`` (host to device copies and the kernel
    launches), ``decode.wait`` (the kernels running), ``decode.d2h`` (the
    decoded tokens copied back)."""
    import jax

    table = _device_table(dictionary)
    with trace.span("decode.dispatch", bytes=words.nbytes + table.nbytes):
        toks = _decode_on(None, words, bits, table)
    with trace.span("decode.wait"):
        jax.block_until_ready(toks)
    with trace.span("decode.d2h", bytes=toks.nbytes):
        return np.asarray(toks)[:, :n]


def chips_decode_batch(parts: Sequence[PackedRows], rows: np.ndarray, sharding):
    """decode="device" with the batch kept on the chips. Batch row ``i`` is
    row ``rows[i, 1]`` of ``parts[rows[i, 0]]``; ``sharding`` divides the
    batch's rows among devices and keeps each sequence whole. Each device is
    sent only its own rows' packed words and mask bits, and the table of
    each split they come from, and decodes them with the one-device path's
    kernels. Returns ``(tokens, mask_bits)``, global arrays under
    ``sharding``: ``(B, n)`` int32 and ``(B, ceil(n / 8))`` uint8.

    Spans: ``decode.chips`` (args ``chips``, and ``bytes`` sent to all of
    them), holding ``decode.dispatch`` (every copy and launch, the same
    ``bytes``), ``decode.wait``, and ``decode.d2h``: where the one-device
    path copies the ids back, this one assembles the global arrays where
    they are, so its ``bytes`` are 0."""
    import jax
    import jax.numpy as jnp

    n = parts[0].n
    if any(p.n != n for p in parts):
        raise ValueError("the rows of one batch share their length")
    b = len(rows)
    mask = np.empty((b,) + parts[0].mask_bits.shape[1:], np.uint8)
    for k, p in enumerate(parts):
        mine = rows[:, 0] == k
        mask[mine] = p.mask_bits[rows[mine, 1]]
    tables = [_device_table(p.dictionary) for p in parts]
    # every device's share, gathered on the host: per split its rows' words
    plans = []
    for dev, (rs, cs) in sharding.addressable_devices_indices_map((b, n)).items():
        if cs != slice(None):
            raise ValueError(f"a sequence must stay whole on its device: {sharding}")
        mine = rows[rs]
        ks = list(dict.fromkeys(mine[:, 0].tolist()))
        at = [np.flatnonzero(mine[:, 0] == k) for k in ks]
        pieces = [(parts[k].words[mine[i, 1]], parts[k].bits, tables[k]) for k, i in zip(ks, at)]
        plans.append((dev, pieces, np.concatenate(at), mask[rs]))
    sent = sum(w.nbytes + t.nbytes for _, pieces, _, _ in plans for w, _, t in pieces)
    sent += sum(m.nbytes for *_, m in plans)
    with trace.span("decode.chips", chips=len(plans), bytes=sent):
        with trace.span("decode.dispatch", bytes=sent):
            toks, masks = [], []
            for dev, pieces, order, m in plans:
                ts = [_decode_on(dev, *p) for p in pieces]
                if len(ts) > 1:  # rows of several splits: back into batch order
                    t = jnp.concatenate([t[:, :n] for t in ts])[np.argsort(order)]
                else:
                    t = ts[0] if ts[0].shape[1] == n else ts[0][:, :n]
                toks.append(t)
                masks.append(jax.device_put(m, dev))
        with trace.span("decode.wait"):
            jax.block_until_ready(toks)
        with trace.span("decode.d2h", bytes=0):
            return (jax.make_array_from_single_device_arrays((b, n), sharding, toks),
                    jax.make_array_from_single_device_arrays(mask.shape, sharding, masks))


def pack_bits(mask: np.ndarray) -> bytes:
    return np.packbits(mask.astype(bool), bitorder="little").tobytes()


def unpack_bits(raw: bytes, n: int) -> np.ndarray:
    return np.unpackbits(np.frombuffer(raw, np.uint8), bitorder="little")[:n].astype(np.int32)


class TokenCorpusWriter:
    """Packs document token streams into seq_len sequences.  Sequences are
    appended as raw int arrays; the dictionary + bit-packing that earlier
    revisions hand-rolled here is now the generic dict encoding: the tokens
    column is forced to ``encoding="dict"`` with ``enc_block=split_records``,
    so each split's column file is ONE self-describing dictionary page."""

    def __init__(self, root: str, seq_len: int, split_records: int = 1024):
        self.root = root
        self.seq_len = seq_len
        self.split_records = split_records
        os.makedirs(root, exist_ok=True)
        self._cof = COFWriter(
            root, token_schema(),
            formats={
                "meta": ColumnFormat("dcsl"),
                "tokens": ColumnFormat("plain", encoding="dict",
                                       enc_block=split_records),
            },
            split_records=split_records,
        )
        self._carry: List[int] = []
        self._carry_mask: List[int] = []
        self.n_sequences = 0
        self.max_token = 0

    def add_document(self, tokens: np.ndarray, meta: Optional[Dict[str, str]] = None) -> None:
        if len(tokens):
            self.max_token = max(self.max_token, int(np.max(tokens)))
        self._carry.extend(int(t) for t in tokens)
        self._carry_mask.extend([1] * len(tokens))
        while len(self._carry) >= self.seq_len:
            seq = np.asarray(self._carry[: self.seq_len], np.int32)
            msk = np.asarray(self._carry_mask[: self.seq_len], np.int32)
            del self._carry[: self.seq_len]
            del self._carry_mask[: self.seq_len]
            self._cof.append({
                "tokens": seq,
                "n_tokens": len(seq),
                "loss_mask": pack_bits(msk),
                "meta": dict(meta or {}),
            })
            self.n_sequences += 1

    def close(self) -> None:
        # drop a final partial sequence (standard LM packing) but flush splits
        self._cof.close()
        from ..core.durable import durable_write_json

        durable_write_json(
            os.path.join(self.root, "corpus.json"),
            {
                "seq_len": self.seq_len,
                "n_sequences": self.n_sequences,
                "vocab_size": self.max_token + 1,
            },
        )


class TokenSplit:
    """Reader for one split: yields (codes|tokens, loss_mask) arrays.

    The dictionary and code width come from the token column's embedded
    dict page (``dict_page()``); packed words for a batch come from ONE
    ``read_packed`` gather.  No sidecar files, no private dictionary."""

    def __init__(
        self,
        split_dir: str,
        schema: Schema,
        *,
        split_id=None,
        placement=None,
        fault_plan=None,
        policy=None,
        fail=None,
        cache=None,
    ):
        self.split_dir = split_dir
        self.legacy = schema.type_of("tokens").kind == "bytes"
        from ..core.cif import SplitReader

        with trace.span("split.open", split=split_id):
            # projection pushdown: meta.col is never opened for training
            self.reader = SplitReader(
                split_dir, schema, ["tokens", "n_tokens", "loss_mask"],
                split_id=split_id, placement=placement, fault_plan=fault_plan,
                policy=policy, fail=fail, cache=cache,
            )
            if self.legacy:
                self.dictionary = np.load(os.path.join(split_dir, "tokens.dict.npy"))
                with open(os.path.join(split_dir, "tokens.meta.json")) as f:
                    m = json.load(f)
                self.bits = m["bits"]
                self.seq_len = m["seq_len"]
            else:
                page = self.reader.readers["tokens"].dict_page()
                self.dictionary = np.asarray(page.values, np.int32)
                self.bits = page.bits
                self.seq_len = int(page.cell_lens[0]) if len(page.cell_lens) else 0

    def __len__(self) -> int:
        return self.reader.n_records

    def record(self, i: int, decode: str = "np") -> Tuple[np.ndarray, np.ndarray]:
        t, m = self.record_batch([i], decode=decode)
        return t[0], m[0]

    def read_packed(self, ids) -> PackedRows:
        """Rows ``ids`` (sorted, strictly increasing) as stored: packed words
        from one ``read_packed`` gather off the dict page (the legacy layout:
        its BYTES cells), lengths and mask bits via bulk ``read_many``."""
        ids = list(ids)
        assert all(b > a for a, b in zip(ids, ids[1:])), "ids must be strictly increasing"
        rd = self.reader.readers
        with trace.span("split.read_packed", rows=len(ids)):
            if self.legacy:
                words = rd["tokens"].read_many(ids)
                dictionary, bits = self.dictionary, self.bits
            else:
                words, dictionary, bits, n = rd["tokens"].read_packed(ids)
            ns = np.asarray(rd["n_tokens"].read_many(ids))
            msk_raw = rd["loss_mask"].read_many(ids)
            if not ids:
                return PackedRows(np.empty((0, 0), np.uint32), np.asarray(dictionary, np.int32),
                                  bits, self.seq_len, np.empty((0, 0), np.uint8))
            if self.legacy:
                n = int(ns[0])
                words = words.as_matrix().view("<u4").copy()
            assert (ns == n).all(), "sequences in one split share seq_len"
            # read_many hands back RaggedColumn views: equal-length cells gather
            # with one fancy index straight off the column-file buffer.
            return PackedRows(words, np.asarray(dictionary, np.int32), bits, n,
                              msk_raw.as_matrix())

    def record_batch(self, ids, decode: str = "np") -> Tuple[np.ndarray, np.ndarray]:
        """Batch fetch of sorted, strictly-increasing record ids.

        The batch's packed words and mask come from ``read_packed``, then the
        whole batch gets ONE vectorized unpack and ONE dictionary gather (or
        one kernel launch for decode="device").  Returns ``(tokens,
        loss_mask)`` shaped ``(B, seq_len)`` int32 — or ``(B, W)`` uint32
        packed words for decode="packed".
        """
        check_decode(decode)
        p = self.read_packed(ids)
        if not len(p.words):
            z = np.empty((0, self.seq_len), np.int32)
            return z, z.copy()
        mask = np.unpackbits(p.mask_bits, axis=1, bitorder="little")[:, :p.n].astype(np.int32)
        if decode == "packed":
            return p.words, mask
        if decode == "device":
            return device_decode_batch(p.words, p.bits, p.n, p.dictionary), mask
        codes = unpack_codes_batch(p.words, p.bits, p.n)
        if decode == "py":  # the "Java" path, for Fig. 8 benchmarks
            toks = np.asarray(
                [[int(p.dictionary[c]) for c in row] for row in codes], np.int32
            )
        else:
            toks = p.dictionary[codes]
        return toks.astype(np.int32), mask

    @property
    def position(self) -> int:
        """Lowest record id still readable by the forward-only readers."""
        return self.reader.readers["tokens"].position


class TokenCorpus:
    def __init__(self, root: str, *, placement=None, fault_plan=None,
                 failure_policy=None, cache=None):
        self.root = root
        # fault-tolerant read wiring (PR 6), threaded into every TokenSplit
        self.placement = placement
        self.fault_plan = fault_plan
        self.failure_policy = failure_policy
        # shared decoded-block cache (PR 8): every split this corpus opens
        # consults it, so training and serving pool one set of hot blocks
        self.cache = cache
        # the dataset's own schema.json tells new (ARRAY tokens) from legacy
        # (BYTES tokens + sidecar) corpora
        try:
            self.schema = read_schema(root)
        except FileNotFoundError:
            self.schema = token_schema()
        self.splits = list_splits(root)
        meta_path = os.path.join(root, "corpus.json")
        self.meta: Dict = {}
        if os.path.exists(meta_path):
            with open(meta_path) as f:
                self.meta = json.load(f)

    @property
    def vocab_size(self) -> Optional[int]:
        return self.meta.get("vocab_size")

    def open_split(self, split_id: int, *, fail=None, cache=None) -> TokenSplit:
        d = dict(self.splits)[split_id]
        return TokenSplit(
            d, self.schema, split_id=split_id, placement=self.placement,
            fault_plan=self.fault_plan, policy=self.failure_policy, fail=fail,
            cache=cache if cache is not None else self.cache,
        )

    def split_ids(self) -> List[int]:
        return [i for i, _ in self.splits]

    def split_sizes(self) -> Dict[int, int]:
        """``split_id -> n_records`` from each split's ``_meta.json`` only —
        no column file is opened or read (a host sizing the corpus must not
        pull every split's data; CPP locality starts at metadata)."""
        sizes: Dict[int, int] = {}
        for sid, sdir in self.splits:
            with open(os.path.join(sdir, "_meta.json")) as f:
                sizes[sid] = json.load(f)["n_records"]
        return sizes

    def scan_batches(
        self,
        columns: Optional[List[str]] = None,
        batch_size: int = 1024,
        host: Optional[int] = None,
        n_hosts: Optional[int] = None,
    ) -> Iterator[Dict]:
        """Sharded columnar scan over the corpus (CIF batch path): with
        ``host``/``n_hosts`` each host iterates only its CPP-local shard,
        and the union of all hosts' batches covers every sequence exactly
        once."""
        reader = CIFReader(self.root, columns=columns or ["tokens", "n_tokens"])
        yield from reader.scan_batches(batch_size=batch_size, host=host, n_hosts=n_hosts)
