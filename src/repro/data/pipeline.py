"""Per-host input pipeline: columnar corpus -> device-ready batches.

The paper's storage wins land here: projection pushdown (only the token +
mask columns are opened), lazy decode, split->host co-location (CPP analog),
and a prefetch thread so storage decode overlaps the train step.

Batches are built on the columnar fast path: sampled ``(split, record)`` ids
are grouped by split and sorted within each split, each group is fetched
with ONE ``TokenSplit.record_batch`` call (one packed-word gather off the
split's dict-encoded token page + one unpack + one dictionary gather), and
rows land in preallocated ``(B, S)`` arrays.  The dictionary itself lives
in the column file's dict page (the generic encoding layer) — no
pipeline-private dictionary sidecars.  ``decode`` selects the token decode
world: "np" (host vectorized), "py" (per-element loop, Fig. 8's slow world),
"packed" (raw words, caller decodes), or "device" (packed words are shipped
as-is and the Pallas ``bitunpack``/``dict_decode`` kernels expand them
on-accelerator); any other name is a ``ValueError``.

The sampler walks one split's records in order, so one split serves many
batches in a row. The pipeline holds the split its last batch read open and
reads the next group from it while the forward-only readers can still reach
the group's first record; otherwise (another split, the epoch wrapping onto
the split it holds, a resumed state) it opens the split afresh and drops the
one it held. Each open reads and verifies the split's files once.

With decode="device" and a ``sharding`` (the batch's ``NamedSharding``, rows
over the mesh axis ``data`` as ``fit`` builds it), the batch is made on the
chips: each chip is sent only its own rows' packed words and mask bits and
decodes them (``tokens.chips_decode_batch``), labels and the loss mask are
built row by row where the rows are, and the three arrays are yielded as
global arrays under that sharding. Nothing comes back to the host. Without a
sharding every batch is host numpy.

Batch layout: {"tokens": (B,S) int32, "labels": (B,S) int32,
               "loss_mask": (B,S) float32} — labels are next-token shifted,
with the final position masked.
"""
from __future__ import annotations

import queue
import threading
from dataclasses import dataclass, replace
from typing import Dict, Iterator, Optional, Tuple

import numpy as np

from ..core import trace
from ..core.blockcache import BlockCache
from ..core.cif import ScanStats
from ..core.colfile import ReadCounters
from ..core.placement import Placement
from .sampler import SamplerState, ShardedSampler
from .tokens import TokenCorpus, TokenSplit, check_decode, chips_decode_batch


def labels_and_mask(tokens, mask_bits):
    """labels and loss_mask of a batch from its tokens and mask bits, row by
    row: no row needs another's data, so each chip builds its own."""
    import jax.numpy as jnp

    b, n = tokens.shape
    labels = jnp.concatenate([tokens[:, 1:], jnp.zeros((b, 1), tokens.dtype)], axis=1)
    bits = (mask_bits[:, :, None] >> jnp.arange(8, dtype=jnp.uint8)) & 1
    lm = bits.reshape(b, -1)[:, :n].astype(jnp.float32)
    return labels, lm.at[:, -1].set(0.0)


@dataclass
class PipelineState:
    sampler: SamplerState

    def to_json(self):
        return {"sampler": self.sampler.to_json()}

    @staticmethod
    def from_json(d):
        return PipelineState(SamplerState.from_json(d["sampler"]))


class HostPipeline:
    def __init__(
        self,
        corpus: TokenCorpus,
        batch_per_host: int,
        n_hosts: int = 1,
        host: int = 0,
        seed: int = 0,
        prefetch: int = 2,
        state: Optional[PipelineState] = None,
        decode: str = "np",
        cache: Optional[BlockCache] = None,
        sharding=None,
    ):
        self.corpus = corpus
        self.batch = batch_per_host
        self.decode = check_decode(decode)
        if sharding is not None and decode != "device":
            raise ValueError(f"batches on the chips need decode='device', not {decode!r}")
        self.sharding = sharding
        self._layout = None  # ``labels_and_mask`` jitted for ``sharding``, on the first batch
        # decoded blocks of a split opened again (the epoch wrap, another
        # pipeline, the serving path) come back from the SHARED block cache;
        # pass the serving engine's cache to pool hot blocks across training
        # and serving.  ``stats`` holds every reader's counters, the held
        # split's included, as of the last batch (cache reuse included).
        self.cache = cache if cache is not None else BlockCache(
            self.DEFAULT_CACHE_BYTES
        )
        self.stats = ScanStats()
        # the split the last batch read, held open for the batches after it:
        # (split id, TokenSplit, each reader's counters as last folded)
        self._held: Optional[Tuple[int, TokenSplit, Dict[str, ReadCounters]]] = None
        ids = corpus.split_ids()
        # size the corpus from split metadata only — opening every split
        # would read every column file on every host (anti-CPP startup scan)
        sizes = corpus.split_sizes()
        placement = Placement(n_splits=len(ids), n_hosts=n_hosts)
        self.sampler = ShardedSampler(
            sizes, placement, host, seed=seed,
            state=state.sampler if state else None,
        )
        self._prefetch_n = prefetch
        self._made = 0  # batches made: the ordinal on each ``feed.batch`` span
        self._q: Optional[queue.Queue] = None
        self._thread: Optional[threading.Thread] = None
        self._stop = threading.Event()

    # -- core synchronous iteration ----------------------------------------
    DEFAULT_CACHE_BYTES = 64 << 20

    def _split(self, sid: int, first: int) -> Tuple[TokenSplit, int]:
        """Split ``sid`` to read from record ``first`` on, and the number of
        splits opened for it: the held split while its forward-only readers
        can still reach ``first``, else ``sid`` opened afresh and held in its
        place. An open charges its files' bytes to ``stats``, once."""
        if self._held is not None:
            held, sp, _ = self._held
            if held == sid and first >= sp.position:
                return sp, 0
        sp = self.corpus.open_split(sid, cache=self.cache)
        for r in sp.reader.readers.values():
            self.stats.bytes_io += r.file_bytes
            self.stats.files_opened += 1
        self._held = (sid, sp, {c: ReadCounters() for c in sp.reader.readers})
        return sp, 1

    def _fold(self) -> None:
        """Fold into ``stats`` what the held split's readers counted since
        the last fold."""
        _, sp, seen = self._held
        for c, r in sp.reader.readers.items():
            now = replace(r.counters)
            self.stats.absorb_since(now, seen[c])
            seen[c] = now

    def _make_batch(self) -> Dict[str, np.ndarray]:
        with trace.span("feed.batch", batch=self._made) as span:
            self._made += 1
            it = iter(self.sampler)
            draws = [next(it) for _ in range(self.batch)]
            by_split: Dict[int, list] = {}
            for slot, (sid, rid) in enumerate(draws):
                by_split.setdefault(sid, []).append((rid, slot))
            groups = []
            opened = 0
            for sid, rid_slots in by_split.items():
                # sorted ids keep the forward-only monotone readers happy; the
                # whole group decodes in one record_batch call, from the held
                # split when its readers have not passed the group's first id.
                # A batch that runs into the next split finishes the one held.
                rid_slots.sort()
                uniq = sorted({r for r, _ in rid_slots})
                sp, n = self._split(sid, uniq[0])
                opened += n
                if self.sharding is not None:
                    groups.append((rid_slots, uniq, sp.read_packed(uniq)))
                else:
                    groups.append((rid_slots, uniq, *sp.record_batch(uniq, decode=self.decode)))
                self._fold()
            span.set_metadata(opened=opened)
            if self.sharding is not None:
                return self._on_chips(groups)
            with trace.span("feed.assemble"):
                _, _, t, m = groups[0]
                tokens = np.empty((self.batch,) + t.shape[1:], t.dtype)
                mask = np.empty((self.batch,) + m.shape[1:], m.dtype)
                for rid_slots, uniq, t, m in groups:
                    row_of = {r: i for i, r in enumerate(uniq)}
                    for rid, slot in rid_slots:
                        tokens[slot] = t[row_of[rid]]
                        mask[slot] = m[row_of[rid]]
                labels = np.concatenate(
                    [tokens[:, 1:], np.zeros((tokens.shape[0], 1), np.int32)], axis=1
                )
                lm = mask.astype(np.float32)
                lm[:, -1] = 0.0
            return {"tokens": tokens, "labels": labels, "loss_mask": lm}

    def _on_chips(self, groups) -> Dict:
        """The batch decoded on the chips of ``sharding``, each its own rows."""
        import jax

        rows = np.empty((self.batch, 2), np.int64)  # (group, row in it) a slot
        for k, (rid_slots, uniq, _) in enumerate(groups):
            row_of = {r: i for i, r in enumerate(uniq)}
            for rid, slot in rid_slots:
                rows[slot] = k, row_of[rid]
        tokens, mask_bits = chips_decode_batch([p for *_, p in groups], rows, self.sharding)
        if self._layout is None:
            self._layout = jax.jit(labels_and_mask, out_shardings=(self.sharding, self.sharding))
        with trace.span("feed.assemble"):
            labels, lm = jax.block_until_ready(self._layout(tokens, mask_bits))
        return {"tokens": tokens, "labels": labels, "loss_mask": lm}

    # -- prefetching --------------------------------------------------------
    def _worker(self) -> None:
        while not self._stop.is_set():
            try:
                item = (self._make_batch(), self.state())
            except Exception as e:  # surface errors on the consumer side
                self._q.put(e)
                return
            self._q.put(item)

    def __iter__(self) -> Iterator[Dict[str, np.ndarray]]:
        if self._prefetch_n <= 0:
            while True:
                with trace.span("feed.wait"):
                    batch = self._make_batch()
                yield batch
        self._q = queue.Queue(maxsize=self._prefetch_n)
        self._thread = threading.Thread(target=self._worker, daemon=True)
        self._thread.start()
        self._last_state = self.state()
        while True:
            with trace.span("feed.wait"):
                item = self._q.get()
            if isinstance(item, Exception):
                raise item
            batch, st = item
            self._consumed_state = st
            yield batch

    def state(self) -> PipelineState:
        return PipelineState(
            SamplerState(
                self.sampler.state.epoch,
                self.sampler.state.cursor,
                self.sampler.state.record,
            )
        )

    def consumed_state(self) -> PipelineState:
        """State AFTER the last yielded batch (checkpoint this)."""
        return getattr(self, "_consumed_state", self.state())

    def stop(self) -> None:
        self._stop.set()
