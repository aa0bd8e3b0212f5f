"""What decides ``correct``: the timed path's output against the plain
reference, the corpus made again from the seed (``corpus.py``).

A feed is held to its configuration's guarantees:

- every batch is ``batch_per_host`` rows of ``seq_len``;
- every id of every position comes back bit-exact as int32; ``labels`` are
  the tokens shifted left by one with 0 last, and ``loss_mask`` is float32 1
  everywhere but the last position (the corpus has no padding);
- each pass over the corpus hands out every sequence once, in any order.

Two kinds of evidence are compared. The first ``KEY_TOKENS`` ids of every
row the window delivered, kept on the host in the order delivered, name the
corpus sequence each row is (a key that names none is counted); that stream
of sequences is held to the pass guarantee. A seeded sample of the batches,
kept on the device as placed, is compared whole, row by row, against the
sequences their places in the stream name.

Every number compared is a count whose limit is 0.
"""
from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import numpy as np

KEY_TOKENS = 16
FEED_COUNTS = ("short_rows", "unknown_rows", "unexplained_repeats", "token_mismatch",
               "label_mismatch", "mask_mismatch", "misplaced")


def expected_batch(rows: np.ndarray) -> Dict[str, np.ndarray]:
    labels = np.concatenate([rows[:, 1:], np.zeros((rows.shape[0], 1), np.int32)], axis=1)
    mask = np.ones(rows.shape, np.float32)
    mask[:, -1] = 0.0
    return {"tokens": rows.astype(np.int32), "labels": labels.astype(np.int32),
            "loss_mask": mask}


class CorpusIndex:
    """The corpus's sequences numbered split by split, found by key."""

    def __init__(self, splits: Sequence[np.ndarray]):
        self.splits = splits
        self.n = sum(a.shape[0] for a in splits)
        self._where: List[Tuple[int, int]] = []
        self._by_key: Dict[bytes, int] = {}
        for k, arr in enumerate(splits):
            keys = np.ascontiguousarray(arr[:, :KEY_TOKENS].astype(np.int32))
            for r in range(arr.shape[0]):
                self._by_key[keys[r].tobytes()] = len(self._where)
                self._where.append((k, r))

    def ids(self, keys: np.ndarray) -> np.ndarray:
        """The sequence each key names, -1 where it names none."""
        keys = np.ascontiguousarray(np.asarray(keys).astype(np.int32))
        if keys.ndim != 2 or keys.shape[1] != KEY_TOKENS:
            return np.full(keys.shape[0] if keys.ndim else 1, -1, np.int64)
        return np.array([self._by_key.get(k.tobytes(), -1) for k in keys], np.int64)

    def row(self, gid: int) -> np.ndarray:
        k, r = self._where[gid]
        return self.splits[k][r]


def unexplained_repeats(ids: np.ndarray, n: int) -> int:
    """Repeats of a sequence in the stream ``ids`` that no cut into passes
    explains.

    A sound stream is passes of the corpus's ``n`` sequences, each pass a
    permutation, and the window may start anywhere in a pass: cut at some
    offset ``o`` and every ``n`` rows after it, no run between two cuts holds
    a sequence twice. A sequence at ``i`` last seen at ``p`` needs a cut at a
    position in ``(p, i]``. For each offset the count of repeats that it
    leaves without one is taken, and the least of these is returned: 0 for
    a sound stream, whatever its offset. Keys that named no sequence (-1)
    are counted elsewhere and skipped here."""
    last: Dict[int, int] = {}
    diff = np.zeros(n + 1, np.int64)
    pairs = 0
    for i, g in enumerate(ids.tolist()):
        if g < 0:
            continue
        p = last.get(g)
        last[g] = i
        if p is None or i - p >= n:  # every n positions hold a cut
            continue
        pairs += 1
        a, b = (p + 1) % n, i % n  # offsets of the cuts in (p, i]
        if a <= b:
            diff[a] += 1
            diff[b + 1] -= 1
        else:
            diff[a] += 1
            diff[n] -= 1
            diff[0] += 1
            diff[b + 1] -= 1
    if not pairs:
        return 0
    return int(pairs - np.cumsum(diff[:n]).max())


def check_feed(keys: np.ndarray, sample: List[Tuple[int, Dict[str, np.ndarray]]],
               splits: Sequence[np.ndarray], batch: int, short_rows: int = 0,
               misplaced: int = 0) -> Dict[str, int]:
    """Counts of what the window got wrong.

    ``keys`` is ``(rows, KEY_TOKENS)``: the first ids of every row
    delivered, in order. ``sample`` holds ``(index of the batch in the
    window, batch)`` of the sampled batches. ``short_rows`` and
    ``misplaced`` are counted by the loop from every delivered batch's
    shapes and shardings."""
    index = CorpusIndex(splits)
    ids = index.ids(keys)
    out = {"short_rows": int(short_rows), "unknown_rows": int(np.sum(ids < 0)),
           "unexplained_repeats": unexplained_repeats(ids, index.n),
           "token_mismatch": 0, "label_mismatch": 0, "mask_mismatch": 0,
           "misplaced": int(misplaced)}
    want_dtype = {"tokens": np.int32, "labels": np.int32, "loss_mask": np.float32}
    names = {"tokens": "token_mismatch", "labels": "label_mismatch",
             "loss_mask": "mask_mismatch"}
    ats = [at for at, _ in sample]
    if len(set(ats)) != len(ats):
        raise ValueError(f"a batch sampled twice: {sorted(ats)}")
    for at, b in sample:
        # where every batch is whole (``short_rows`` counts those that are
        # not), batch ``at`` is rows ``at * batch`` on of the stream
        gids = ids[at * batch:(at + 1) * batch]
        for i, gid in enumerate(gids.tolist()):
            if gid < 0:
                continue
            want = expected_batch(index.row(gid)[None, :])
            for k, counter in names.items():
                got = np.asarray(b[k])
                if got.ndim != 2 or got.shape[0] <= i or got.dtype != want_dtype[k] \
                        or got.shape[1] != want[k].shape[1]:
                    out[counter] += int(want[k].size)
                else:
                    out[counter] += int(np.sum(got[i] != want[k][0]))
    return out


def verdict(counts: Dict[str, int], limits: Dict[str, int]
            ) -> Tuple[bool, Dict[str, Dict[str, int]]]:
    checks = {k: {"value": int(v), "limit": limits[k]} for k, v in counts.items()}
    return all(c["value"] <= c["limit"] for c in checks.values()), checks
