"""The benchmark's own token corpus: made from the seed, written through the
program's writer, and made again from the seed as the plain reference.

Token ids follow a Zipf law of exponent ``zipf_s`` over the whole vocabulary:
the rank of a token is the continuous Zipf (bounded Pareto) inverse of a
uniform draw, floored, so p(rank) falls as rank**-s away from the first few
ranks (for s = 1, p(r) = ln((r + 2) / (r + 1)) / ln(V + 1)), and a seeded
permutation maps ranks to ids. Split
``k`` holds ``split_records`` sequences of ``seq_len`` tokens drawn from its
own generator, so any split can be made again alone, and every seed makes the
same number of tokens of every split.
"""
from __future__ import annotations

import os
import time
from typing import Dict, List

import numpy as np

_PERM_STREAM = 1 << 40  # generator stream of the rank -> id permutation


def _seed_key(seed: int) -> int:
    return int(seed) % (1 << 64)


def rank_to_id(cfg: Dict, seed: int) -> np.ndarray:
    rng = np.random.default_rng([_seed_key(seed), _PERM_STREAM])
    return rng.permutation(cfg["vocab_size"]).astype(np.int32)


def zipf_ranks(u: np.ndarray, vocab: int, s: float) -> np.ndarray:
    """Ranks in [0, vocab) by the inverse of the continuous Zipf law
    p(x) ~ x**-s on [1, vocab + 1)."""
    if abs(s - 1.0) < 1e-12:
        x = np.exp(u * np.log(vocab + 1.0))
    else:
        a = 1.0 - s
        x = (u * ((vocab + 1.0) ** a - 1.0) + 1.0) ** (1.0 / a)
    return np.minimum(x.astype(np.int64) - 1, vocab - 1)


def split_tokens(cfg: Dict, seed: int, split: int, perm: np.ndarray) -> np.ndarray:
    """Split ``split``'s sequences, ``(split_records, seq_len)`` int32."""
    rng = np.random.default_rng([_seed_key(seed), int(split)])
    n = cfg["split_records"] * cfg["seq_len"]
    ranks = zipf_ranks(rng.random(n), cfg["vocab_size"], cfg["zipf_s"])
    return perm[ranks].reshape(cfg["split_records"], cfg["seq_len"])


def reference_corpus(cfg: Dict, seed: int) -> List[np.ndarray]:
    perm = rank_to_id(cfg, seed)
    return [split_tokens(cfg, seed, k, perm) for k in range(cfg["n_splits"])]


def sync_tree(root: str) -> None:
    """Flush every file under ``root`` to disk, so that the kernel's
    write-back of them does not land in the window."""
    for d, _, files in os.walk(root):
        for name in files:
            fd = os.open(os.path.join(d, name), os.O_RDONLY)
            try:
                os.fsync(fd)
            finally:
                os.close(fd)


def write_corpus(cfg: Dict, seed: int, root: str) -> Dict:
    """Write the corpus through ``TokenCorpusWriter``, one document per
    sequence, and flush it to disk; returns what the writing cost and
    made."""
    from repro.data.tokens import TokenCorpusWriter

    t0 = time.perf_counter()
    perm = rank_to_id(cfg, seed)
    w = TokenCorpusWriter(root, seq_len=cfg["seq_len"],
                          split_records=cfg["split_records"])
    for k in range(cfg["n_splits"]):
        for seq in split_tokens(cfg, seed, k, perm):
            w.add_document(seq)
    w.close()
    sync_tree(root)
    col_bytes = sum(
        os.path.getsize(os.path.join(root, d, "tokens.col"))
        for d in os.listdir(root) if d.startswith("split-"))
    return {"write_s": time.perf_counter() - t0, "n_sequences": w.n_sequences,
            "tokens_col_bytes": col_bytes}
