"""The ``feed_chips`` loop: the ``feed`` loop's closed trainer, with the batch
made on the chips.

``HostPipeline`` is given the cell's batch sharding (rows over the
configuration's ``chips_per_host`` chips on the mesh axis ``data``, the axis
``fit`` shards the batch over), so each chip decodes its own rows
(``repro.data.tokens.chips_decode_batch``) and the pipeline yields
``tokens``/``labels``/``loss_mask`` already on the chips. Each batch is then
placed as ``fit`` places it, which copies nothing, and waited for; the
``bench.place_batch`` span's ``bytes`` say so. The window's rate, sample and
counts are the ``feed`` loop's, from its helpers, except that only the first
``reference.KEY_TOKENS`` ids of each row come back to the host, sliced on the
chips.

The warm-up decodes one batch of every split through the per-chip path (each
dictionary size class and code width on every chip), then runs the pipeline.
There is no other decode path: a program without the per-chip decode cannot
load this loop.
"""
from __future__ import annotations

import os
import time
from typing import Callable, Dict, List

import numpy as np

from bench import reference, spec
from bench.window import counters, reservoir_slot
from repro.data.tokens import chips_decode_batch

feed = spec.load_loop("feed", os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
LIMITS = feed.LIMITS
END_TO_END = feed.END_TO_END
check = feed.check


def host_pipeline(corpus, cfg: Dict, traffic: Dict, seed: int, sharding):
    from repro.data.pipeline import HostPipeline

    return HostPipeline(corpus, batch_per_host=cfg["batch_per_host"], seed=seed,
                        prefetch=traffic["prefetch"], decode=traffic["decode"],
                        sharding=sharding)


def run(cfg: Dict, traffic: Dict, corpus, seed: int, win, devices,
        make_pipeline: Callable = host_pipeline) -> Dict:
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    b, s = cfg["batch_per_host"], cfg["seq_len"]
    if cfg["chips_per_host"] != len(devices) or b % len(devices):
        raise ValueError(f"{b} rows over {cfg['chips_per_host']} chips a host, "
                         f"run on {len(devices)}")
    sharding = NamedSharding(Mesh(np.asarray(devices), ("data",)), P("data", None))
    pipe = make_pipeline(corpus, cfg, traffic, seed, sharding)
    it = iter(pipe)

    def deliver():
        batch = next(it)
        arrays = {k: batch[k] for k in feed.BATCH_KEYS}
        with win.spans("bench.place_batch", bytes=feed.host_bytes(arrays)):
            placed = jax.block_until_ready(jax.device_put(arrays, sharding))
        return placed, np.asarray(placed["tokens"][:, :reference.KEY_TOKENS])

    # every split's dictionary and code width on every chip, then the whole path
    rows = np.stack([np.zeros(b, np.int64), np.arange(b)], axis=1)
    for sid in corpus.split_ids():
        packed = corpus.open_split(sid).read_packed(range(b))
        jax.block_until_ready(chips_decode_batch([packed], rows, sharding))
    for _ in range(traffic["warmup_batches"]):
        deliver()
    setup_end = time.perf_counter()

    rng = np.random.default_rng([int(seed) % (1 << 64), 7])
    k = traffic["sample_batches"]
    kept: List = [None] * (feed.FIRST_KEPT + k)
    keys: List[np.ndarray] = []
    done: List[float] = []  # when each batch was delivered
    n = n_rows = short = misplaced = 0
    t0 = win.open()
    try:
        while True:
            placed, key = deliver()
            keys.append(key)
            n_rows += placed["tokens"].shape[0]
            short += b * any(v.shape != (b, s) for v in placed.values())
            misplaced += sum(v.sharding != sharding for v in placed.values())
            slot = n if n < feed.FIRST_KEPT else reservoir_slot(rng, n - feed.FIRST_KEPT, k)
            if slot is not None:
                kept[slot if n < feed.FIRST_KEPT else feed.FIRST_KEPT + slot] = (n, placed)
            n += 1
            t_end = time.perf_counter()
            done.append(t_end - t0)
            if t_end - t0 >= win.seconds:
                break
    finally:
        win.close()
    sample = [x for x in kept if x is not None]
    if n - 1 not in {i for i, _ in sample}:
        sample.append((n - 1, placed))
    pipe.stop()
    made = traffic["warmup_batches"] + n + feed._drain(pipe)
    return {
        "window_s": t_end - t0,
        "setup_end": setup_end,
        "attempted": n,
        "failed": 0,
        "feed_tokens_per_s": n_rows * s / (t_end - t0),
        "keys": np.concatenate(keys),
        "sample": sample,
        "short_rows": short,
        "misplaced": misplaced,
        "counters": counters(pipe.stats),
        "tokens_made": made * b * s,
        "batch_tokens": b * s,
        "batches_by_tenth": np.bincount(
            np.minimum((10 * np.asarray(done) / win.seconds).astype(int), 9),
            minlength=10).tolist(),
    }
