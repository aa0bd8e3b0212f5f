"""The ``feed`` loop: a closed-loop trainer.

``HostPipeline`` over the corpus yields batches; each is placed on the chips
as ``training.train_loop.fit`` places it (``jax.device_put`` of
``tokens``/``labels``/``loss_mask`` to the batch sharding) and waited for.
The window's rate is every row of every batch delivered, as placed, times
the sequence length, over the time from the window's start to the last
delivery. In a traced run each placement is a ``bench.place_batch`` span
whose ``bytes`` are what it copies from the host.

For the check it keeps the first ids of every delivered row on the host, the
shape and sharding of every delivered batch, and a seeded sample of the
batches as placed: the window's first, a reservoir over the rest, and the
last.
"""
from __future__ import annotations

import queue
import time
from typing import Callable, Dict, List

import numpy as np

from bench import reference
from bench.window import Window, counters, reservoir_slot

BATCH_KEYS = ("tokens", "labels", "loss_mask")
FIRST_KEPT = 4  # the window's first batches are always in the sample
LIMITS = {k: 0 for k in reference.FEED_COUNTS}
END_TO_END: Dict[str, Callable[[Dict], float]] = {
    "feed_tokens_per_s": lambda r: r["feed_tokens_per_s"],
}


def host_pipeline(corpus, cfg: Dict, traffic: Dict, seed: int):
    from repro.data.pipeline import HostPipeline

    return HostPipeline(corpus, batch_per_host=cfg["batch_per_host"], seed=seed,
                        prefetch=traffic["prefetch"], decode=traffic["decode"])


def host_bytes(arrays: Dict) -> int:
    """Bytes that placing ``arrays`` copies from the host: arrays already on
    a device count 0."""
    return sum(v.nbytes for v in arrays.values() if isinstance(v, np.ndarray))


def _drain(pipe) -> int:
    """Batches the prefetch thread makes after ``stop()``. The thread is not
    joined by ``stop()``: take what it queues until it ends, so that the
    counters and the count of batches made are one snapshot."""
    made = 0
    thread, q = getattr(pipe, "_thread", None), getattr(pipe, "_q", None)
    while thread is not None and thread.is_alive():
        try:
            item = q.get(timeout=0.05)
        except queue.Empty:  # the thread is finishing a batch
            continue
        made += not isinstance(item, Exception)
    while q is not None and not q.empty():
        made += not isinstance(q.get_nowait(), Exception)
    return made


def run(cfg: Dict, traffic: Dict, corpus, seed: int, win: Window, devices,
        make_pipeline: Callable = host_pipeline) -> Dict:
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    b, s = cfg["batch_per_host"], cfg["seq_len"]
    pipe = make_pipeline(corpus, cfg, traffic, seed)
    sharding = NamedSharding(Mesh(np.asarray(devices), ("data",)), P("data", None))
    it = iter(pipe)

    def deliver():
        batch = next(it)
        host = {k: batch[k] for k in BATCH_KEYS}
        with win.spans("bench.place_batch", bytes=host_bytes(host)):
            placed = jax.device_put(host, sharding)
            return batch, jax.block_until_ready(placed)

    # every split's dictionary and code width, then the whole path
    for sid in corpus.split_ids():
        corpus.open_split(sid).record_batch(list(range(b)), decode=traffic["decode"])
    for _ in range(traffic["warmup_batches"]):
        deliver()
    setup_end = time.perf_counter()

    rng = np.random.default_rng([int(seed) % (1 << 64), 7])
    k = traffic["sample_batches"]
    kept: List = [None] * (FIRST_KEPT + k)
    keys: List[np.ndarray] = []
    done: List[float] = []  # when each batch was delivered
    n = rows = short = misplaced = 0
    t0 = win.open()
    try:
        while True:
            batch, placed = deliver()
            keys.append(np.array(np.asarray(batch["tokens"])[:, :reference.KEY_TOKENS]))
            rows += placed["tokens"].shape[0]
            short += b * any(v.shape != (b, s) for v in placed.values())
            misplaced += sum(v.sharding != sharding for v in placed.values())
            slot = n if n < FIRST_KEPT else reservoir_slot(rng, n - FIRST_KEPT, k)
            if slot is not None:
                kept[slot if n < FIRST_KEPT else FIRST_KEPT + slot] = (n, placed)
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
    made = traffic["warmup_batches"] + n + _drain(pipe)
    return {
        "window_s": t_end - t0,
        "setup_end": setup_end,
        "attempted": n,
        "failed": 0,
        "feed_tokens_per_s": rows * s / (t_end - t0),
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


def check(res: Dict, splits, cfg: Dict) -> Dict[str, int]:
    """The window's counts, with the sample copied to the host."""
    sample = [(i, {k: np.asarray(v) for k, v in x.items()}) for i, x in res.pop("sample")]
    return reference.check_feed(res.pop("keys"), sample, splits, cfg["batch_per_host"],
                                res["short_rows"], res["misplaced"])
