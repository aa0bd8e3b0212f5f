"""Subprocess helper for test_chips_feed.py: the per-chip token feed on four
CPU devices.

    python tests/_chips_feed_check.py <scratch dir>

Writes seeded corpora at 16-bit and 32-bit code widths and checks, on a
four-device batch sharding, that ``HostPipeline(decode="device",
sharding=...)`` yields what ``decode="np"`` yields, each device holding its
own rows; that the per-chip path emits ``decode.chips`` and copies no
byte back; and that ``fit`` steps from it as from the host feed. The
last line of standard output is a JSON object of the findings.
"""
import os
import sys

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import dataclasses  # noqa: E402
import json  # noqa: E402

import jax  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P  # noqa: E402

from repro.core import trace  # noqa: E402
from repro.data.pipeline import HostPipeline  # noqa: E402
from repro.data.tokens import TokenCorpus, TokenCorpusWriter  # noqa: E402

BATCH, N_BATCHES = 8, 5


def write_corpus(root, bits, seed=0):
    """16-bit codes: 2,000 ids over 63-token rows (a row's last word half
    used, so the decode drops padding), splits of 13 rows (batches of 8
    straddle splits, and one device's rows come from two). 32-bit codes:
    more than 65,536 distinct ids a split."""
    rng = np.random.default_rng(seed)
    if bits == 16:
        seq_len, split_records, vocab, n_seq = 63, 13, 2000, 39
    else:
        seq_len, split_records, vocab, n_seq = 128, 640, 1 << 22, 1280
    w = TokenCorpusWriter(root, seq_len=seq_len, split_records=split_records)
    for _ in range(n_seq):
        w.add_document(rng.integers(0, vocab, seq_len).astype(np.int32))
    w.close()
    corpus = TokenCorpus(root)
    assert {corpus.open_split(s).bits for s in corpus.split_ids()} == {bits}
    return corpus


def batch_sharding(devices):
    return NamedSharding(Mesh(np.asarray(devices), ("data",)), P("data", None))


def compare(corpus, sharding):
    """Per-chip batches against ``decode="np"``, array by array and shard by
    shard: counts of what differs."""
    host = iter(HostPipeline(corpus, BATCH, seed=5, prefetch=0, decode="np"))
    chips = iter(HostPipeline(corpus, BATCH, seed=5, prefetch=0, decode="device",
                              sharding=sharding))
    out = {"mismatch": 0, "not_on_chips": 0, "shard_not_own_rows": 0, "dtype": 0}
    rows_of = sharding.addressable_devices_indices_map((BATCH, 1))
    for _ in range(N_BATCHES):
        a, b = next(host), next(chips)
        for k in ("tokens", "labels", "loss_mask"):
            got = b[k]
            out["not_on_chips"] += not isinstance(got, jax.Array) or got.sharding != sharding
            out["dtype"] += got.dtype != a[k].dtype
            out["mismatch"] += int(np.sum(np.asarray(got) != a[k]))
            for shard in got.addressable_shards:
                rs = rows_of[shard.device][0]
                own = shard.index[0] == rs and np.array_equal(np.asarray(shard.data), a[k][rs])
                out["shard_not_own_rows"] += not own
    return out


def spans_of(corpus, sharding):
    with trace.tracing() as tr:
        it = iter(HostPipeline(corpus, BATCH, seed=5, prefetch=0, decode="device",
                               sharding=sharding))
        for _ in range(2):
            next(it)
    spans = [(name, args or {}) for ph, name, _ts, _dur, _tid, args, _cat, _d in tr.events()
             if ph == "X"]
    chips = [a for n, a in spans if n == "decode.chips"]
    dispatch = [a for n, a in spans if n == "decode.dispatch"]
    return {"chips_spans": len(chips), "chips_args": sorted({a.get("chips", -1) for a in chips}),
            "chips_bytes": [a.get("bytes", 0) for a in chips],
            "dispatch_bytes": [a.get("bytes", 0) for a in dispatch],
            "d2h_bytes": [a.get("bytes") for n, a in spans if n == "decode.d2h"]}


def fit_losses(corpus):
    """Losses of two ``fit`` steps on a four-device mesh, fed by the host
    feed and by the per-chip feed; the sharding ``fit`` gave the latter, and
    whether its placement of each batch passed the arrays through."""
    from repro.configs import get_config, reduced
    from repro.configs.base import ShapeConfig
    from repro.distributed.sharding import default_sharding
    from repro.launch.mesh import make_host_mesh
    from repro.training.train_loop import TrainLoopConfig, fit

    cfg = dataclasses.replace(reduced(get_config("tinyllama-1.1b")), vocab_size=2000,
                              n_layers=2, d_model=32, n_heads=2, n_kv_heads=2, head_dim=16,
                              d_ff=64)
    mesh = make_host_mesh()
    shape = ShapeConfig("t", corpus.open_split(0).seq_len, BATCH, "train")
    out = {"passed_through": []}
    device_put = jax.device_put

    def spy(x, *a, **kw):  # does fit's placement of a batch copy anything?
        y = device_put(x, *a, **kw)
        if isinstance(x, dict) and "tokens" in x:
            out["passed_through"].append(all(y[k] is x[k] for k in x))
        return y

    for decode in ("np", "device"):
        pipe = HostPipeline(corpus, BATCH, seed=5, prefetch=0, decode=decode)
        jax.device_put = spy if decode == "device" else device_put
        try:
            res = fit(cfg, mesh, default_sharding(cfg), shape, pipe,
                      TrainLoopConfig(steps=2, log_every=1), on_metrics=lambda *a: None)
        finally:
            jax.device_put = device_put
        out[decode] = [m["loss"] for m in res["history"]]
        if decode == "device":
            out["sharding_spec"] = str(pipe.sharding.spec)
            out["sharding_devices"] = len(pipe.sharding.mesh.devices.flat)
    return out


def main(tmp):
    sharding = batch_sharding(jax.devices())
    out = {"devices": len(jax.devices())}
    for bits in (16, 32):
        corpus = write_corpus(os.path.join(tmp, f"c{bits}"), bits)
        out[f"compare_{bits}"] = compare(corpus, sharding)
        if bits == 16:
            out["spans"] = spans_of(corpus, sharding)
            out["fit"] = fit_losses(corpus)
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main(sys.argv[1])
