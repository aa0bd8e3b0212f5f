"""The feed loop's rate over the window, and what it keeps for the check."""
import time

import numpy as np
import pytest

import bench_tiny  # noqa: F401
from bench import spec, stats
from bench.window import Window
from bench_tiny import TINY


def test_finite_keeps_json_numbers():
    assert stats.finite(float("inf")) > 1e300 and stats.finite(2.5) == 2.5
    with pytest.raises(ValueError):
        stats.finite(float("nan"))


class _FixedFeed:
    """Batches of distinct rows, with a pause before each: ``rows`` rows a
    batch where given, else ``b``."""

    def __init__(self, b, s, pause, rows=None):
        self.b, self.s, self.pause, self.rows = b, s, pause, rows or b
        self.stats = type("S", (), {"__getattr__": lambda self, k: 0})()

    def __iter__(self):
        i = 0
        while True:
            time.sleep(self.pause)
            tok = np.arange(i, i + self.rows, dtype=np.int32)[:, None] + np.zeros(
                (1, self.s), np.int32)
            i += self.b
            yield {"tokens": tok, "labels": tok, "loss_mask": tok.astype(np.float32)}

    def stop(self):
        pass


class _NoSplits:
    def split_ids(self):
        return []


@pytest.mark.parametrize("rows", [None, 3])
def test_feed_rate_is_every_token_over_the_window(rows):
    import jax

    cfg = dict(TINY, zipf_s=1.0)
    b, s = cfg["batch_per_host"], cfg["seq_len"]
    traffic = {"warmup_batches": 2, "sample_batches": 4}
    feed = spec.load_loop("feed")
    win = Window(0.3, None)
    res = feed.run(cfg, traffic, _NoSplits(), 1, win, jax.devices()[:1],
                   make_pipeline=lambda *a: _FixedFeed(b, s, 0.01, rows))
    got = rows or b
    assert res["feed_tokens_per_s"] == pytest.approx(
        res["attempted"] * got * s / res["window_s"])
    assert res["window_s"] >= 0.3
    assert 10 <= res["attempted"] <= 31
    # the first 4 batches, a reservoir of 4 over the rest, and the last
    at = [i for i, _ in res["sample"]]
    assert len(set(at)) == len(at) and 8 <= len(at) <= 9
    assert {0, 1, 2, 3, res["attempted"] - 1} <= set(at) and res["misplaced"] == 0
    assert res["keys"].shape == (res["attempted"] * got, 16)
    assert res["short_rows"] == (0 if rows is None else b * res["attempted"])
