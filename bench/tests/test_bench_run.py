"""Whole runs of the harness at a tiny size on the CPU: sound runs are
correct; the control, and each fault planted in the timed path, are not;
without a chip, or without the program, a run prints no result."""
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

import bench_tiny
from bench_tiny import FEED


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return bench_tiny.make_root(str(tmp_path_factory.mktemp("bench")))


def _failing(out):
    return {k: c["value"] for k, c in out["checks"].items() if c["value"] > c["limit"]}


@pytest.mark.parametrize("workload, trace", [(FEED, False), (FEED, True)])
def test_sound_run_is_correct(root, workload, trace):
    out = bench_tiny.run(root, workload, trace=trace)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out)[-1] == "checks" and out["checks"]["compiles_in_window"]["limit"] == 0
    if trace:
        assert {"io_bytes_per_token.feed", "cache_hit_pct.feed"} <= set(out["metrics"])
        assert "busy_s" in out["device"] and "breakdown" in out
    else:
        assert {"feed_tokens_per_s", "setup_s"} <= set(out["metrics"])
        assert all(m["value"] > 0 for m in out["metrics"].values())
    json.dumps(out, allow_nan=False)


@pytest.mark.parametrize("workload", [FEED])
def test_control_is_not_correct(root, workload):
    from bench import control

    out = bench_tiny.run(root, workload, loop=control.control_loop(root))
    assert not out["correct"]
    assert _failing(out), out["checks"]


def _stale_batches(orig):
    """A step that returns its state unchanged: the same batch every time."""
    def make(self):
        if not hasattr(self, "_bench_first"):
            self._bench_first = orig(self)
        return {k: v.copy() for k, v in self._bench_first.items()}
    return make


def _half_batch(orig):
    """Half of each batch left out."""
    def make(self):
        b = orig(self)
        h = b["tokens"].shape[0] // 2
        return {k: v[:h] for k, v in b.items()}
    return make


def _half_duplicated(orig):
    """Half of each batch left out, the other half sent twice."""
    def make(self):
        b = orig(self)
        h = b["tokens"].shape[0] // 2
        return {k: np.concatenate([v[:h], v[:h]]) for k, v in b.items()}
    return make


def _altered_token(orig):
    """A token altered where the batch is made, past the rows' keys."""
    def make(self):
        b = orig(self)
        b["tokens"][0, 100] += 1
        return b
    return make


def _compiles_late(orig):
    """A new program compiled for a batch made inside the window."""
    def make(self):
        import jax

        self._bench_n = getattr(self, "_bench_n", 0) + 1
        if self._bench_n > 10:
            jax.jit(lambda x: x + 1)(np.zeros(self._bench_n))
        return orig(self)
    return make


@pytest.mark.parametrize("fault, counter", [
    (_stale_batches, "unexplained_repeats"), (_half_batch, "short_rows"),
    (_half_duplicated, "unexplained_repeats"), (_altered_token, "token_mismatch"),
    (_compiles_late, "compiles_in_window"),
])
def test_feed_fault_is_not_correct(root, monkeypatch, fault, counter):
    from repro.data.pipeline import HostPipeline

    monkeypatch.setattr(HostPipeline, "_make_batch", fault(HostPipeline._make_batch))
    out = bench_tiny.run(root, FEED)
    assert not out["correct"] and counter in _failing(out), out["checks"]


def test_feed_of_one_split_is_not_correct(root, monkeypatch):
    """A feed that cycles one split, as one whose other splits never left
    the cache would, breaks the pass guarantee."""
    from repro.data.sampler import ShardedSampler

    orig = ShardedSampler._host_splits
    monkeypatch.setattr(ShardedSampler, "_host_splits", lambda self, e: orig(self, e)[:1])
    out = bench_tiny.run(root, FEED, seconds=2.0)
    assert out["attempted"] * bench_tiny.TINY["batch_per_host"] > 3 * 64
    assert not out["correct"] and "unexplained_repeats" in _failing(out), out["checks"]


def _command(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "olmoe-tokens.feed-stream",
         "--seed", str(2**31 + 1), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_chip_no_result():
    """On the CPU the run fails its device check; it never falls back."""
    p = _command(bench_tiny.ROOT)
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_benchmark_files_alone_give_no_result(tmp_path):
    shutil.copy(os.path.join(bench_tiny.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(bench_tiny.BENCH, tmp_path / "bench")
    p = _command(str(tmp_path))
    assert p.returncode != 0 and p.stdout.strip() == ""
