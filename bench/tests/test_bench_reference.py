"""The comparison that decides ``correct``: sound output passes, and each
fault a feed can have is counted."""
import numpy as np
import pytest

import bench_tiny  # noqa: F401
from bench import corpus, reference
from bench_tiny import TINY

CFG = dict(TINY, zipf_s=1.0)
SPLITS = corpus.reference_corpus(CFG, 11)
B = CFG["batch_per_host"]
N = sum(a.shape[0] for a in SPLITS)
K = reference.KEY_TOKENS


def rows_of(order):
    """The corpus's sequences in the order of their numbers, split by split."""
    flat = np.concatenate(SPLITS)
    return flat[np.asarray(order)]


def passes(n_passes, offset=0, seed=0):
    """A sound stream: passes, each a permutation, from ``offset`` on."""
    rng = np.random.default_rng(seed)
    order = np.concatenate([rng.permutation(N) for _ in range(n_passes)])
    return order[offset:]


def check(order, sample_at=(0, 1), alter=None, **kw):
    rows = rows_of(order)
    sample = []
    for at in sample_at:
        b = reference.expected_batch(rows[at * B:(at + 1) * B])
        if alter is not None:
            alter(b)
        sample.append((at, b))
    return reference.check_feed(rows[:, :K], sample, SPLITS, B, **kw)


def total(counts):
    return sum(counts.values())


@pytest.mark.parametrize("offset", [0, 1, 37, N - 3])
def test_sound_stream_passes(offset):
    got = check(passes(3, offset, seed=offset), sample_at=(0, 2, 9))
    assert total(got) == 0
    ok, checks = reference.verdict(got, {k: 0 for k in got})
    assert ok and all(c["limit"] == 0 for c in checks.values())


def test_a_sequence_may_end_one_pass_and_begin_the_next():
    order = np.concatenate([np.arange(N), np.arange(N)[::-1]])
    assert reference.unexplained_repeats(order[N - 5:N + 20], N) == 0


@pytest.mark.parametrize("fault", ["stale", "one_split", "twice_in_a_pass", "half_duplicated"])
def test_broken_passes_are_counted(fault):
    order = passes(2)
    if fault == "stale":  # the same batch every time
        order = np.tile(order[:B], 6)
    elif fault == "one_split":  # a feed cycling one split
        order = np.tile(np.arange(TINY["split_records"]), 4)
    elif fault == "twice_in_a_pass":
        order = order.copy()
        order[[10, 50]] = order[[3, 20]]
    elif fault == "half_duplicated":
        order = order[: 8 * B].reshape(8, B)
        order = np.concatenate([order[:, : B // 2], order[:, : B // 2]], axis=1).ravel()
    assert reference.unexplained_repeats(order, N) > 0
    assert check(order)["unexplained_repeats"] > 0


def _alter(kind):
    def f(b):
        if kind == "token":
            b["tokens"][2, 100] += 1
        elif kind == "label":
            b["labels"][1, -1] = 5
        elif kind == "mask":
            b["loss_mask"][0, -1] = 1.0
        elif kind == "dtype":
            b["tokens"] = b["tokens"].astype(np.int64)
        elif kind == "half_left_out":
            for k in list(b):
                b[k] = b[k][: B // 2]
        elif kind == "swapped":
            b["tokens"] = b["tokens"][::-1].copy()
    return f


@pytest.mark.parametrize("kind, counter", [
    ("token", "token_mismatch"), ("label", "label_mismatch"), ("mask", "mask_mismatch"),
    ("dtype", "token_mismatch"), ("half_left_out", "token_mismatch"),
    ("swapped", "token_mismatch"),
])
def test_each_sample_fault_is_counted(kind, counter):
    got = check(passes(1), alter=_alter(kind))
    assert got[counter] > 0
    assert not reference.verdict(got, {k: 0 for k in got})[0]


def test_keys_that_name_no_sequence():
    order = passes(1)[: 6 * B]
    rows = rows_of(order).astype(np.int16).astype(np.int32)  # the control's ids
    got = reference.check_feed(rows[:, :K], [], SPLITS, B)
    assert got["unknown_rows"] > 0.9 * len(order)
    bad = reference.CorpusIndex(SPLITS).ids(np.zeros((3, K + 1), np.int32))
    assert (bad == -1).all()


def test_counts_from_the_loop_pass_through():
    got = check(passes(1), short_rows=B, misplaced=2)
    assert got["short_rows"] == B and got["misplaced"] == 2
    with pytest.raises(ValueError):
        reference.check_feed(rows_of(passes(1))[:, :K], [(0, {}), (0, {})], SPLITS, B)
