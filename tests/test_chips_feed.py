"""The token feed on the chips of a batch sharding: each device decodes its
own rows and the batch stays there (``HostPipeline(decode="device",
sharding=...)``), checked on four CPU devices in one subprocess
(``_chips_feed_check.py``); and the decode worlds by name."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.data.pipeline import HostPipeline
from repro.data.tokens import DECODES, TokenCorpus, TokenCorpusWriter
from repro.launch.load_data import synth_token_docs

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def four(tmp_path_factory):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, os.path.join(HERE, "_chips_feed_check.py"),
                        str(tmp_path_factory.mktemp("chips"))],
                       env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["devices"] == 4
    return out


@pytest.mark.parametrize("bits", [16, 32])
def test_chips_batch_is_the_host_batch_row_by_row(four, bits):
    # every token, label and mask value, and each device's shard its own rows
    assert four[f"compare_{bits}"] == {"mismatch": 0, "not_on_chips": 0,
                                       "shard_not_own_rows": 0, "dtype": 0}


def test_chips_decode_spans(four):
    s = four["spans"]
    assert s["chips_spans"] == 2 and s["chips_args"] == [4]
    # the copies inside carry the same bytes: packed words, tables, mask bits
    assert s["dispatch_bytes"] == s["chips_bytes"] and min(s["chips_bytes"]) > 0
    assert s["d2h_bytes"] == [0, 0]  # nothing comes back


def test_fit_steps_from_the_chips_feed(four):
    f = four["fit"]
    assert f["sharding_spec"] == "PartitionSpec('data', None)" and f["sharding_devices"] == 4
    assert all(np.isfinite(f["device"]))
    np.testing.assert_allclose(f["device"], f["np"], rtol=1e-6)
    # fit's placement hands the placed batch to the step as it is
    assert f["passed_through"] == [True, True]


@pytest.fixture(scope="module")
def corpus(tmp_path_factory):
    root = tmp_path_factory.mktemp("corpus")
    w = TokenCorpusWriter(str(root), seq_len=64, split_records=32)
    for toks, meta in synth_token_docs(80, vocab=2000):
        w.add_document(toks, meta)
    w.close()
    return TokenCorpus(str(root))


@pytest.mark.parametrize("decode", list(DECODES) + ["gpu"])
def test_decode_world_by_name(corpus, decode):
    """A decode name the program does not know is refused, not read as the
    host path; a sharding needs the device path."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    sp = corpus.open_split(corpus.split_ids()[0])
    sharding = NamedSharding(Mesh(np.asarray(jax.devices()[:1]), ("data",)), P("data", None))
    if decode not in DECODES:
        with pytest.raises(ValueError, match="unknown decode"):
            sp.record_batch([0, 1], decode=decode)
        with pytest.raises(ValueError, match="unknown decode"):
            HostPipeline(corpus, 2, decode=decode)
        return
    toks, mask = sp.record_batch([0, 1], decode=decode)
    assert toks.shape[0] == mask.shape[0] == 2
    if decode == "device":
        batch = next(iter(HostPipeline(corpus, 2, prefetch=0, decode=decode, sharding=sharding)))
        ref = next(iter(HostPipeline(corpus, 2, prefetch=0, decode="np")))
        for k, v in ref.items():
            np.testing.assert_array_equal(np.asarray(batch[k]), v)
            assert batch[k].sharding == sharding
    else:
        with pytest.raises(ValueError, match="decode='device'"):
            HostPipeline(corpus, 2, decode=decode, sharding=sharding)
