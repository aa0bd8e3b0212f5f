"""Per-kernel validation: interpret-mode Pallas vs pure-jnp oracle across
shape/dtype sweeps (the kernels/ contract)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.flash_attn import flash_attention, flash_attention_ref


def test_interpret_mode_only_on_cpu(monkeypatch):
    assert ops.interpret_mode()  # the tests run on the CPU backend
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert not ops.interpret_mode()
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="no Pallas kernel path"):
        ops.interpret_mode()


@pytest.mark.parametrize("bits", [4, 8, 16])
@pytest.mark.parametrize("n", [128, 8192, 10_001])
def test_bitunpack(rng, bits, n):
    w = jnp.asarray(rng.integers(0, 2**32, size=(n,), dtype=np.uint32))
    got = ops.bitunpack(w, bits, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref.bitunpack_ref(w, bits)))


@pytest.mark.parametrize("vdtype", [jnp.int32, jnp.float32])
# 65,536 and 131,072 are the token path's size classes for 50k and 100k
# vocabularies; 300,000 entries span three row tiles of the kernel's grid
@pytest.mark.parametrize(
    "n,v", [(4096, 16), (9000, 700), (2048, 65536), (2048, 131072), (2048, 300_000)]
)
def test_dict_decode(rng, n, v, vdtype):
    codes = rng.integers(0, v, size=(n,))
    # the first and last lane of the first, second and last table row
    edges = [c for c in (0, 127, 128, v - 128, v - 1) if 0 <= c < v]
    codes[: len(edges)] = edges
    if vdtype == jnp.int32:  # token ids of a 100k vocabulary
        table = rng.integers(0, 100_352, size=(v,)).astype(np.int32)
        table[-1] = 100_351
    else:  # the kernel moves bits: signs, -0.0 and inf come back as stored
        table = (rng.normal(size=(v,)) * 100).astype(np.float32)
        table[:4] = [-0.0, np.inf, -np.inf, -1.5]
        codes[len(edges): len(edges) + 4] = np.arange(4)
    codes, table = jnp.asarray(codes, jnp.int32), jnp.asarray(table)
    got = ops.dict_decode(codes, table, interpret=True)
    want = ref.dict_decode_ref(codes, table)
    assert got.dtype == want.dtype
    np.testing.assert_array_equal(np.asarray(got).view(np.uint32), np.asarray(want).view(np.uint32))


def test_dict_decode_takes_32_bit_tables():
    with pytest.raises(TypeError, match="32-bit"):
        ops.dict_decode(jnp.zeros((8,), jnp.int32), jnp.zeros((16,), jnp.int16), interpret=True)


@pytest.mark.parametrize("d", [128, 512])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_dict_embed(rng, d, dtype):
    codes = jnp.asarray(rng.integers(0, 300, size=(2048,)), jnp.int32)
    dict_ids = jnp.asarray(rng.integers(0, 5000, size=(300,)), jnp.int32)
    emb = jnp.asarray(rng.normal(size=(5000, d)), dtype)
    got = ops.dict_embed(codes, dict_ids, emb, interpret=True)
    want = ref.dict_embed_ref(codes, dict_ids, emb)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=2e-2 if dtype == jnp.bfloat16 else 1e-5,
    )


@pytest.mark.parametrize("density", [0.0, 0.02, 0.3, 1.0])
@pytest.mark.parametrize("n", [1024, 5000])
def test_filter_compact(rng, density, n):
    mask = jnp.asarray(rng.random(n) < density)
    idx, cnt = ops.filter_compact(mask, interpret=True)
    ridx, rcnt = ref.filter_compact_ref(mask)
    assert int(cnt) == int(rcnt)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(ridx))


def test_late_materialize(rng):
    mask = jnp.asarray(rng.random(2048) < 0.06)
    col = jnp.asarray(rng.normal(size=(2048, 8)), jnp.float32)
    rows, cnt = ops.late_materialize(mask, col, interpret=True)
    want = np.asarray(col)[np.asarray(mask)]
    np.testing.assert_allclose(np.asarray(rows)[: int(cnt)], want, rtol=1e-6)


@pytest.mark.parametrize(
    "bh,sq,sk,d,causal,bq,bk",
    [
        (2, 256, 256, 64, True, 64, 64),
        (1, 512, 512, 128, True, 256, 128),
        (2, 128, 256, 64, False, 64, 64),
    ],
)
def test_flash_attention(rng, bh, sq, sk, d, causal, bq, bk):
    q = jnp.asarray(rng.normal(size=(bh, sq, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(bh, sk, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(bh, sk, d)), jnp.float32)
    got = flash_attention(q, k, v, causal=causal, block_q=bq, block_k=bk, interpret=True)
    want = flash_attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=3e-5)


def test_flash_attention_bf16(rng):
    q = jnp.asarray(rng.normal(size=(2, 256, 64)), jnp.bfloat16)
    got = flash_attention(q, q, q, interpret=True)
    want = flash_attention_ref(q, q, q)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), atol=3e-2
    )
