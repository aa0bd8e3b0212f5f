"""Every Pallas kernel compiles for a described TPU v5e chip.

No chip is needed: the TPU compiler compiles for a topology that is
described and not attached, and refuses what the chip would refuse
(unaligned blocks, too much VMEM, unsupported ops), which interpret mode
cannot show.  Shapes are the widths ``chip_smoke.py`` runs: a batch of
8 x 2048 tokens of 16-bit codes, one chip's 8 x 4,096 of a four-chip
host's batch, a 32000-id vocabulary at d_model 2048,
a 2**24-row mask and TinyLlama's 64-wide attention heads.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and each test worker imports this file.
"""
import os
from functools import partial

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.kernels.flash_attn import flash_attention

TOKENS = 8 * 2048


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # an entry compiled for a described chip cannot be read back without one
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


CASES = {
    "bitunpack": (partial(ops.bitunpack, bits=16), [((TOKENS // 2,), jnp.uint32)]),
    # the token path pads each split's dictionary to a power of two: TinyLlama's
    # 32,000 ids take the 32,768 class, StableLM 2's 100,352 the 131,072 class
    "dict_decode": (ops.dict_decode, [((TOKENS,), jnp.int32), ((32768,), jnp.int32)]),
    "dict_decode_131072": (ops.dict_decode, [((TOKENS,), jnp.int32), ((131072,), jnp.int32)]),
    # one chip's share of a four-chip host's batch, 8 rows of 4,096 tokens,
    # as the per-chip decode hands it to the kernels
    "bitunpack_rows": (partial(ops.bitunpack, bits=16), [((8, 2048), jnp.uint32)]),
    "dict_decode_rows": (ops.dict_decode, [((8, 4096), jnp.int32), ((65536,), jnp.int32)]),
    "dict_embed": (ops.dict_embed, [
        ((TOKENS,), jnp.int32), ((4096,), jnp.int32), ((32000, 2048), jnp.bfloat16),
    ]),
    "filter_compact": (ops.filter_compact, [((1 << 24,), jnp.bool_)]),
    "flash_attention": (flash_attention, [((32, 2048, 64), jnp.bfloat16)] * 3),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, shapes = CASES[name]
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


def test_chips_layout_compiles_for_v5e_2x2_with_no_exchange(topo):
    """labels and loss_mask of a 32 x 4,096 batch, its rows over the four
    chips of a v5e host: each chip builds its own rows, so the compiled
    program moves nothing between chips."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.data.pipeline import labels_and_mask

    rows = NamedSharding(Mesh(np.asarray(topo.devices), ("data",)), P("data", None))
    args = [jax.ShapeDtypeStruct((32, 4096), jnp.int32, sharding=rows),
            jax.ShapeDtypeStruct((32, 512), jnp.uint8, sharding=rows)]
    text = jax.jit(labels_and_mask, out_shardings=(rows, rows)).lower(*args).compile().as_text()
    for op in ("all-gather", "all-reduce", "all-to-all", "collective-permute"):
        assert op not in text, op
