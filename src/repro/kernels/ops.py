"""jit'd public wrappers around the Pallas kernels (padding, reshaping,
composition).  `interpret=True` runs kernel bodies on CPU for validation;
on TPU the same code emits real Mosaic kernels.
"""
from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp

from .bitunpack import LANE, bitunpack_tiles
from .dict_decode import BLOCK, dict_decode_rows, dict_decode_scalar
from .filter_compact import compact_indices


def interpret_mode() -> bool:
    """Kernels compile for the TPU and run interpreted on the CPU backend,
    where the tests run.  Any other backend has no kernel path."""
    backend = jax.default_backend()
    if backend not in ("tpu", "cpu"):
        raise RuntimeError(f"no Pallas kernel path for JAX backend {backend!r}")
    return backend == "cpu"


def _pad_to(x: jax.Array, mult: int, fill=0) -> Tuple[jax.Array, int]:
    n = x.shape[0]
    pad = (-n) % mult
    if pad:
        x = jnp.concatenate([x, jnp.full((pad,) + x.shape[1:], fill, x.dtype)])
    return x, n


@partial(jax.jit, static_argnames=("bits", "interpret"))
def bitunpack(words: jax.Array, bits: int, interpret: bool = False) -> jax.Array:
    """words: (..., W) uint32 -> (..., W*32//bits) int32 codes."""
    tile = 64 * LANE
    w, n = _pad_to(words.reshape(-1), tile)
    tiles = w.reshape(-1, LANE)
    out = bitunpack_tiles(tiles, bits, interpret=interpret)
    return out.reshape(-1)[: n * (32 // bits)].reshape(words.shape[:-1] + (-1,))


@partial(jax.jit, static_argnames=("interpret",))
def dict_decode(codes: jax.Array, table: jax.Array, interpret: bool = False) -> jax.Array:
    """codes: (...) int32, table: (V,) of a 32-bit dtype -> decoded values
    shaped as ``codes``."""
    c, n = _pad_to(codes.reshape(-1), BLOCK)
    return dict_decode_scalar(c, table, interpret=interpret)[:n].reshape(codes.shape)


@partial(jax.jit, static_argnames=("interpret",))
def dict_embed(
    codes: jax.Array, dict_ids: jax.Array, emb: jax.Array, interpret: bool = False
) -> jax.Array:
    """Fused DCSL decode + embedding lookup: codes (N,) -> (N, D).

    The dictionary's embedding rows are gathered once (V rows, tiny), then
    the Pallas kernel expands codes -> rows blockwise in VMEM.  Raw token
    ids are never materialized in HBM."""
    d = emb.shape[1]
    dict_rows = jnp.take(emb, dict_ids, axis=0)  # (V, D) — V is dict-sized
    block_d = 512 if d % 512 == 0 else d
    c, n = _pad_to(codes, 256)
    out = dict_decode_rows(
        c[:, None], dict_rows, block_n=256, block_d=block_d, interpret=interpret
    )
    return out[:n]


@partial(jax.jit, static_argnames=("interpret",))
def filter_compact(mask: jax.Array, interpret: bool = False):
    """mask: (N,) bool -> (indices (N,) int32 padded with N, count)."""
    m, n = _pad_to(mask, 8 * LANE, fill=False)
    idx, count = compact_indices(m, rows=8, interpret=interpret)
    idx = jnp.where(idx >= n, n, idx)[: n]
    return idx, count


def late_materialize(
    mask: jax.Array, column: jax.Array, interpret: bool = False
) -> Tuple[jax.Array, jax.Array]:
    """The paper's lazy-record pattern on device: gather `column` rows only
    where mask holds.  Returns (gathered (N, ...) with tail garbage, count)."""
    idx, count = filter_compact(mask, interpret=interpret)
    safe = jnp.minimum(idx, column.shape[0] - 1)
    return jnp.take(column, safe, axis=0), count
