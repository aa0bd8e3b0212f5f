"""Pallas TPU kernel: dictionary decode (gather LUT), the device half of DCSL.

Dictionary-compressed token/metadata blocks ship to the device as small
integer codes and are expanded there against the block's dictionary — the
DCSL "cheap decode" property (§5.3) carried across the host->HBM->VMEM path.

Two variants:
  * scalar table (V,): codes -> values                 (token ids, ints)
  * vector table (V,D): codes -> rows                  (fused dict+embed:
    the wrapper in ops.py pre-gathers the dictionary's embedding rows so raw
    token ids are never materialized in HBM)

TPU has no fast arbitrary VMEM gather, so both gathers go through the MXU.
The row variant is a one-hot (bn x V) @ (V x D) matmul, the standard TPU
idiom when V is dictionary-sized.  The scalar variant is a two-level lookup,
so a token dictionary can hold a whole 100k vocabulary: code c names row
c >> 7 and lane c & 127 of the table viewed as (V/128, 128).  The table's
32-bit entries are split (bitcast, not converted) into four byte planes,
each exact in bf16; a one-hot over the rows picks every code's row of all
four planes on the MXU (one term reaches each output, so the f32 result is
exact), a masked sum over sublanes picks the lane, and shifts and ORs put the
bytes back together.  Codes stay in lanes throughout, so nothing is
transposed.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANE = 128
PLANES = 4  # bytes of a 32-bit table entry
# codes decoded per grid step; the wrapper pads codes to a multiple of it
BLOCK = 1024
# table rows (of LANE entries) one grid step picks from: larger tables take
# more steps along the grid's last axis, so VMEM stays bounded whatever V is
ROW_TILE = 1024
# a bf16 one-hot needs at least 16 rows, so tables are padded to this
MIN_TABLE = 16 * LANE


def _scalar_kernel(codes_ref, planes_ref, out_ref):
    j = pl.program_id(1)
    rows = planes_ref.shape[1]
    codes = codes_ref[...]  # (1, BLOCK) int32
    row = jax.lax.broadcasted_iota(jnp.int32, (rows, BLOCK), 0) + j * rows
    onehot = (row == (codes >> 7)).astype(jnp.bfloat16)
    # (PLANES*LANE, rows) @ (rows, BLOCK): every lane of each code's row
    picked = jnp.dot(planes_ref[...], onehot, preferred_element_type=jnp.float32)
    lane = jax.lax.broadcasted_iota(jnp.int32, (LANE, BLOCK), 0) == (codes & (LANE - 1))
    word = jnp.zeros(codes.shape, jnp.int32)
    for p in range(PLANES):
        byte = jnp.sum(
            jnp.where(lane, picked[p * LANE:(p + 1) * LANE], 0.0), axis=0, keepdims=True
        )
        word = word | (byte.astype(jnp.int32) << (8 * p))

    # a code's row lies in one row tile; the others contribute zeros
    @pl.when(j == 0)
    def _():
        out_ref[...] = word

    @pl.when(j > 0)
    def _():
        out_ref[...] |= word


def _vector_kernel(codes_ref, table_ref, out_ref):
    codes = codes_ref[...][:, 0]  # (bn,) int32 — one code per output row
    table = table_ref[...]  # (V, D)
    v = table.shape[0]
    onehot = (codes[:, None] == jnp.arange(v, dtype=jnp.int32)[None, :]).astype(
        table.dtype
    )
    # the MXU accumulates in 32 bits; the one-hot picks one row exactly
    out_ref[...] = jnp.dot(
        onehot, table, preferred_element_type=jnp.float32
    ).astype(out_ref.dtype)


def dict_decode_scalar(codes: jax.Array, table: jax.Array, interpret: bool = False) -> jax.Array:
    """codes: (N,) int32, N a multiple of BLOCK; table: (V,) of a 32-bit
    dtype -> (N,) of table.dtype, bit for bit ``table[codes]``."""
    (n,) = codes.shape
    assert n % BLOCK == 0, n
    if table.dtype.itemsize != 4:
        raise TypeError(f"dict_decode takes a table of a 32-bit dtype, not {table.dtype}")
    v = table.shape[0]
    rows = -(-max(v, MIN_TABLE) // LANE)
    tile = min(rows, ROW_TILE)
    rows = -(-rows // tile) * tile
    # the bits of each entry, whatever its dtype; codes never reach the padding
    words = jax.lax.bitcast_convert_type(table, jnp.uint32)
    words = jnp.pad(words, (0, rows * LANE - v)).reshape(rows, LANE)
    # planes[p * LANE + l, r] = byte p of table[r * LANE + l]
    planes = jnp.stack([(words >> (8 * p)) & 0xFF for p in range(PLANES)])
    planes = planes.transpose(0, 2, 1).reshape(PLANES * LANE, rows).astype(jnp.bfloat16)
    g = n // BLOCK
    out = pl.pallas_call(
        _scalar_kernel,
        grid=(g, rows // tile),
        in_specs=[
            pl.BlockSpec((None, 1, BLOCK), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((PLANES * LANE, tile), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((None, 1, BLOCK), lambda i, j: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((g, 1, BLOCK), jnp.int32),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(codes.reshape(g, 1, BLOCK), planes)
    return jax.lax.bitcast_convert_type(out.reshape(n), table.dtype)


def dict_decode_rows(
    codes: jax.Array,
    table: jax.Array,
    block_n: int = 256,
    block_d: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """codes: (N, 1) int32; table: (V, D) -> (N, D) gathered rows."""
    n = codes.shape[0]
    v, d = table.shape
    assert n % block_n == 0 and d % block_d == 0, (n, d)
    return pl.pallas_call(
        _vector_kernel,
        grid=(n // block_n, d // block_d),
        in_specs=[
            pl.BlockSpec((block_n, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((v, block_d), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((block_n, block_d), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n, d), table.dtype),
        interpret=interpret,
    )(codes, table)
