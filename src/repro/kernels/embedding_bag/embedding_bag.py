"""Fused hot-embedding SparseLengthsSum Pallas kernel.

TPU adaptation of the paper's locality-aware hot-table partition: the hot
table (sized by repro.core.partition to the fast-memory budget) is pinned
whole in VMEM; each grid step brings one batch tile of ids into SMEM and
performs the gather + pool on-chip, writing only the pooled [tile, D] rows
back. This replaces the NMP DIMM's rank-parallel Gather-Reduce with a
VMEM-resident gather: HBM sees ids in and pooled vectors out — never the
P individual rows.

Grid: (B // tile_b,). BlockSpecs:
    ids   [tile_b, P] int32 — per-step tile in SMEM (scalar reads drive the
                              row addresses).
    table [H, D]    — constant block (index_map -> (0, 0)), lives in VMEM
                      across grid steps; H*D*dtype must fit the ~16 MB
                      twin-buffer budget (the partitioner guarantees it).
    out   [tile_b, D] f32   — per-step tile (one-row stores need 32 bits;
                              cast to the table dtype outside).

The gather is a scalar loop: for each bag, each id is read from SMEM and
its row is loaded from the VMEM table with a dynamic slice, then
accumulated in float32. (An in-kernel vector ``jnp.take`` is refused by
the TPU compiler.)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _row_loader(table_ref, rows: int):
    """Return ``load(idx) -> [1, D] float32`` for row ``idx >= 0``.

    A 32-bit table loads the row with a one-row dynamic slice.  A packed
    (16-bit) table holds ``rows`` rows per sublane tile, and the TPU
    compiler only takes slices aligned to it: load the aligned tile and
    select the row from it."""
    if rows == 1:
        return lambda idx: table_ref[pl.ds(idx, 1), :].astype(jnp.float32)

    def load(idx):
        base = pl.multiple_of((idx // rows) * rows, rows)
        tile = table_ref[pl.ds(base, rows), :].astype(jnp.float32)
        hit = jax.lax.broadcasted_iota(jnp.int32, tile.shape, 0) == idx - base
        return jnp.where(hit, tile, 0.0).sum(axis=0, keepdims=True)

    return load


def _kernel(ids_ref, table_ref, out_ref, *, rows):
    tile_b, P = ids_ref.shape
    D = table_ref.shape[1]
    load = _row_loader(table_ref, rows)

    def bag(b, carry):
        def lookup(p, acc):
            idx = ids_ref[b, p]
            row = load(jnp.maximum(idx, 0))
            return acc + jnp.where(idx >= 0, row, 0.0)

        acc = jax.lax.fori_loop(0, P, lookup, jnp.zeros((1, D), jnp.float32))
        out_ref[pl.ds(b, 1), :] = acc
        return carry

    jax.lax.fori_loop(0, tile_b, bag, 0)


@functools.partial(jax.jit, static_argnames=("tile_b", "interpret"))
def hot_embedding_bag_pallas(table: jax.Array, ids: jax.Array, *,
                             tile_b: int = 128, interpret: bool = False):
    """table [H, D]; ids [B, P] (-1 padded) -> pooled [B, D]."""
    B, P = ids.shape
    H, D = table.shape
    if B % tile_b:
        raise ValueError(f"batch {B} must be a multiple of tile_b {tile_b}")
    # rows per sublane tile of a packed dtype (16 for bf16), 1 for 32-bit;
    # the table is padded to whole tiles so an aligned load stays in bounds
    rows = 1 if table.dtype.itemsize == 4 else 8 * 4 // table.dtype.itemsize
    if H % rows:
        table = jnp.pad(table, ((0, rows - H % rows), (0, 0)))
        H = table.shape[0]
    grid = (B // tile_b,)
    return pl.pallas_call(
        functools.partial(_kernel, rows=rows),
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile_b, P), lambda i: (i, 0),
                         memory_space=pltpu.SMEM),        # ids tile
            pl.BlockSpec((H, D), lambda i: (0, 0)),       # table resident
        ],
        out_specs=pl.BlockSpec((tile_b, D), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, D), jnp.float32),
        interpret=interpret,
    )(ids.astype(jnp.int32), table).astype(table.dtype)
