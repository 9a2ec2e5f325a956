"""Jitted wrapper whose kernel mode comes from ``interpret_mode``."""
from __future__ import annotations

from repro.kernels import interpret_mode
from repro.kernels.embedding_bag.embedding_bag import hot_embedding_bag_pallas


def hot_embedding_bag(table, ids, *, tile_b: int = 128):
    """Fused hot-table SLS: table [H, D], ids [B, P] -> [B, D].

    Pads the batch up to tile_b internally."""
    B = ids.shape[0]
    pad = (-B) % tile_b
    if pad:
        import jax.numpy as jnp

        ids = jnp.pad(ids, ((0, pad), (0, 0)), constant_values=-1)
    out = hot_embedding_bag_pallas(
        table, ids, tile_b=tile_b, interpret=interpret_mode()
    )
    return out[:B] if pad else out
