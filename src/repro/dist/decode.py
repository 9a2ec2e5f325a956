"""Distributed flash decode over a sequence-sharded KV cache.

The long-context decode cells (``decode_32k`` / ``long_500k``) keep the KV
cache sequence-sharded: [B, S, KVH, hd] with the S dimension split over the
"model" axis (and over the data axes too when batch == 1 — long_500k's only
option, see ``repro.dist.sharding.kv_seq_axes``).  A naive attention over
that layout forces GSPMD to all-gather the whole cache onto every chip —
exactly the transfer the layout exists to avoid.

This module runs the split-KV schedule across chips instead: under a
``shard_map`` each shard runs the on-chip Pallas kernel
(:func:`~repro.kernels.flash_attention.flash_decode.flash_decode_partials`)
on its *local* KV slice — passing its global base offset so a ragged
``kv_len`` that ends mid-shard masks correctly — producing per-shard
softmax partials ``(m, l, o)``.  A single all-gather of the partials
(tiny: [group, hd] per kv head, independent of S) followed by the same
``lse_combine`` primitive the kernel uses for its on-chip chunk merge
combines them, so the cross-chip merge and the on-chip merge share one
correctness oracle.  The merge is permutation-invariant (max + weighted
sums), so gather order across a multi-axis shard never matters.

``decode_attention`` is the model-facing entry: it reads the active logical
binding (``repro.dist.logical``) and picks the distributed path iff a mesh
is bound with a non-trivial "kv_seq" rule; otherwise it runs the local
kernel — the same code path serves single-device smoke tests and the
sharded cells.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.dist import logical
from repro.kernels import interpret_mode
from repro.kernels.flash_attention.flash_decode import (
    flash_decode_partials,
    flash_decode_pallas,
    lse_combine,
)


def _as_axes(axes) -> tuple[str, ...]:
    """Normalize a rule binding (name | tuple | None) to a tuple of names."""
    if axes is None:
        return ()
    if isinstance(axes, str):
        return (axes,)
    return tuple(axes)


def seq_shard_index(mesh, seq_axes: tuple[str, ...]):
    """Flat shard index along a dimension sharded over ``seq_axes``.

    PartitionSpec orders multi-axis sharding major-to-minor, so the shard
    holding global rows [i * S_local, (i+1) * S_local) has
    i = axis_index(major) * size(minor) + axis_index(minor).
    """
    idx = jnp.zeros((), jnp.int32)
    for a in seq_axes:
        idx = idx * mesh.shape[a] + jax.lax.axis_index(a)
    return idx


def flash_decode_sharded(q, k, v, *, kv_len, mesh, seq_axes, batch_axes=(),
                         bk=512, interpret=False):
    """Flash decode with k/v sequence-sharded over ``seq_axes``.

    q [B, 1, H, hd] (replicated over ``seq_axes``; optionally sharded on
    batch over ``batch_axes``); k/v [B, S, KVH, hd] with S sharded over
    ``seq_axes``.  kv_len is the GLOBAL live cache length — it may land
    anywhere inside any shard; shards entirely past it contribute empty
    partials.  Returns [B, 1, H, hd] with q's sharding.
    """
    seq_axes = _as_axes(seq_axes)
    batch_axes = _as_axes(batch_axes)
    if not seq_axes:
        return flash_decode_pallas(q, k, v, kv_len=kv_len, bk=bk,
                                   interpret=interpret)
    n_shards = 1
    for a in seq_axes:
        n_shards *= mesh.shape[a]
    S = k.shape[1]
    if S % n_shards:
        raise ValueError(f"S {S} not divisible by {n_shards} seq shards "
                         f"({seq_axes})")
    s_local = S // n_shards

    b_ax = batch_axes or None
    q_spec = P(b_ax, None, None, None)
    kv_spec = P(b_ax, seq_axes, None, None)

    def local_decode(q_l, k_l, v_l):
        offset = seq_shard_index(mesh, seq_axes) * s_local
        m, l, o = flash_decode_partials(
            q_l, k_l, v_l, kv_len=kv_len, kv_offset=offset, bk=bk,
            interpret=interpret,
        )
        # partials are [B_l, KVH, group, {1, hd}] — gathering them moves
        # O(B * H * hd) bytes per chip, independent of S
        m_all, l_all, o_all = jax.lax.all_gather(
            (m, l, o), seq_axes, axis=0)
        _, l_c, o_c = lse_combine(m_all, l_all, o_all, axis=0)
        out = (o_c / jnp.maximum(l_c, 1e-30)).astype(q_l.dtype)
        b_l, kvh, group, hd = o_c.shape
        return out.reshape(b_l, kvh * group, hd).reshape(b_l, 1, kvh * group, hd)

    return jax.shard_map(
        local_decode, mesh=mesh,
        in_specs=(q_spec, kv_spec, kv_spec),
        out_specs=q_spec,
        check_vma=False,
    )(q, k, v)


def decode_attention(q, k, v, *, kv_len, bk=512, interpret=None):
    """Model-facing decode attention: distributed iff "kv_seq" is bound.

    Reads the active logical binding at trace time: with a mesh and a
    non-empty "kv_seq" rule the KV cache is sequence-sharded and the
    shard_map path runs; otherwise the local split-KV kernel does.  The
    "batch" rule (if bound) carries through as the batch sharding.
    """
    interpret = interpret_mode(interpret)
    mesh = logical.current_mesh()
    seq_axes = logical.bound_axes("kv_seq")
    if mesh is None or not seq_axes:
        return flash_decode_pallas(q, k, v, kv_len=kv_len, bk=bk,
                                   interpret=interpret)
    return flash_decode_sharded(
        q, k, v, kv_len=kv_len, mesh=mesh, seq_axes=seq_axes,
        batch_axes=logical.bound_axes("batch"), bk=bk, interpret=interpret,
    )
