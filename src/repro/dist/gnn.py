"""Sharded GNN execution via ``shard_map`` (vertex/edge partition).

Full-graph GraphSAGE distributes by sharding the EDGE LIST: each device
gathers messages for its edge shard, segment-sums a partial [N, d]
aggregation, and a psum over the mesh reconstructs the exact full-graph
aggregate (sum and mean are linear in the edge set; max uses pmax).  The
dense SAGE combine then runs replicated outside the shard_map — parameters
never enter the mapped region, so this composes with jit/grad without
per-leaf spec plumbing.

Batched small graphs (molecule cells) are embarrassingly parallel instead:
the packed [G·n] node / [G·e] edge arrays shard on their graph-major axis,
and each device runs the whole forward on its own block of graphs after
rebasing the global node/graph ids to its shard.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models import gnn as gnn_lib


def _sharded_aggregate(h, edges, mesh, n_nodes, aggregator):
    """Exact full-graph aggregation with edges sharded over every mesh axis.

    h: [N, d] (replicated into the map), edges: [2, E] -> ([N, d], [N, 1])
    aggregate and in-degree, both replicated (psum'd) on the way out.
    """
    axes = tuple(mesh.axis_names)

    def body(h_full, edges_local):
        src, dst = edges_local[0], edges_local[1]
        msg = jnp.take(h_full, src, axis=0)                   # [E_local, d]
        if aggregator == "max":
            agg = jax.ops.segment_max(msg, dst, num_segments=n_nodes)
            agg = jnp.where(jnp.isfinite(agg), agg, -jnp.inf)
            agg = jax.lax.pmax(agg, axes)
            agg = jnp.where(jnp.isfinite(agg), agg, 0.0)
            deg = jnp.ones((n_nodes, 1), h_full.dtype)        # unused for max
            return agg, deg
        agg = jax.ops.segment_sum(msg, dst, num_segments=n_nodes)
        deg = jax.ops.segment_sum(
            jnp.ones_like(dst, h_full.dtype), dst, num_segments=n_nodes
        )[:, None]
        return jax.lax.psum(agg, axes), jax.lax.psum(deg, axes)

    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(None, None), P(None, axes)),
        out_specs=(P(None, None), P(None, None)),
    )(h, edges)


def apply_full_sharded(params, feats, edges, labels, label_mask, cfg, mesh,
                       n_nodes):
    """Full-graph GraphSAGE forward + masked softmax CE under edge sharding.

    Numerically identical to ``gnn.apply_full`` -> ``gnn.softmax_ce`` on one
    device; returns the scalar loss.
    """
    h = feats.astype(cfg.dtype)
    for layer in params["layers"]:
        agg, deg = _sharded_aggregate(h, edges, mesh, n_nodes, cfg.aggregator)
        if cfg.aggregator == "mean":
            agg = agg / jnp.maximum(deg, 1.0)
        h = gnn_lib._sage_combine(layer, h, agg, activate=True)
    logits = h @ params["cls"]
    return gnn_lib.softmax_ce(logits, labels, label_mask)


def apply_batched_sharded(params, batch, cfg, mesh, dp, n_graphs, n_nodes,
                          n_edges):
    """Packed-small-graph forward with graphs sharded over the ``dp`` axes.

    batch: feats [G·n, d] / edges [2, G·e] (global node ids) / node_mask
    [G·n] / graph_ids [G·n] (global graph ids) / labels [G], uniformly
    packed (graph g owns nodes [g·n, (g+1)·n)).  Each shard rebases ids to
    its local block and runs the plain batched forward.  Returns
    (logits [G, C], labels [G]) for the caller's loss.
    """
    dp = (dp,) if isinstance(dp, str) else tuple(dp)
    n_shards = 1
    for a in dp:
        n_shards *= mesh.shape[a]
    if n_graphs % n_shards:
        raise ValueError(f"{n_graphs} graphs do not tile {n_shards} shards")
    g_local = n_graphs // n_shards

    p_specs = jax.tree.map(lambda l: P(*([None] * jnp.ndim(l))), params)

    def body(p, feats, edges, node_mask, graph_ids, labels):
        idx = jnp.zeros((), jnp.int32)
        for a in dp:  # flattened shard index over the dp axes, major-first
            idx = idx * mesh.shape[a] + jax.lax.axis_index(a)
        edges_l = edges - idx * (g_local * n_nodes)
        gids_l = graph_ids - idx * g_local
        logits = gnn_lib.apply_batched(
            p, feats, edges_l, node_mask, gids_l, g_local, cfg
        )
        return logits, labels

    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(p_specs, P(dp, None), P(None, dp), P(dp), P(dp), P(dp)),
        out_specs=(P(dp, None), P(dp)),
    )(params, batch["feats"], batch["edges"], batch["node_mask"],
      batch["graph_ids"], batch["labels"])
