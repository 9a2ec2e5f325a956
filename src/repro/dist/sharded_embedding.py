"""Model-axis-sharded embedding lookup (the distributed SparseNet).

The combined embedding table is row-sharded over the "model" mesh axis
(:func:`repro.dist.sharding.param_spec_tree`).  A row gather against a
row-sharded operand lowers, under GSPMD, to exactly the paper's Psum
dataflow: every shard gathers the requested rows it owns (masked local
gather) and the partial results are all-reduced — no shard ever
materializes the full table.  This module pins that layout with sharding
constraints so the partitioner cannot fall back to an all-gather of the
multi-GB table.

Single-device semantics are identical (the constraints are no-ops outside
an ``axis_rules`` binding), which is what the numerical-equivalence tests
in ``tests/test_distributed.py`` exercise.
"""
from __future__ import annotations

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.dist import logical
from repro.models import embedding as emb_lib


def sharded_row_gather(table, ids, axis_name=None):
    """Row gather from a (possibly) row-sharded table.

    table: [rows, dim] (or a ``LineTable``, sharded by lines) annotated
    sharded over the model axis; ids: any int shape.  ``axis_name`` pins
    the table to an explicit mesh axis instead of the bound logical "model"
    axis (None = use the active binding; no binding = plain local gather).
    Returns ``ids.shape + (dim,)``.
    """
    if axis_name is not None:
        mesh = logical.current_mesh()
        if mesh is not None:
            table = jax.tree.map(lambda t: jax.lax.with_sharding_constraint(
                t, NamedSharding(mesh, P(axis_name, None))), table)
        return emb_lib.gather_rows(table, ids)
    if logical.model_axis_name() is not None:
        table = _row_sharded(table)
    return emb_lib.gather_rows(table, ids)


def _row_sharded(table):
    """The table (its rows, or a ``LineTable``'s lines) pinned row-sharded."""
    return jax.tree.map(lambda t: logical.constrain(t, ("model", None)), table)


def embedding_bag_sharded(params, ids, cfg):
    """Multi-hot gather + pool against the row-sharded combined table.

    Delegates to :func:`repro.models.embedding.embedding_bag_local` (same
    QR handling, same masked pooling — one body to maintain) with the
    table pinned row-sharded and the pooled output pinned batch-sharded.
    ids: [B, F, P] int32, -1-padded -> [B, F, dim].
    """
    pooled = emb_lib.embedding_bag_local({"table": _row_sharded(params["table"])},
                                         ids, cfg)
    return logical.constrain(pooled, ("batch", None, None))
