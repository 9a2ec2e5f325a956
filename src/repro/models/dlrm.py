"""DLRM family (Facebook, arXiv:1906.00091): RMC1 / RMC2 / RMC3 / dlrm-rm2.

Dense features -> Bottom-MLP; sparse features -> EmbeddingBag (SparseNet);
pairwise dot-product interaction; Top-MLP -> CTR logit.

The SparseNet / DenseNet decomposition used by the paper's HW-aware model
partition is explicit here: ``apply_sparse`` is exactly `G_s` and
``apply_dense_given_pooled`` is `G_d`, so the S-D pipeline scheduler can
launch them as separate stages with the pooled [B, F, D] tensor as the
intermediate-queue payload.

Each layer runs under a ``jax.named_scope`` (``sparse``, ``dense``,
``interaction``; ``mlp`` in ``layers.apply_mlp``; ``gather`` and ``pool`` in
``embedding``), so every compiled instruction's ``op_name`` names the layer
it belongs to and a device trace can be read by those names.  Whatever
implements a layer later runs inside its scope.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.common.init import he_init
from repro.models import embedding as emb_lib
from repro.models.layers import apply_mlp, init_mlp
from repro.models.recsys_base import RecsysConfig


def init(key, cfg: RecsysConfig):
    k_emb, k_bot, k_top = jax.random.split(key, 3)
    d = cfg.embed_dim
    params = {"embedding": emb_lib.init_embedding(k_emb, cfg.embedding)}
    if cfg.n_dense:
        params["bottom_mlp"] = init_mlp(
            k_bot, (cfg.n_dense, *cfg.bottom_mlp), dtype=cfg.dtype
        )
        if cfg.bottom_mlp[-1] != d:
            raise ValueError("bottom MLP must project dense features to embed_dim")
    n_vec = cfg.embedding.num_features + (1 if cfg.n_dense else 0)
    n_inter = n_vec * (n_vec - 1) // 2
    top_in = n_inter + (d if cfg.n_dense else 0)
    params["top_mlp"] = init_mlp(k_top, (top_in, *cfg.top_mlp, 1), dtype=cfg.dtype)
    return params


def apply_sparse(params, batch, cfg: RecsysConfig) -> jax.Array:
    """G_s: the SparseNet — multi-hot EmbeddingBag -> pooled [B, F, D]."""
    with jax.named_scope("sparse"):
        return emb_lib.embedding_bag(params["embedding"], batch["sparse_ids"],
                                     cfg.embedding)


def dot_interaction(pooled: jax.Array, dense_v: jax.Array | None = None) -> jax.Array:
    """DLRM's interaction: pairwise dots among the n feature vectors (the
    bottom MLP's output [B, D], where there is one, and the pooled
    embeddings [B, F, D]), led by that output as in arXiv:1906.00091's
    ``interact_features`` -> top MLP input [B, (D +) n(n-1)/2]."""
    with jax.named_scope("interaction"):
        vectors = pooled
        if dense_v is not None:
            vectors = jnp.concatenate([dense_v[:, None, :], pooled], axis=1)
        n = vectors.shape[1]
        z = jnp.einsum("bnd,bmd->bnm", vectors, vectors)
        iu, ju = jnp.triu_indices(n, k=1)
        inter = z[:, iu, ju]
        return inter if dense_v is None else jnp.concatenate([dense_v, inter], axis=-1)


def apply_dense_given_pooled(params, batch, pooled, cfg: RecsysConfig) -> jax.Array:
    """G_d: DenseNet given pooled sparse embeddings [B, F, D] -> logit [B]."""
    with jax.named_scope("dense"):
        dense_v = None
        if cfg.n_dense:
            dense_v = apply_mlp(params["bottom_mlp"], batch["dense"].astype(cfg.dtype),
                                final_activation="relu")
        top_in = dot_interaction(pooled, dense_v)
        return apply_mlp(params["top_mlp"], top_in)[:, 0]


def apply(params, batch, cfg: RecsysConfig) -> jax.Array:
    pooled = apply_sparse(params, batch, cfg)
    return apply_dense_given_pooled(params, batch, pooled, cfg)
