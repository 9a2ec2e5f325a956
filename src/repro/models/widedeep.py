"""Wide & Deep (arXiv:1606.07792) and MT-WnD (multi-task, arXiv RecSys'19).

Wide: generalized linear part over sparse features (dim-1 embedding bags =
per-id scalar weights) + dense features. Deep: concat embeddings + dense
-> MLP. MT-WnD (cfg.n_tasks > 1): N task towers, each its own predict MLP,
matching the paper's "N×(1024-512-256)" Predict-FC column.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.models import embedding as emb_lib
from repro.models.embedding import EmbeddingConfig
from repro.models.layers import apply_mlp, init_mlp
from repro.models.recsys_base import RecsysConfig


def _wide_cfg(cfg: RecsysConfig) -> EmbeddingConfig:
    """Dim-1 clone of the embedding config for the wide (linear) part."""
    return dataclasses.replace(cfg.embedding, dim=1)


def init(key, cfg: RecsysConfig):
    k_emb, k_wide, k_deep, k_tower = jax.random.split(key, 4)
    emb = cfg.embedding
    params = {
        "embedding": emb_lib.init_embedding(k_emb, emb),
        "wide": emb_lib.init_embedding(k_wide, _wide_cfg(cfg)),
    }
    deep_in = emb.num_features * emb.dim + cfg.n_dense
    if cfg.n_dense:
        params["wide_dense"] = jnp.zeros((cfg.n_dense,), cfg.dtype)
    params["deep_mlp"] = init_mlp(k_deep, (deep_in, *cfg.top_mlp), dtype=cfg.dtype)
    tower_keys = jax.random.split(k_tower, cfg.n_tasks)
    params["towers"] = [
        init_mlp(tk, (cfg.top_mlp[-1], 1), dtype=cfg.dtype) for tk in tower_keys
    ]
    return params


def apply_sparse(params, batch, cfg: RecsysConfig):
    """G_s: deep embeddings [B, F, D] and wide scalar sums [B, F, 1]."""
    with jax.named_scope("sparse"):
        deep = emb_lib.embedding_bag(params["embedding"], batch["sparse_ids"],
                                     cfg.embedding)
        wide = emb_lib.embedding_bag(params["wide"], batch["sparse_ids"], _wide_cfg(cfg))
        return deep, wide


def apply_dense_given_pooled(params, batch, pooled, cfg: RecsysConfig) -> jax.Array:
    """G_d: wide logit plus the deep MLP and task towers -> logit(s)."""
    deep_emb, wide_emb = pooled
    B = deep_emb.shape[0]
    with jax.named_scope("dense"):
        deep_in = deep_emb.reshape(B, -1)
        wide_logit = wide_emb.sum(axis=(1, 2))
        if cfg.n_dense:
            dense = batch["dense"].astype(cfg.dtype)
            deep_in = jnp.concatenate([deep_in, dense], axis=-1)
            wide_logit = wide_logit + dense @ params["wide_dense"]
        hidden = apply_mlp(params["deep_mlp"], deep_in, final_activation="relu")
        logits = jnp.stack(
            [apply_mlp(t, hidden)[:, 0] for t in params["towers"]], axis=-1
        )  # [B, n_tasks]
        logits = logits + wide_logit[:, None]
        return logits[:, 0] if cfg.n_tasks == 1 else logits


def apply(params, batch, cfg: RecsysConfig) -> jax.Array:
    pooled = apply_sparse(params, batch, cfg)
    return apply_dense_given_pooled(params, batch, pooled, cfg)
