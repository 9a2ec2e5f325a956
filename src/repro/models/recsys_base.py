"""Shared recsys model protocol.

Every recsys model module exposes::

    init(key, cfg)                 -> params pytree
    apply(params, batch, cfg)      -> logits [B] (or [B, n_tasks])
    input_specs(cfg, batch, ...)   -> dict of ShapeDtypeStruct

Batch layout (dense dict of arrays; unused keys absent):
    dense       [B, n_dense] f32    continuous features
    sparse_ids  [B, F, P]   i32     multi-hot ids, -1-padded
    history_ids [B, T]      i32     behaviour sequence (DIN/DIEN/MIND)
    target_id   [B]         i32     candidate item (DIN/DIEN/MIND)
    history_cat_ids [B, T]  i32     the behaviours' categories (item_category)
    target_cat_id   [B]     i32     the candidate's category (item_category)
    profile_ids [B, n]      i32     user/context ids (DIN/DIEN)
    label       [B]         f32     click label (training)
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from repro.models.embedding import EmbeddingConfig


@dataclasses.dataclass(frozen=True)
class RecsysConfig:
    """Family-agnostic recsys configuration; models read what they need."""

    name: str
    embedding: EmbeddingConfig
    n_dense: int = 0
    bottom_mlp: tuple[int, ...] = ()       # hidden+out sizes after n_dense input
    top_mlp: tuple[int, ...] = ()          # hidden+out sizes, output appended
    interaction: str = "dot"               # dot | concat | target-attn | multi-interest
    # DIN/DIEN/MIND:
    seq_len: int = 0
    attn_mlp: tuple[int, ...] = ()         # DIN attention-unit hidden sizes
    use_gru: bool = False                  # DIEN
    item_category: bool = False            # behaviour = item ⊕ category (feature 1)
    n_interests: int = 0                   # MIND
    capsule_iters: int = 3                 # MIND routing iterations
    # MT-WnD:
    n_tasks: int = 1
    dtype: Any = jnp.float32

    @property
    def embed_dim(self) -> int:
        return self.embedding.dim

    @property
    def behaviour_tables(self) -> int:
        """Tables whose rows make a behaviour: the item's, and the category's."""
        return 2 if self.item_category else 1


def binary_ce(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Numerically-stable sigmoid cross-entropy, mean over batch/tasks."""
    logits = logits.astype(jnp.float32)
    labels = labels.astype(jnp.float32)
    if logits.ndim > labels.ndim:
        labels = labels[..., None]  # broadcast labels over task dim
    zeros = jnp.zeros_like(logits)
    loss = jnp.maximum(logits, zeros) - logits * labels + jnp.log1p(
        jnp.exp(-jnp.abs(logits))
    )
    return loss.mean()


def input_specs(
    cfg: RecsysConfig,
    batch_size: int,
    *,
    with_labels: bool = False,
    n_candidates: int = 0,
):
    """ShapeDtypeStruct stand-ins for the dry-run (no allocation)."""
    emb = cfg.embedding
    specs: dict[str, jax.ShapeDtypeStruct] = {}
    if cfg.n_dense:
        specs["dense"] = jax.ShapeDtypeStruct((batch_size, cfg.n_dense), cfg.dtype)
    if cfg.interaction in ("dot", "concat"):
        specs["sparse_ids"] = jax.ShapeDtypeStruct(
            (batch_size, emb.num_features, emb.max_pooling), jnp.int32
        )
    if cfg.seq_len:
        specs["history_ids"] = jax.ShapeDtypeStruct((batch_size, cfg.seq_len), jnp.int32)
        if cfg.item_category:
            specs["history_cat_ids"] = specs["history_ids"]
        if not n_candidates:
            specs["target_id"] = jax.ShapeDtypeStruct((batch_size,), jnp.int32)
            if cfg.item_category:
                specs["target_cat_id"] = specs["target_id"]
        n_profile = emb.num_features - cfg.behaviour_tables
        if n_profile:
            specs["profile_ids"] = jax.ShapeDtypeStruct((batch_size, n_profile), jnp.int32)
    if n_candidates:
        specs["candidate_ids"] = jax.ShapeDtypeStruct((n_candidates,), jnp.int32)
    if with_labels:
        shape = (batch_size,) if cfg.n_tasks == 1 else (batch_size, cfg.n_tasks)
        specs["label"] = jax.ShapeDtypeStruct(shape, cfg.dtype)
    return specs
