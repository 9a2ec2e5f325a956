"""DIN (arXiv:1706.06978) and DIEN (arXiv:1809.03672).

Embedding layout convention for behaviour-sequence models: feature 0 of the
EmbeddingConfig is the ITEM table (shared by history_ids and target_id).
With ``cfg.item_category`` feature 1 is the CATEGORY table (shared by
history_cat_ids and target_cat_id), and a behaviour or the target is its
item's row followed by its category's row, as the DIEN authors' released
code builds them.  The remaining features are 1-hot profile/context tables
looked up via batch["profile_ids"] [B, n_profile].

DIN: local activation unit — per history item, an MLP over
[e_h, e_t, e_h - e_t, e_h * e_t] produces an attention weight; the weighted
sum of history embeddings is the user interest vector.

DIEN (cfg.use_gru): interest-extractor GRU over history, then AUGRU
(attention-update-gate GRU) with DIN-style scores drives interest evolution;
both recurrences live in ``models/gru.py``.

``apply`` names its layers with ``jax.named_scope``: ``sparse`` (the history,
target and profile lookups), then ``dense``, holding ``gru`` (the interest
extractor), ``attention`` (the attention unit and its softmax or weighted
sum), ``augru`` (interest evolution) and the top MLP's ``mlp``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import embedding as emb_lib
from repro.models import gru as gru_lib
from repro.models.layers import apply_mlp, init_mlp
from repro.models.recsys_base import RecsysConfig


def behaviour_dim(cfg: RecsysConfig) -> int:
    """Width of a behaviour and of the target: the item's row, followed by
    its category's row where ``cfg.item_category``.  Both GRUs' hidden size."""
    return cfg.embed_dim * cfg.behaviour_tables


def _rows(params, ids, feature: int, cfg: RecsysConfig):
    """Rows of ``feature``'s table; a -1 id reads the table's row 0.

    Routes through the model-axis-sharded gather under a mesh context."""
    from repro.dist.sharded_embedding import sharded_row_gather

    base = int(cfg.embedding.row_offsets[feature])
    return sharded_row_gather(params["embedding"]["table"], base + jnp.maximum(ids, 0), None)


def _behaviour_lookup(params, item_ids, cat_ids, cfg: RecsysConfig):
    """Behaviour rows [..., behaviour_dim]: the item's row, then (with
    ``cfg.item_category``) the row of ``cat_ids`` in the category table."""
    rows = _rows(params, item_ids, 0, cfg)
    if cfg.item_category:
        rows = jnp.concatenate([rows, _rows(params, cat_ids, 1, cfg)], axis=-1)
    return rows


def _profile_lookup(params, profile_ids, cfg: RecsysConfig):
    """1-hot lookups of the profile features (those after the behaviour's)
    -> [B, n_profile * D].  A missing id (-1) gives the zero vector, as a
    masked history step does."""
    valid = (profile_ids >= 0).astype(cfg.dtype)
    first = cfg.behaviour_tables
    outs = []
    for f in range(first, cfg.embedding.num_features):
        rows = _rows(params, profile_ids[:, f - first], f, cfg)
        outs.append(rows * valid[:, f - first, None])
    return jnp.concatenate(outs, axis=-1)


def init(key, cfg: RecsysConfig):
    k_emb, k_attn, k_top, k_gru1, k_gru2 = jax.random.split(key, 5)
    w = behaviour_dim(cfg)
    params = {
        "embedding": emb_lib.init_embedding(k_emb, cfg.embedding),
        # attention unit input: [e_h, e_t, e_h - e_t, e_h * e_t]
        "attn_mlp": init_mlp(k_attn, (4 * w, *cfg.attn_mlp, 1), dtype=cfg.dtype),
    }
    n_profile = cfg.embedding.num_features - cfg.behaviour_tables
    top_in = 2 * w + n_profile * cfg.embed_dim  # [interest, e_target, profiles]
    params["top_mlp"] = init_mlp(k_top, (top_in, *cfg.top_mlp, 1), dtype=cfg.dtype)
    if cfg.use_gru:
        params["gru"] = gru_lib.init_gru(k_gru1, w, w, dtype=cfg.dtype)
        params["augru"] = gru_lib.init_gru(k_gru2, w, w, dtype=cfg.dtype)
    return params


def attention_scores(params, hist_emb, target_emb, mask, cfg: RecsysConfig):
    """DIN local activation unit -> [B, T] weights (not normalized, per paper;
    masked positions get zero weight)."""
    B, T, d = hist_emb.shape
    t = jnp.broadcast_to(target_emb[:, None, :], (B, T, d))
    feat = jnp.concatenate([hist_emb, t, hist_emb - t, hist_emb * t], axis=-1)
    logit = apply_mlp(params["attn_mlp"], feat)[..., 0]  # [B, T]
    return jnp.where(mask, logit, 0.0)


def apply(params, batch, cfg: RecsysConfig) -> jax.Array:
    hist = batch["history_ids"]                     # [B, T]
    mask = hist >= 0
    cats = (batch["history_cat_ids"], batch["target_cat_id"]) if cfg.item_category else (None, None)
    with jax.named_scope("sparse"):
        hist_emb = _behaviour_lookup(params, hist, cats[0], cfg)
        hist_emb = hist_emb * mask[..., None].astype(cfg.dtype)  # [B, T, W]
        target_emb = _behaviour_lookup(params, batch["target_id"], cats[1], cfg)  # [B, W]
        feats = [target_emb]
        if cfg.embedding.num_features > cfg.behaviour_tables and "profile_ids" in batch:
            feats.append(_profile_lookup(params, batch["profile_ids"], cfg))

    with jax.named_scope("dense"):
        if cfg.use_gru:  # DIEN
            with jax.named_scope("gru"):                # interest extractor
                states = gru_lib.run_gru(params["gru"], hist_emb)
            with jax.named_scope("attention"):
                att = attention_scores(params, states, target_emb, mask, cfg)
                att = jax.nn.softmax(jnp.where(mask, att, -1e30), axis=-1)
            with jax.named_scope("augru"):              # interest evolution
                interest = gru_lib.run_gru(params["augru"], states, att=att)[:, -1, :]
        else:  # DIN
            with jax.named_scope("attention"):
                att = attention_scores(params, hist_emb, target_emb, mask, cfg)
                interest = jnp.einsum("bt,btd->bd", att, hist_emb)

        x = jnp.concatenate([interest, *feats], axis=-1)
        return apply_mlp(params["top_mlp"], x)[:, 0]


def retrieval_scores(params, batch, candidate_ids, cfg: RecsysConfig) -> jax.Array:
    """Score one user's history against N candidate items -> [N] (DIN: item rows).

    DIN's attention depends on the target, so each candidate re-attends over
    the history — but the history embeddings are gathered ONCE (not N times)
    and broadcast; the N x T attention-unit MLP is the honest cost.
    """
    hist = batch["history_ids"][0]                       # [T]
    mask = hist >= 0
    hist_emb = _rows(params, hist, 0, cfg)               # [T, D]
    hist_emb = hist_emb * mask[:, None].astype(cfg.dtype)
    cand_emb = _rows(params, candidate_ids, 0, cfg)      # [N, D]
    N, D = cand_emb.shape
    T = hist.shape[0]
    h = jnp.broadcast_to(hist_emb[None], (N, T, D))
    att = attention_scores(params, h, cand_emb, jnp.broadcast_to(mask[None], (N, T)), cfg)
    interest = jnp.einsum("nt,ntd->nd", att, h)
    feats = [interest, cand_emb]
    if cfg.embedding.num_features > cfg.behaviour_tables and "profile_ids" in batch:
        prof = _profile_lookup(params, batch["profile_ids"], cfg)  # [1, (F-1)D]
        feats.append(jnp.broadcast_to(prof, (N, prof.shape[-1])))
    x = jnp.concatenate(feats, axis=-1)
    return apply_mlp(params["top_mlp"], x)[:, 0]
