"""Dense building blocks: MLPs, norms, rotary embedding, GQA attention, MoE.

Functional convention: ``init_*(key, ...) -> params`` pytree and a matching
apply function. No framework dependency — params are plain dicts so they
shard cleanly with pjit/shard_map and checkpoint as raw arrays.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Sequence

import jax
import jax.numpy as jnp

from repro.common.init import he_init, normal_init, xavier_init


# ---------------------------------------------------------------------------
# MLP (the recsys DenseNet primitive: Bottom-FC / Predict-FC / attention MLPs)
# ---------------------------------------------------------------------------


def init_mlp(key, sizes: Sequence[int], dtype=jnp.float32):
    """sizes = [in, h1, ..., out]; ReLU hidden, linear output."""
    params = []
    keys = jax.random.split(key, len(sizes) - 1)
    for i, k in enumerate(keys):
        params.append(
            {
                "w": he_init(k, (sizes[i], sizes[i + 1]), dtype=dtype),
                "b": jnp.zeros((sizes[i + 1],), dtype),
            }
        )
    return params


def apply_mlp(params, x, *, final_activation=None):
    """ReLU between layers; ``final_activation`` in {None,'relu','sigmoid'}.
    Runs under the ``mlp`` scope."""
    n = len(params)
    with jax.named_scope("mlp"):
        for i, layer in enumerate(params):
            x = x @ layer["w"] + layer["b"]
            if i < n - 1:
                x = jax.nn.relu(x)
            elif final_activation == "relu":
                x = jax.nn.relu(x)
            elif final_activation == "sigmoid":
                x = jax.nn.sigmoid(x)
        return x


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def init_rmsnorm(dim, dtype=jnp.float32):
    return {"scale": jnp.ones((dim,), dtype)}


def apply_rmsnorm(params, x, eps=1e-6):
    dtype = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    out = x32 * jax.lax.rsqrt(var + eps)
    return (out * params["scale"].astype(jnp.float32)).astype(dtype)


def init_layernorm(dim, dtype=jnp.float32):
    return {"scale": jnp.ones((dim,), dtype), "bias": jnp.zeros((dim,), dtype)}


def apply_layernorm(params, x, eps=1e-5):
    dtype = x.dtype
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mu), axis=-1, keepdims=True)
    out = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return (out * params["scale"] + params["bias"]).astype(dtype)


# ---------------------------------------------------------------------------
# Rotary position embedding
# ---------------------------------------------------------------------------


def rope_angles(positions: jax.Array, head_dim: int, theta: float = 10000.0):
    """positions [*, T] -> (cos, sin) each [*, T, head_dim/2] in f32."""
    half = head_dim // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None] * freqs  # [*, T, half]
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x: [..., T, n_heads, head_dim]; cos/sin: [..., T, head_dim/2]."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    c = cos[..., None, :].astype(x.dtype)
    s = sin[..., None, :].astype(x.dtype)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


# ---------------------------------------------------------------------------
# GQA attention (shared by all assigned LM archs)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class AttentionConfig:
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    qkv_bias: bool = False  # qwen2 uses bias on QKV
    rope_theta: float = 10000.0


def init_attention(key, cfg: AttentionConfig, dtype=jnp.float32):
    kq, kk, kv, ko = jax.random.split(key, 4)
    d, h, kvh, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    p = {
        "wq": normal_init(kq, (d, h * hd), dtype=dtype),
        "wk": normal_init(kk, (d, kvh * hd), dtype=dtype),
        "wv": normal_init(kv, (d, kvh * hd), dtype=dtype),
        "wo": normal_init(ko, (h * hd, d), dtype=dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((h * hd,), dtype)
        p["bk"] = jnp.zeros((kvh * hd,), dtype)
        p["bv"] = jnp.zeros((kvh * hd,), dtype)
    return p


def qkv_projection(params, x, cfg: AttentionConfig):
    """x [B, T, d] -> q [B, T, H, hd], k/v [B, T, KVH, hd]."""
    B, T, _ = x.shape
    q = x @ params["wq"]
    k = x @ params["wk"]
    v = x @ params["wv"]
    if cfg.qkv_bias:
        q = q + params["bq"]
        k = k + params["bk"]
        v = v + params["bv"]
    q = q.reshape(B, T, cfg.n_heads, cfg.head_dim)
    k = k.reshape(B, T, cfg.n_kv_heads, cfg.head_dim)
    v = v.reshape(B, T, cfg.n_kv_heads, cfg.head_dim)
    return q, k, v


def gqa_attention(q, k, v, *, causal: bool = True, kv_valid_len=None):
    """Reference dot-product GQA attention (pure jnp; the Pallas flash
    kernel in repro/kernels/flash_attention is the production path).

    q: [B, Tq, H, hd]; k/v: [B, Tk, KVH, hd]. H must be a multiple of KVH.
    kv_valid_len: optional [B] — mask KV positions >= this (decode cache).
    """
    B, Tq, H, hd = q.shape
    KVH = k.shape[2]
    group = H // KVH
    qg = q.reshape(B, Tq, KVH, group, hd)
    scale = 1.0 / jnp.sqrt(jnp.asarray(hd, jnp.float32)).astype(q.dtype)
    logits = jnp.einsum("btkgh,bskh->bkgts", qg, k) * scale  # [B,KVH,g,Tq,Tk]
    Tk = k.shape[1]
    neg = jnp.asarray(-1e30, logits.dtype)
    if causal and Tq > 1:
        # offset alignment: query i attends kv j <= i + (Tk - Tq)
        mask = jnp.arange(Tk)[None, :] <= (jnp.arange(Tq)[:, None] + (Tk - Tq))
        logits = jnp.where(mask[None, None, None], logits, neg)
    if kv_valid_len is not None:
        mask = jnp.arange(Tk)[None, :] < kv_valid_len[:, None]  # [B, Tk]
        logits = jnp.where(mask[:, None, None, None], logits, neg)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgts,bskh->btkgh", probs, v)
    return out.reshape(B, Tq, H, hd)


def attention_output(params, attn_out):
    B, T = attn_out.shape[:2]
    return attn_out.reshape(B, T, -1) @ params["wo"]


# ---------------------------------------------------------------------------
# SwiGLU FFN + MoE
# ---------------------------------------------------------------------------


def init_swiglu(key, d_model: int, d_ff: int, dtype=jnp.float32):
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "w_gate": normal_init(k1, (d_model, d_ff), dtype=dtype),
        "w_up": normal_init(k2, (d_model, d_ff), dtype=dtype),
        "w_down": normal_init(k3, (d_ff, d_model), dtype=dtype),
    }


def apply_swiglu(params, x):
    return (jax.nn.silu(x @ params["w_gate"]) * (x @ params["w_up"])) @ params["w_down"]


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    d_model: int
    d_ff: int                 # per-expert FFN width
    n_experts: int
    top_k: int
    n_shared: int = 0         # shared (always-on) experts, qwen2-moe style
    shared_d_ff: int = 0      # width of the fused shared expert (0 = d_ff * n_shared)
    router_dtype: Any = jnp.float32
    capacity_factor: float = 1.25
    # expert arrays are stored zero-padded to a multiple of this so the
    # E dimension shards evenly over the model axis (EP); the router only
    # ever routes to the first n_experts.
    pad_to: int = 16

    @property
    def n_experts_padded(self) -> int:
        return -(-self.n_experts // self.pad_to) * self.pad_to


def init_moe(key, cfg: MoEConfig, dtype=jnp.float32):
    kr, ke, ks = jax.random.split(key, 3)
    E, d, f = cfg.n_experts_padded, cfg.d_model, cfg.d_ff
    # Experts stored stacked [E_pad, ...] so they shard evenly over the
    # model axis; rows >= n_experts are zero-padded and never routed to.
    ekeys = jax.random.split(ke, 3)

    def experts_init(k, shape):
        w = normal_init(k, shape, dtype=dtype)
        if E > cfg.n_experts:
            zero = jnp.zeros((E - cfg.n_experts, *shape[1:]), dtype)
            w = jnp.concatenate([w[: cfg.n_experts], zero], axis=0)
        return w

    params = {
        "router": normal_init(kr, (d, cfg.n_experts), stddev=0.006,
                              dtype=jnp.float32),
        "experts": {
            "w_gate": experts_init(ekeys[0], (E, d, f)),
            "w_up": experts_init(ekeys[1], (E, d, f)),
            "w_down": experts_init(ekeys[2], (E, f, d)),
        },
    }
    if cfg.n_shared:
        sf = cfg.shared_d_ff or cfg.d_ff * cfg.n_shared
        params["shared"] = init_swiglu(ks, d, sf, dtype=dtype)
    return params


def moe_router(params, x, cfg: MoEConfig):
    """x [N, d] -> (topk_idx [N,k], topk_weight [N,k], aux_loss scalar)."""
    logits = x.astype(cfg.router_dtype) @ params["router"]
    probs = jax.nn.softmax(logits, axis=-1)
    topk_w, topk_idx = jax.lax.top_k(probs, cfg.top_k)
    topk_w = topk_w / jnp.maximum(topk_w.sum(-1, keepdims=True), 1e-9)
    # Switch-style load-balance auxiliary loss.
    E = cfg.n_experts
    me = probs.mean(axis=0)                                   # mean router prob
    ce = jnp.zeros((E,), probs.dtype).at[topk_idx.reshape(-1)].add(
        1.0 / (topk_idx.size)
    )                                                          # token fraction
    aux = E * jnp.sum(me * ce)
    return topk_idx, topk_w.astype(x.dtype), aux


def apply_moe_dense(params, x, cfg: MoEConfig):
    """Reference dense-dispatch MoE: every expert runs on every token via a
    one-hot mixing matrix. O(E·N·d·f) — used for correctness tests and tiny
    smoke configs; the EP all_to_all path lives in repro/dist/moe.py.

    x: [N, d]; returns ([N, d], aux_loss).
    """
    topk_idx, topk_w, aux = moe_router(params, x, cfg)
    E = cfg.n_experts
    # combine[n, e] = weight of expert e for token n (0 if not routed)
    combine = jnp.zeros((x.shape[0], E), x.dtype)
    for j in range(cfg.top_k):
        combine = combine.at[jnp.arange(x.shape[0]), topk_idx[:, j]].add(topk_w[:, j])
    ex = jax.tree.map(lambda t: t[: cfg.n_experts], params["experts"])
    h_gate = jnp.einsum("nd,edf->enf", x, ex["w_gate"])
    h_up = jnp.einsum("nd,edf->enf", x, ex["w_up"])
    h = jax.nn.silu(h_gate) * h_up
    y_e = jnp.einsum("enf,efd->end", h, ex["w_down"])  # [E, N, d]
    y = jnp.einsum("end,ne->nd", y_e, combine)
    if cfg.n_shared:
        y = y + apply_swiglu(params["shared"], x)
    return y, aux
