"""Plain DLRM reference (Naumov et al., arXiv:1906.00091), for the benchmark.

Written from the paper's equations and the configuration file's sizes; it
imports nothing of the program under test.

    x      = relu(W_L ... relu(W_1 dense + b_1) ... + b_L)     bottom MLP
    e_f    = sum_{p < n_f} E_f[id_{f,p}]                         pooled bag f
    z_ij   = <v_i, v_j>  for i < j,  v = (x, e_1, ..., e_F)      dot interaction
    logit  = top MLP(concat(x, z)), ReLU between layers, linear output

Weights are drawn from the seed by the same recipe the configuration
states (``weights``): one combined table of all features, uniform in
(-1, 1) scaled by 1/sqrt(rows) of each feature's table, and He-normal MLP
weights with zero biases.

``precision`` names how the arithmetic is done:

- ``"default"``: float32 values, matrix products at the platform's default
  precision for float32, as the configuration states.  On a TPU that is
  one MXU pass: operands rounded to bfloat16, products summed in float32.
  On a CPU it is full float32.
- ``"highest"``: float32 throughout, products at full float32 precision.
- ``"bfloat16"``: every weight, table row and activation held in bfloat16
  (the control: the next precision below the stated one).

Work counts (``work``) give the least operations and bytes the forward of
one launch needs, for the roofline and MFU readers.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

PRECISIONS = ("default", "highest", "bfloat16")


def total_rows(cfg: dict) -> int:
    raw = cfg["num_tables"] * cfg["rows_per_table"]
    pad = cfg["weights"]["row_pad"]
    return -(-raw // pad) * pad


def mlp_sizes(cfg: dict) -> tuple[tuple[int, ...], tuple[int, ...]]:
    """(bottom, top) layer sizes, input first, the top's output 1 last."""
    d = cfg["embedding_dim"]
    n_vec = cfg["num_tables"] + 1
    bottom = (cfg["num_dense"], *cfg["bottom_mlp"])
    top = (d + n_vec * (n_vec - 1) // 2, *cfg["top_mlp"], 1)
    return bottom, top


def _mlp_init(key, sizes):
    layers = []
    for i, k in enumerate(jax.random.split(key, len(sizes) - 1)):
        w = jax.random.normal(k, (sizes[i], sizes[i + 1]))
        layers.append({"w": w * jnp.sqrt(2.0 / sizes[i]),
                       "b": jnp.zeros((sizes[i + 1],), jnp.float32)})
    return layers


def init(seed: int, cfg: dict) -> dict:
    """Float32 weights from ``seed``, drawn on the default device."""
    rows, tables = total_rows(cfg), cfg["num_tables"]
    per = cfg["rows_per_table"]
    bottom, top = mlp_sizes(cfg)

    def draw(key):
        k_emb, k_bot, k_top = jax.random.split(key, 3)
        table = jax.random.uniform(k_emb, (rows, cfg["embedding_dim"]),
                                   minval=-1.0, maxval=1.0, dtype=jnp.float32)
        row = jnp.arange(rows)[:, None]
        scale = jnp.where(row < tables * per,
                          jnp.float32(1.0 / math.sqrt(per)), jnp.float32(1.0))
        return {"table": table * scale, "bottom": _mlp_init(k_bot, bottom),
                "top": _mlp_init(k_top, top)}

    return jax.jit(draw)(jax.random.PRNGKey(seed % 2**32))


def _dot(a, b, precision: str, spec: str = "ij,jk->ik"):
    if precision == "highest":
        return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST)
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.DEFAULT)


def _mlp(layers, x, precision: str, relu_last: bool):
    for i, layer in enumerate(layers):
        x = _dot(x, layer["w"], precision) + layer["b"]
        if i < len(layers) - 1 or relu_last:
            x = jnp.maximum(x, 0)
    return x


def forward(params: dict, dense, ids, cfg: dict, precision: str = "default"):
    """Logits [B] for dense [B, num_dense] and ids [B, F, P] (-1 = empty)."""
    if precision not in PRECISIONS:
        raise ValueError(f"unknown precision {precision!r}")
    dtype = jnp.bfloat16 if precision == "bfloat16" else jnp.float32
    params = jax.tree.map(lambda a: a.astype(dtype), params)
    dense = dense.astype(dtype)
    B, F, P = ids.shape
    per = cfg["rows_per_table"]
    valid = ids >= 0
    rows = jnp.where(valid, ids, 0) + (jnp.arange(F) * per)[None, :, None]
    bags = params["table"][rows] * valid[..., None].astype(dtype)
    pooled = bags.sum(axis=2)                                   # [B, F, D]
    x = _mlp(params["bottom"], dense, precision, relu_last=True)  # [B, D]
    v = jnp.concatenate([x[:, None, :], pooled], axis=1)        # [B, F+1, D]
    z = _dot(v, v, precision, "bnd,bmd->bnm")
    i, j = np.triu_indices(F + 1, k=1)
    top_in = jnp.concatenate([x, z[:, i, j]], axis=1)
    return _mlp(params["top"], top_in, precision, relu_last=False)[:, 0]


def forward_fn(cfg: dict, precision: str):
    """The jitted forward for one precision, of a launch's batch
    ``{"dense": [B, num_dense], "sparse_ids": [B, F, P]}``."""
    return jax.jit(lambda p, batch: forward(p, batch["dense"], batch["sparse_ids"],
                                            cfg, precision))


# ---------------------------------------------------------------------------
# Work counts: the least operations and bytes one launch needs
# ---------------------------------------------------------------------------


def dense_flops_per_item(cfg: dict) -> int:
    """Multiply-adds x 2 of the bottom MLP, the pairwise dots (i < j only)
    and the top MLP, for one item."""
    bottom, top = mlp_sizes(cfg)
    n_vec = cfg["num_tables"] + 1
    mlp = sum(a * b for a, b in zip(bottom, bottom[1:]))
    mlp += sum(a * b for a, b in zip(top, top[1:]))
    dots = n_vec * (n_vec - 1) // 2 * cfg["embedding_dim"]
    return 2 * (mlp + dots)


def dense_weight_bytes(cfg: dict) -> int:
    bottom, top = mlp_sizes(cfg)
    params = sum(a * b + b for s in (bottom, top) for a, b in zip(s, s[1:]))
    return 4 * params


def work(cfg: dict, items: int, valid_lookups: int, launches: int) -> dict:
    """Least operations and bytes of ``launches`` launches that scored
    ``items`` real items with ``valid_lookups`` table rows looked up in all.
    Padding slots are work a launch does but not work the forward needs, so
    only real items count.

    sparse: each valid row read once (dim x 4 B), the ids of every item
    read (F x P x 4 B) and its pooled vectors written (F x D x 4 B); one add
    per element of each valid row.
    dense: its operations; its weights read once per launch, and per item
    the pooled vectors and dense features read and one logit written.
    flops: the whole forward's, sparse and dense.
    """
    d, F, P = cfg["embedding_dim"], cfg["num_tables"], cfg["pooling"]
    sparse_flops = valid_lookups * d
    dense_flops = items * dense_flops_per_item(cfg)
    return {
        "flops": sparse_flops + dense_flops,
        "sparse_bytes": 4 * (valid_lookups * d + items * F * (P + d)),
        "sparse_flops": sparse_flops,
        "dense_flops": dense_flops,
        "dense_bytes": (launches * dense_weight_bytes(cfg)
                        + 4 * items * (F * d + cfg["num_dense"] + 1)),
    }
