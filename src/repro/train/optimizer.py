"""Optimizers (pytree-functional, no optax dependency).

- ``adamw``  : LM / GNN training.
- ``adagrad``: DLRM-style embedding training (row-wise variant keeps one
  accumulator scalar per embedding row — the production recsys choice,
  8x less optimizer memory on multi-GB tables).
- ``sgd``    : baseline.

Each factory returns (init_fn, update_fn):
    state = init_fn(params)
    params, state = update_fn(params, grads, state)
"""
from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp

from repro.models.embedding import LineTable


@dataclasses.dataclass(frozen=True)
class Optimizer:
    init: Callable
    update: Callable
    name: str = ""


def sgd(lr: float = 0.01, momentum: float = 0.0) -> Optimizer:
    def init(params):
        if momentum:
            return {"mu": jax.tree.map(jnp.zeros_like, params)}
        return {}

    def update(params, grads, state):
        if momentum:
            mu = jax.tree.map(lambda m, g: momentum * m + g, state["mu"], grads)
            params = jax.tree.map(lambda p, m: p - lr * m, params, mu)
            return params, {"mu": mu}
        return jax.tree.map(lambda p, g: p - lr * g, params, grads), state

    return Optimizer(init, update, f"sgd(lr={lr})")


def adamw(lr: float = 3e-4, b1: float = 0.9, b2: float = 0.95,
          eps: float = 1e-8, weight_decay: float = 0.0) -> Optimizer:
    def init(params):
        return {
            "m": jax.tree.map(lambda p: jnp.zeros_like(p, jnp.float32), params),
            "v": jax.tree.map(lambda p: jnp.zeros_like(p, jnp.float32), params),
            "step": jnp.zeros((), jnp.int32),
        }

    def update(params, grads, state):
        step = state["step"] + 1
        m = jax.tree.map(lambda m_, g: b1 * m_ + (1 - b1) * g.astype(jnp.float32),
                         state["m"], grads)
        v = jax.tree.map(
            lambda v_, g: b2 * v_ + (1 - b2) * jnp.square(g.astype(jnp.float32)),
            state["v"], grads)
        bc1 = 1 - b1 ** step.astype(jnp.float32)
        bc2 = 1 - b2 ** step.astype(jnp.float32)

        def upd(p, m_, v_):
            u = (m_ / bc1) / (jnp.sqrt(v_ / bc2) + eps)
            if weight_decay:
                u = u + weight_decay * p.astype(jnp.float32)
            return (p.astype(jnp.float32) - lr * u).astype(p.dtype)

        params = jax.tree.map(upd, params, m, v)
        return params, {"m": m, "v": v, "step": step}

    return Optimizer(init, update, f"adamw(lr={lr})")


def rowwise_adagrad(lr: float = 0.01, eps: float = 1e-8,
                    embedding_keys: tuple[str, ...] = ("table", "hot", "cold"),
                    ) -> Optimizer:
    """AdaGrad with row-wise accumulators for 2-D embedding tables (one
    scalar per row, a ``LineTable``'s rows included) and full accumulators
    elsewhere."""

    def _is_embedding(path, p) -> bool:
        return isinstance(p, LineTable) or (
            any(getattr(k, "key", None) in embedding_keys for k in path)
            and p.ndim == 2)

    def _rows(p):
        return p.rows() if isinstance(p, LineTable) else p

    def _is_table(x) -> bool:
        return isinstance(x, LineTable)

    def init(params):
        def acc(path, p):
            if _is_embedding(path, p):
                return jnp.zeros((p.shape[0], 1), jnp.float32)
            return jnp.zeros_like(p, jnp.float32)
        return {"acc": jax.tree_util.tree_map_with_path(acc, params,
                                                        is_leaf=_is_table)}

    def update(params, grads, state):
        def upd(path, p, g, a):
            g32 = _rows(g).astype(jnp.float32)
            if _is_embedding(path, p):
                a_new = a + jnp.mean(jnp.square(g32), axis=1, keepdims=True)
            else:
                a_new = a + jnp.square(g32)
            p_new = _rows(p).astype(jnp.float32) - lr * g32 / (jnp.sqrt(a_new) + eps)
            p_new = p_new.astype(p.dtype)
            if isinstance(p, LineTable):
                p_new = LineTable.pack(p_new, p.lines.shape[0])
            return p_new, a_new

        flat = jax.tree_util.tree_map_with_path(
            lambda path, p, g, a: upd(path, p, g, a), params, grads, state["acc"],
            is_leaf=_is_table,
        )
        params = jax.tree.map(lambda t: t[0], flat,
                              is_leaf=lambda x: isinstance(x, tuple))
        acc = jax.tree.map(lambda t: t[1], flat,
                           is_leaf=lambda x: isinstance(x, tuple))
        return params, {"acc": acc}

    return Optimizer(init, update, f"rowwise_adagrad(lr={lr})")
