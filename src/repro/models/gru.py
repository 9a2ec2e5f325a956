"""GRU and AUGRU recurrences of DIEN (arXiv:1809.03672, eqs. 1-4 and 8).

A cell's parameters are three gates (``r`` reset, ``z`` update, ``h``
candidate), each ``wx [in, hidden]``, ``wh [hidden, hidden]`` and ``b``:

    r  = sigmoid(x wx_r + h wh_r + b_r)
    z  = sigmoid(x wx_z + h wh_z + b_z)
    hh = tanh(x wx_h + (r * h) wh_h + b_h)
    h' = (1 - z) * h + z * hh

The reset gate scales the state before its product (Cho et al., as
TensorFlow's ``GRUCell`` does), not the product as eq. 3 writes it.  The
AUGRU (eq. 8) scales the update gate by an attention weight ``a``, so a
step of weight 0 keeps its state: ``h' = (1 - a z) * h + a z * hh``.

Kept apart from the attention unit (``models/din.py``) so that a device
trace can be read by source file even inside the recurrence's loop body.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.common.init import normal_init


def init_gru(key, in_dim, hidden, dtype=jnp.float32):
    ks = jax.random.split(key, 3)

    def gate(k):
        return {
            "wx": normal_init(k, (in_dim, hidden), stddev=0.05, dtype=dtype),
            "wh": normal_init(jax.random.fold_in(k, 1), (hidden, hidden), stddev=0.05, dtype=dtype),
            "b": jnp.zeros((hidden,), dtype),
        }
    return {"r": gate(ks[0]), "z": gate(ks[1]), "h": gate(ks[2])}


def gru_cell(p, h, x, update_scale=None):
    r = jax.nn.sigmoid(x @ p["r"]["wx"] + h @ p["r"]["wh"] + p["r"]["b"])
    z = jax.nn.sigmoid(x @ p["z"]["wx"] + h @ p["z"]["wh"] + p["z"]["b"])
    hh = jnp.tanh(x @ p["h"]["wx"] + (r * h) @ p["h"]["wh"] + p["h"]["b"])
    if update_scale is not None:  # AUGRU: attention scales the update gate
        z = z * update_scale[:, None]
    return (1.0 - z) * h + z * hh


def run_gru(p, xs, att=None):
    """xs [B, T, D] -> all hidden states [B, T, D] via lax.scan over T;
    with ``att`` [B, T], the AUGRU."""
    B, T, D = xs.shape
    h0 = jnp.zeros((B, D), xs.dtype)
    xs_t = xs.swapaxes(0, 1)  # [T, B, D]
    if att is None:
        def step(h, x):
            h = gru_cell(p, h, x)
            return h, h
        _, hs = jax.lax.scan(step, h0, xs_t)
    else:
        def step_a(h, inp):
            x, a = inp
            h = gru_cell(p, h, x, update_scale=a)
            return h, h
        _, hs = jax.lax.scan(step_a, h0, (xs_t, att.swapaxes(0, 1)))
    return hs.swapaxes(0, 1)  # [B, T, D]
