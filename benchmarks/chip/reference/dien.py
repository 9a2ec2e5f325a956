"""Plain DIEN reference (Zhou et al., arXiv:1809.03672), for the benchmark.

Written from the paper's equations and the configuration file's sizes; it
imports nothing of the program under test.  An item is scored from its
user's behaviour history (item ids and their categories, -1 past its
length), the target item and its category, and the user's profile ids
(-1: missing, the zero vector):

    e_t    = E[item_t] ++ E[cat_t],  e_a = E[target] ++ E[target_cat]
    u_t    = sigmoid(e_t W_u + h_{t-1} U_u + b_u)             eq. 1
    r_t    = sigmoid(e_t W_r + h_{t-1} U_r + b_r)             eq. 2
    h~_t   = tanh(e_t W_h + (r_t * h_{t-1}) U_h + b_h)        eq. 3
    h_t    = (1 - u_t) * h_{t-1} + u_t * h~_t                 eq. 4
    a_t    = softmax over valid t of MLP([h_t, e_a, h_t - e_a, h_t * e_a])
    h'_t   = (1 - a_t u'_t) * h'_{t-1} + a_t u'_t * h~'_t        eq. 8, AUGRU
    logit  = MLP([h'_L, e_a, E[profile_1], E[profile_2]])

with the AUGRU's gates u'_t and h~'_t those of eqs. 1-3 over the inputs
h_1..h_T and its own state h'_{t-1}, and its own weights.  ``L`` is the
history's length: both recurrences keep their state past it.  The MLPs
have ReLU between layers and a linear output.  The file's first two
tables are the items' and the categories'; a behaviour is an item's row
and its category's side by side, 2 x 18 floats, and both GRUs are that
wide (``hidden``), as the authors' released code has them (EMBEDDING_DIM
18, HIDDEN_SIZE 36).  The other tables are the profile's.

Departures from the paper that the program shares (from the paper and
the authors' released code):

- ReLU in both MLPs (the authors' code uses sigmoid in the attention unit
  and Dice in the top MLP);
- the top MLP reads ``[h'_L, e_a, profile rows]`` (the authors' code
  feeds the user's row, e_a, the sum of the history's rows, its product
  with e_a and h'_L);
- DIN's MLP attention unit over ``[h, e_a, h - e_a, h * e_a]`` in place of
  eq. 6's bilinear ``h W e_a``, as the authors' code does;
- the reset gate scales the state before its product (eq. 3 above; the
  paper's eq. 3 scales the product ``r * (h U_h)``), as TensorFlow's
  ``GRUCell`` in the authors' code does;
- no auxiliary loss (it is training-only).

Weights are drawn from the seed by the recipe the configuration states
(``weights``): a key split in five (table, attention MLP, top MLP, GRU,
AUGRU); one combined table of all features, uniform in (-1, 1) scaled by
1/sqrt(rows) of each feature's table, then by ``table_scale``; He-normal
MLP weights with zero biases; each GRU's key split in three gates (reset,
update, candidate), the input weights normal x 0.05 from the gate's key
then x ``gru_input_scale``, the state weights normal x 0.05 from
``fold_in(key, 1)`` then x ``gru_state_scale``, zero biases.  The scales
make the history count: at 1/sqrt(rows) rows and 0.05 gates, dropping
the recurrences moves the scores by about as much as bfloat16 rounding
does (1e-5), so no limit could tell the one from the other.

``precision`` names how the arithmetic is done, as in ``reference/dlrm.py``:
``"default"`` (float32 values, matrix products at the platform's default
precision: one bfloat16 MXU pass on a TPU, full float32 on a CPU),
``"highest"`` (full float32 products) and ``"bfloat16"`` (every weight,
row, state and activation in bfloat16: the control).

Work counts (``work``) give the least operations and bytes of the
window's launches, for the MFU and roofline readers.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

PRECISIONS = ("default", "highest", "bfloat16")
GATES = ("reset", "update", "candidate")
BEHAVIOUR = 2  # tables whose rows make a behaviour: item, category


def total_rows(cfg: dict) -> int:
    raw = sum(cfg["table_rows"])
    pad = cfg["weights"]["row_pad"]
    return -(-raw // pad) * pad


def offsets(cfg: dict) -> list[int]:
    """First row of each feature (item, category, then profiles) in the
    combined table."""
    return [int(x) for x in np.cumsum([0] + cfg["table_rows"][:-1])]


def width(cfg: dict) -> int:
    """A behaviour's width, and both GRUs': an item's and a category's row."""
    return BEHAVIOUR * cfg["embedding_dim"]


def mlp_sizes(cfg: dict) -> tuple[tuple[int, ...], tuple[int, ...]]:
    """(attention unit, top) layer sizes, input first, output 1 last."""
    d, w = cfg["embedding_dim"], width(cfg)
    n_profile = len(cfg["table_rows"]) - BEHAVIOUR
    attn = (4 * w, *cfg["attn_mlp"], 1)
    top = (2 * w + n_profile * d, *cfg["top_mlp"], 1)
    return attn, top


def _mlp_init(key, sizes):
    layers = []
    for i, k in enumerate(jax.random.split(key, len(sizes) - 1)):
        w = jax.random.normal(k, (sizes[i], sizes[i + 1]))
        layers.append({"w": w * jnp.sqrt(2.0 / sizes[i]),
                       "b": jnp.zeros((sizes[i + 1],), jnp.float32)})
    return layers


def _gru_init(key, d, in_scale, state_scale):
    gates = {}
    for name, k in zip(GATES, jax.random.split(key, 3)):
        w_in = 0.05 * jax.random.normal(k, (d, d))
        w_state = 0.05 * jax.random.normal(jax.random.fold_in(k, 1), (d, d))
        gates[name] = {"w_in": w_in * in_scale, "w_state": w_state * state_scale,
                       "b": jnp.zeros((d,), jnp.float32)}
    return gates


def init(seed: int, cfg: dict) -> dict:
    """Float32 weights from ``seed``, drawn on the default device."""
    rows, d = total_rows(cfg), cfg["embedding_dim"]
    attn, top = mlp_sizes(cfg)
    ends = np.cumsum(cfg["table_rows"])
    w = cfg["weights"]
    scales = (jnp.float32(w["gru_input_scale"]), jnp.float32(w["gru_state_scale"]))

    def draw(key):
        k_emb, k_attn, k_top, k_gru, k_augru = jax.random.split(key, 5)
        table = jax.random.uniform(k_emb, (rows, d), minval=-1.0, maxval=1.0,
                                   dtype=jnp.float32)
        row = jnp.arange(rows)[:, None]
        scale = jnp.float32(1.0)
        for end, v in reversed(list(zip(ends, cfg["table_rows"]))):
            scale = jnp.where(row < end, jnp.float32(1.0 / math.sqrt(v)), scale)
        return {"table": table * scale * jnp.float32(w["table_scale"]),
                "attn": _mlp_init(k_attn, attn), "top": _mlp_init(k_top, top),
                "gru": _gru_init(k_gru, width(cfg), *scales),
                "augru": _gru_init(k_augru, width(cfg), *scales)}

    return jax.jit(draw)(jax.random.PRNGKey(seed % 2**32))


def _dot(a, b, precision: str):
    p = jax.lax.Precision.HIGHEST if precision == "highest" else jax.lax.Precision.DEFAULT
    return jnp.matmul(a, b, precision=p)


def _mlp(layers, x, precision: str):
    for i, layer in enumerate(layers):
        x = _dot(x, layer["w"], precision) + layer["b"]
        if i < len(layers) - 1:
            x = jnp.maximum(x, 0)
    return x


def _recurrence(g, xs, valid, att, precision: str):
    """The GRU (``att`` None) or the AUGRU over xs [B, T, D]; the state is
    kept where ``valid`` [B, T] is false.  All states [B, T, D]."""
    B, T, D = xs.shape

    def lin(gate, x, h):
        p = g[gate]
        return _dot(x, p["w_in"], precision) + _dot(h, p["w_state"], precision) + p["b"]

    def step(h, inp):
        x, ok, a = inp
        u = jax.nn.sigmoid(lin("update", x, h))
        r = jax.nn.sigmoid(lin("reset", x, h))
        p = g["candidate"]
        cand = jnp.tanh(_dot(x, p["w_in"], precision)
                        + _dot(r * h, p["w_state"], precision) + p["b"])
        if a is not None:
            u = a[:, None] * u
        new = (1 - u) * h + u * cand
        h = jnp.where(ok[:, None], new, h)
        return h, h

    seq = (xs.swapaxes(0, 1), valid.T, None if att is None else att.T)
    _, hs = jax.lax.scan(step, jnp.zeros((B, D), xs.dtype), seq)
    return hs.swapaxes(0, 1)


def _lookup(table, ids, offset: int):
    """Rows ``offset + ids`` of the combined table; -1 gives zeros."""
    ok = ids >= 0
    return table[jnp.where(ok, ids, 0) + offset] * ok[..., None].astype(table.dtype)


def forward(params: dict, batch: dict, cfg: dict, precision: str = "default"):
    """Logits [B] of ``history_ids`` and ``history_cat_ids [B, T]``,
    ``target_id`` and ``target_cat_id [B]`` and ``profile_ids [B, P]``."""
    if precision not in PRECISIONS:
        raise ValueError(f"unknown precision {precision!r}")
    dtype = jnp.bfloat16 if precision == "bfloat16" else jnp.float32
    p = jax.tree.map(lambda a: a.astype(dtype), params)
    table, offs = p["table"], offsets(cfg)
    hist = batch["history_ids"]
    valid = hist >= 0
    item, cat = offs[0], offs[1]
    e = jnp.concatenate([_lookup(table, hist, item),
                         _lookup(table, batch["history_cat_ids"], cat)], axis=-1)  # [B, T, W]
    e_a = jnp.concatenate([_lookup(table, batch["target_id"], item),
                           _lookup(table, batch["target_cat_id"], cat)], axis=-1)  # [B, W]
    prof = [_lookup(table, batch["profile_ids"][:, f], off)
            for f, off in enumerate(offs[BEHAVIOUR:])]

    h = _recurrence(p["gru"], e, valid, None, precision)     # interest extractor
    t = jnp.broadcast_to(e_a[:, None, :], h.shape)
    logits = _mlp(p["attn"], jnp.concatenate([h, t, h - t, h * t], axis=-1),
                  precision)[..., 0]                          # [B, T]
    top = jnp.max(jnp.where(valid, logits, -jnp.inf), axis=1, keepdims=True)
    any_valid = valid.any(axis=1, keepdims=True)
    ex = jnp.where(valid, jnp.exp(logits - jnp.where(any_valid, top, 0)), 0)
    a = jnp.where(any_valid, ex / ex.sum(axis=1, keepdims=True), 0)

    h2 = _recurrence(p["augru"], h, valid, a, precision)     # interest evolution
    x = jnp.concatenate([h2[:, -1], e_a, *prof], axis=-1)
    return _mlp(p["top"], x, precision)[:, 0]


def forward_fn(cfg: dict, precision: str):
    """The jitted forward for one precision, of a launch's batch."""
    return jax.jit(lambda p, batch: forward(p, batch, cfg, precision))


# ---------------------------------------------------------------------------
# Work counts: the least operations and bytes of the window's launches
# ---------------------------------------------------------------------------


def work(cfg: dict, items: int, valid_lookups: int, launches: int) -> dict:
    """Least operations and bytes of ``launches`` launches that scored
    ``items`` real items with ``valid_lookups`` table rows looked up in all
    (an item and a category row for each valid history step and for the
    target, and one row for each profile id).
    Padding (slots, history steps past a length) is work a launch does but
    not work the forward needs, so only valid steps count.

    flops (multiply-adds x 2 of the products): per valid step, both
    recurrences' three gates (input and state products) and the attention
    unit's MLP; per item, the top MLP.
    history_bytes: each valid row read once and written once gathered
    (dim x 4 B each), and every id of every item read (4 B: history items
    and categories, target item and category, profile ids).
    """
    d, w, k = cfg["embedding_dim"], width(cfg), BEHAVIOUR
    per_item = len(cfg["table_rows"])          # target's and profile rows
    steps = (valid_lookups - per_item * items) // k   # valid history steps
    attn, top = mlp_sizes(cfg)
    mlp = [sum(a * b for a, b in zip(s, s[1:])) for s in (attn, top)]
    recurrence = 2 * 3 * 2 * w * w             # two recurrences, three gates, two products
    ids = k * cfg["history"] + per_item       # ids an item sends
    return {
        "flops": 2 * (steps * (recurrence + mlp[0]) + items * mlp[1]),
        "history_bytes": 2 * valid_lookups * d * 4 + items * ids * 4,
    }
