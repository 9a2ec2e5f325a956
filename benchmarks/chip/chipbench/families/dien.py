"""The DIEN family (``"family": "dien"``): the program's behaviour-sequence
model (``models/din.py`` with ``use_gru`` and ``item_category``) and its
inputs, an item history and its categories, the target item and its
category, and the user's profile ids.

The harness finds this module by the configuration file's ``family`` key
(see ``chipbench.harness``); the plain reference is ``reference/dien.py``.
"""
from __future__ import annotations

import numpy as np

from chipbench import traffic

# The step's input arrays, each with its value in a launch's padded slots.
INPUTS = {"history_ids": -1, "history_cat_ids": -1, "target_id": -1,
          "target_cat_id": -1, "profile_ids": -1}
IDS = tuple(INPUTS)
# A history's length: lognormal of this median and sigma, clipped to
# [1, history].
TRAFFIC_KEYS = ("history_median", "history_sigma")
# The weights' scales (the file's ``weights``) over the program's own draw:
# the table, and each GRU's input and state weights.
SCALES = {"table_scale": 10.0, "gru_input_scale": 20.0, "gru_state_scale": 2.0}


def program_config(cfg: dict):
    """The program's configuration for this file, checked against its sizes
    and against the rows its reference pads the combined table to."""
    from repro.configs.paper_models import PAPER_MODELS
    from repro.models.din import behaviour_dim

    from reference import dien as ref

    prog = cfg["program"]
    pcfg = PAPER_MODELS[prog["model"]](prod=prog["prod"])
    emb = pcfg.embedding
    have = {
        "table_rows": list(emb.vocab_sizes),
        "embedding_dim": emb.dim,
        "hidden": behaviour_dim(pcfg),
        "history": pcfg.seq_len,
        "attn_mlp": list(pcfg.attn_mlp),
        "top_mlp": list(pcfg.top_mlp),
        "use_gru": pcfg.use_gru,
    }
    diff = {k: (v, cfg[k]) for k, v in have.items() if v != cfg[k]}
    diff.update({k: (v, cfg["weights"].get(k)) for k, v in SCALES.items()
                 if v != cfg["weights"].get(k)})
    if diff:
        raise SystemExit(f"program configuration differs from {prog['model']}'s file: {diff}")
    if emb.total_rows != ref.total_rows(cfg):
        raise SystemExit(
            f"{prog['model']} pads its table to {emb.total_rows} rows, the file's "
            f"weights.row_pad to {ref.total_rows(cfg)}: state the program's multiple")
    return pcfg


def program(pcfg):
    """The program's ``(init(key), apply(params, batch))``: ``RECSYS_INIT``
    and ``RECSYS_APPLY`` by the configuration's interaction, the weights
    scaled by ``SCALES`` after the program draws them."""
    from repro.launch.steps import RECSYS_APPLY, RECSYS_INIT

    init, apply = RECSYS_INIT[pcfg.interaction], RECSYS_APPLY[pcfg.interaction]

    def scaled(key):
        p = init(key, pcfg)
        p["embedding"]["table"] = p["embedding"]["table"] * np.float32(SCALES["table_scale"])
        for rec in ("gru", "augru"):
            for gate in p[rec].values():
                gate["wx"] = gate["wx"] * np.float32(SCALES["gru_input_scale"])
                gate["wh"] = gate["wh"] * np.float32(SCALES["gru_state_scale"])
        return p

    return scaled, (lambda params, batch: apply(params, batch, pcfg))


def make_pool(seed: int, n: int, cfg: dict, dist: traffic.Distributions) -> traffic.Pool:
    """``n`` items from ``seed``, drawn as ``data/clicklog.py`` draws them: a
    history of ``history`` item ids, -1 past its length; a target item;
    each item's category, its id modulo the category table's rows; one id
    of each profile table.  An item's lookups are two rows (item and
    category) for each valid history step and for its target, and one for
    each profile id."""
    rng = np.random.default_rng([seed, 1])
    T = cfg["history"]
    items, cats, *profiles = cfg["table_rows"]
    hist = traffic.zipf_ids(rng, items, (n, T), dist.zipf_alpha)
    ln = rng.lognormal(np.log(dist.extra["history_median"]), dist.extra["history_sigma"], n)
    lengths = np.clip(ln, 1, T).astype(np.int64)
    hist[np.arange(T)[None, :] >= lengths[:, None]] = -1
    target = traffic.zipf_ids(rng, items, n, dist.zipf_alpha)
    prof = np.stack([traffic.zipf_ids(rng, v, n, dist.zipf_alpha) for v in profiles], axis=1)
    hist_cats = np.where(hist >= 0, hist % cats, -1).astype(np.int32)
    arrays = {"history_ids": hist, "history_cat_ids": hist_cats,
              "target_id": target, "target_cat_id": target % cats, "profile_ids": prof}
    return traffic.Pool(arrays=arrays, counts=2 * (lengths + 1) + len(profiles))
