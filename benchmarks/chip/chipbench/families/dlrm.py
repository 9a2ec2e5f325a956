"""The DLRM family (``"family": "dlrm"``): the program's DLRM models and
their inputs, a dense-feature vector and one bag of ids a table an item.

The harness finds this module by the configuration file's ``family`` key
(see ``chipbench.harness``); the plain reference is ``reference/dlrm.py``.
"""
from __future__ import annotations

import numpy as np

from chipbench import traffic

# The step's input arrays, each with its value in a launch's padded slots.
INPUTS = {"dense": 0.0, "sparse_ids": -1}
IDS = ("sparse_ids",)
# Traffic parameters of its own, beyond ``traffic.Distributions``: none.
TRAFFIC_KEYS = ()


def program_config(cfg: dict):
    """The program's configuration for this file, checked against its sizes
    and against the rows its reference pads the combined table to (the
    weights are drawn over the padded table, so the two must agree)."""
    from repro.configs.paper_models import PAPER_MODELS

    from reference import dlrm as ref

    prog = cfg["program"]
    pcfg = PAPER_MODELS[prog["model"]](prod=prog["prod"])
    emb = pcfg.embedding
    have = {
        "num_tables": emb.num_features,
        "rows_per_table": emb.vocab_sizes[0] if len(set(emb.vocab_sizes)) == 1 else None,
        "embedding_dim": emb.dim,
        "pooling": emb.max_pooling if len(set(emb.pooling)) == 1 else None,
        "num_dense": pcfg.n_dense,
        "bottom_mlp": list(pcfg.bottom_mlp),
        "top_mlp": list(pcfg.top_mlp),
    }
    diff = {k: (v, cfg[k]) for k, v in have.items() if v != cfg[k]}
    if diff:
        raise SystemExit(f"program configuration differs from {prog['model']}'s file: {diff}")
    if emb.total_rows != ref.total_rows(cfg):
        raise SystemExit(
            f"{prog['model']} pads its table to {emb.total_rows} rows, the file's "
            f"weights.row_pad to {ref.total_rows(cfg)}: state the program's multiple")
    return pcfg


def program(pcfg):
    """The program's ``(init(key), apply(params, batch))`` for this model."""
    from repro.launch.steps import RECSYS_APPLY, RECSYS_INIT

    init, apply = RECSYS_INIT[pcfg.interaction], RECSYS_APPLY[pcfg.interaction]
    return (lambda key: init(key, pcfg)), (lambda params, batch: apply(params, batch, pcfg))


def make_pool(seed: int, n: int, cfg: dict, dist: traffic.Distributions) -> traffic.Pool:
    """``n`` items from ``seed``: per table a bag of ``pooling`` ids, -1 past
    its count, and ``num_dense`` features."""
    rng = np.random.default_rng([seed, 1])
    F, P, V = cfg["num_tables"], cfg["pooling"], cfg["rows_per_table"]
    counts = traffic.pooling_counts(rng, P, (n, F), dist.pooling_sigma)
    ids = traffic.zipf_ids(rng, V, (n, F, P), dist.zipf_alpha)
    ids[np.arange(P)[None, None, :] >= counts[..., None]] = -1
    dense = rng.standard_normal((n, cfg["num_dense"]), np.float32)
    return traffic.Pool(arrays={"dense": dense, "sparse_ids": ids}, counts=counts)
