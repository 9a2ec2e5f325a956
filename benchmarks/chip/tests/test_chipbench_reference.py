"""The plain reference against the program's forward, on the CPU at a
small DLRM of RMC1's and RMC3's widths, and the bfloat16 control against
the configuration's limit."""
from __future__ import annotations

import copy

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench_tiny import BENCH, dlrm_family, harness, tiny_program
from chipbench import traffic
from reference import dlrm as ref

B = 256


def small(name):
    cfg = harness.load_json(BENCH / "configs" / f"{name}.json")
    cfg = copy.deepcopy(cfg)
    cfg.update(rows_per_table=1000, pooling=8)
    return cfg


def program(cfg, monkeypatch, seed, pool):
    """The program's weights and compiled step, built as the harness builds
    them."""
    tiny_program(monkeypatch)
    return harness.build(dlrm_family, dlrm_family.program_config(cfg), seed, B, pool)


def gap(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want) / (1 + np.abs(want))))


@pytest.mark.parametrize("name", ["dlrm-rmc1", "dlrm-rmc3"])
def test_reference_weights_are_the_programs(name, monkeypatch):
    cfg = small(name)
    seed = 2**31 + 17
    pool = dlrm_family.make_pool(seed, B, cfg, traffic.Distributions())
    params, _ = program(cfg, monkeypatch, seed, pool)
    mine = ref.init(seed, cfg)
    np.testing.assert_array_equal(mine["table"], params["embedding"]["table"])
    for a, b in zip(mine["bottom"] + mine["top"], params["bottom_mlp"] + params["top_mlp"]):
        np.testing.assert_array_equal(a["w"], b["w"])
        np.testing.assert_array_equal(a["b"], b["b"])


@pytest.mark.parametrize("name", ["dlrm-rmc1", "dlrm-rmc3"])
def test_reference_agrees_and_bfloat16_fails(name, monkeypatch):
    cfg = small(name)
    seed = 12345
    pool = dlrm_family.make_pool(seed, B, cfg, traffic.Distributions())
    params, step = program(cfg, monkeypatch, seed, pool)
    batch = {k: jnp.asarray(a) for k, a in pool.arrays.items()}
    got = step(params, batch)
    rp = ref.init(seed, cfg)
    want = ref.forward_fn(cfg, "default")(rp, batch)
    limit = cfg["check"]["score_gap_limit"]
    assert gap(got, want) < limit / 100
    low = ref.forward_fn(cfg, "bfloat16")(rp, batch)
    assert gap(low, want) > 2 * limit
    # dropping the sparse half (every bag empty) is caught too
    empty = dict(batch, sparse_ids=jnp.full_like(batch["sparse_ids"], -1))
    assert gap(ref.forward_fn(cfg, "default")(rp, empty), want) > 2 * limit
