"""A configuration of another model family joins the harness by modules of
its own: a toy sequence family, defined here alone and registered by name,
runs through ``harness.run`` correct, and each fault of the timed path
makes it incorrect.

The toy scores an item from a history of item ids (``history_ids [N, T]``,
-1 past its length), the target item (``target_id [N]``) and two profile
ids (``profile_ids [N, 2]``): attention of the target over the history,
then a linear layer over (interest, target, profile) vectors.  Its program
gathers rows; its reference multiplies one-hot rows, in its own precision.
"""
from __future__ import annotations

import sys
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench_tiny import D, END_TO_END, harness, tiny_program
from chipbench import traffic

FAMILY = "toyseq"
CFG = {
    "name": "toyseq-tiny",
    "family": FAMILY,
    "items": 500,
    "profile_rows": [40, 30],
    "dim": 8,
    "history": 16,
    "check": {"reference_precision": "default", "score_gap_limit": 1e-4},
    "layers": {"seq": ["test_chipbench_family.py"]},
    "scopes": ["attention"],
}
DIST = {"zipf_alpha": 1.05, "history_sigma": 0.5}


def _weights(key, cfg):
    k_item, k_prof, k_out = jax.random.split(key, 3)
    dim = cfg["dim"]
    return {
        "item": jax.random.normal(k_item, (cfg["items"], dim)),
        "profile": jax.random.normal(k_prof, (sum(cfg["profile_rows"]), dim)),
        "out": jax.random.normal(k_out, (3 * dim,)) / np.sqrt(3 * dim),
    }


# ---------------------------------------------------------------------------
# The family module: inputs, program and pool
# ---------------------------------------------------------------------------


def _program(cfg):
    offsets = jnp.asarray(np.cumsum([0] + cfg["profile_rows"][:-1]), jnp.int32)

    def init(key):
        return _weights(key, cfg)

    def apply(p, b):
        hist, target, prof = b["history_ids"], b["target_id"], b["profile_ids"]
        valid = hist >= 0
        e = p["item"][jnp.where(valid, hist, 0)] * valid[..., None]
        t = p["item"][jnp.maximum(target, 0)] * (target >= 0)[:, None]
        with jax.named_scope("attention"):
            logits = jnp.where(valid, jnp.einsum("btd,bd->bt", e, t), -1e9)
            interest = jnp.einsum("bt,btd->bd", jax.nn.softmax(logits, axis=1), e)
        rows = p["profile"][jnp.maximum(prof, 0) + offsets] * (prof >= 0)[..., None]
        return jnp.concatenate([interest, t, rows.sum(1)], axis=1) @ p["out"]

    return init, apply


def _make_pool(seed, n, cfg, dist):
    rng = np.random.default_rng([seed, 1])
    T = cfg["history"]
    ln = rng.lognormal(np.log(T * 0.5), dist.extra["history_sigma"], n)
    lengths = np.clip(ln.astype(np.int64), 1, T)
    hist = traffic.zipf_ids(rng, cfg["items"], (n, T), dist.zipf_alpha)
    hist[np.arange(T)[None, :] >= lengths[:, None]] = -1
    target = traffic.zipf_ids(rng, cfg["items"], n, dist.zipf_alpha)
    prof = np.stack([traffic.zipf_ids(rng, v, n, dist.zipf_alpha)
                     for v in cfg["profile_rows"]], axis=1)
    arrays = {"history_ids": hist, "target_id": target, "profile_ids": prof}
    return traffic.Pool(arrays=arrays, counts=lengths + 1 + len(cfg["profile_rows"]))


def family_module():
    fam = types.ModuleType(f"chipbench.families.{FAMILY}")
    fam.INPUTS = {"history_ids": -1, "target_id": -1, "profile_ids": -1}
    fam.IDS = tuple(fam.INPUTS)
    fam.TRAFFIC_KEYS = ("history_sigma",)
    fam.program_config = lambda cfg: cfg
    fam.program = _program
    fam.make_pool = _make_pool
    return fam


# ---------------------------------------------------------------------------
# The reference module: its own weights and forward
# ---------------------------------------------------------------------------


def _forward(p, b, cfg, precision):
    dtype = jnp.bfloat16 if precision == "bfloat16" else jnp.float32
    p = jax.tree.map(lambda a: a.astype(dtype), p)
    hist = b["history_ids"]
    e = jax.nn.one_hot(hist, cfg["items"], dtype=dtype) @ p["item"]       # -1: zeros
    t = jax.nn.one_hot(b["target_id"], cfg["items"], dtype=dtype) @ p["item"]
    logits = jnp.where(hist >= 0, (e * t[:, None, :]).sum(-1), -1e9)
    w = jax.nn.softmax(logits, axis=1)
    interest = (w[..., None] * e).sum(1)
    prof = []
    for f, rows in enumerate(cfg["profile_rows"]):
        start = sum(cfg["profile_rows"][:f])
        table = p["profile"][start:start + rows]
        prof.append(jax.nn.one_hot(b["profile_ids"][:, f], rows, dtype=dtype) @ table)
    x = jnp.concatenate([interest, t, sum(prof)], axis=1)
    return (x * p["out"]).sum(1)


def _work(cfg, items, valid_lookups, launches):
    """The forward's operations: an add per element of each row looked up,
    then per item the attention's two products over the history and the
    output layer."""
    dim, T = cfg["dim"], cfg["history"]
    return {"flops": valid_lookups * dim + items * (4 * T * dim + 6 * dim)}


def reference_module():
    ref = types.ModuleType(f"reference.{FAMILY}")
    ref.init = lambda seed, cfg: _weights(jax.random.PRNGKey(seed % 2**32), cfg)
    ref.forward_fn = lambda cfg, precision: jax.jit(
        lambda p, batch: _forward(p, batch, cfg, precision))
    ref.work = _work
    return ref


# ---------------------------------------------------------------------------
# Runs
# ---------------------------------------------------------------------------


def toy_cell(kind: str) -> harness.Cell:
    mix = {"kind": "bulk", "distributions": dict(DIST)}
    if kind == "open_loop":
        mix = {"kind": "open_loop", "rate_qps": 40.0,
               "distributions": dict(DIST, query_size_max=2 * D)}
    per_layer = [{"name": "search_s", "unit": "s"}]
    return harness.Cell(f"toyseq.{kind}", 1, dict(CFG), mix, END_TO_END[kind], per_layer)


def _run(monkeypatch, kind, fault=None, seed=2**31 + 23, trace=False, cell=None):
    tiny_program(monkeypatch)  # no compile cache, launches of D items
    monkeypatch.setitem(sys.modules, f"chipbench.families.{FAMILY}", family_module())
    monkeypatch.setitem(sys.modules, f"reference.{FAMILY}", reference_module())
    monkeypatch.setattr(harness, "SAMPLE_ITEMS", 256)
    return harness.run(cell or toy_cell(kind), seed, 0.5, trace, time.perf_counter(),
                       fault=fault, log=lambda m: None)


@pytest.mark.parametrize("kind", ["bulk", "open_loop"])
def test_toy_family_runs_correct(monkeypatch, kind):
    res = _run(monkeypatch, kind)
    assert res["correct"] is True, res["compared"]
    assert 0 <= res["compared"]["score_gap"]["value"] < 1e-5
    assert res["items_compared"] >= 64
    want = {"bulk": "items_per_s", "open_loop": "p99_ms"}[kind]
    assert set(res["metrics"]) == {want, "setup_s"}


@pytest.mark.parametrize("fault", harness.FAULTS)
@pytest.mark.parametrize("kind", ["bulk", "open_loop"])
def test_toy_family_broken_timed_path_is_not_correct(monkeypatch, kind, fault):
    res = _run(monkeypatch, kind, fault)
    assert res["correct"] is False, (fault, res["compared"])


def test_toy_family_runs_traced(monkeypatch):
    """The traced path asks of a family only what its cell's readers read:
    here ``work``'s ``flops`` for ``step_mfu``.  The CPU's trace has no
    device plane, so the device readers find nothing and stay out."""
    peaks = {"flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
    monkeypatch.setattr(harness, "peaks_for", lambda kind: peaks)
    cell = toy_cell("bulk")
    cell.per_layer = [{"name": m, "unit": u} for m, u in
                      [("search_s", "s"), ("step_mfu.bulk", "%"), ("device_ms.bulk", "ms"),
                       ("gather_ms.bulk", "ms")]]
    res = _run(monkeypatch, "bulk", trace=True, cell=cell)
    assert res["correct"] is True, res["compared"]
    assert set(res["metrics"]) == {"search_s", "step_mfu.bulk"}
    assert 0 < res["metrics"]["step_mfu.bulk"]["value"] < 100
    assert res["device"]["window_s"] > 0 and "breakdown" in res


def test_toy_family_refuses_a_traffic_parameter_it_does_not_read(monkeypatch):
    cell = toy_cell("bulk")
    cell.mix["distributions"]["history_sigm"] = 0.5
    with pytest.raises(SystemExit, match="history_sigm"):
        _run(monkeypatch, "bulk", cell=cell)


def test_toy_pool_counts_every_valid_lookup():
    dist = traffic.Distributions.from_mix({"distributions": DIST}, ("history_sigma",))
    pool = _make_pool(5, 300, CFG, dist)
    valid = sum((a >= 0).reshape(300, -1).sum(1) for a in pool.arrays.values())
    np.testing.assert_array_equal(valid, pool.counts)
    assert pool.lookups(250, 100) == valid[np.r_[250:300, 0:50]].sum()
