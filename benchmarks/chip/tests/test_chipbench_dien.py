"""The DIEN family through the harness on the CPU, at a tiny size: its
configuration file with tables of 1000 items, 50 categories, 500 users and
100 contexts, histories of 16 ids (median 8) and launches of 64 items; the
published widths.  A bulk run
is correct and each fault of the timed path makes it incorrect; the family
refuses a traffic parameter it does not read and a file that differs from
the program's model; its pool is the seed's."""
from __future__ import annotations

import copy
import dataclasses
import time

import numpy as np
import pytest

from chipbench_tiny import BENCH, D, END_TO_END, harness
from chipbench import traffic
from chipbench.families import dien as dien_family

CFG = harness.load_json(BENCH / "configs" / "dien.json")
MIX = harness.load_json(BENCH / "traffic" / "bulk_history.json")


def tiny_cfg() -> dict:
    cfg = copy.deepcopy(CFG)
    cfg.update(table_rows=[1000, 50, 500, 100], history=16)
    return cfg


def tiny_cell() -> harness.Cell:
    mix = copy.deepcopy(MIX)
    mix["distributions"]["history_median"] = 8
    per_layer = [{"name": "search_s", "unit": "s"}, {"name": "host_ms.dien", "unit": "ms"}]
    return harness.Cell("dien.tiny", 1, tiny_cfg(), mix, END_TO_END["bulk"], per_layer)


def tiny_program(monkeypatch):
    """DIEN's program configuration at the tiny sizes, and launches of D."""
    from repro.configs.paper_models import dien
    from repro.models.embedding import EmbeddingConfig

    def program_config(cfg):
        emb = EmbeddingConfig(vocab_sizes=tuple(cfg["table_rows"]), dim=cfg["embedding_dim"],
                              pooling=(1,) * len(cfg["table_rows"]),
                              row_pad=cfg["weights"]["row_pad"])
        return dataclasses.replace(dien(prod=False), seq_len=cfg["history"], embedding=emb)

    monkeypatch.setattr(harness, "compile_cache", lambda: None)
    monkeypatch.setattr(dien_family, "program_config", program_config)
    monkeypatch.setattr(harness, "schedule",
                        lambda cfg, pcfg: ({"plan": "tiny", "d": D, "m": 1, "o": 1}, 0.0))


def _run(monkeypatch, fault=None, cell=None, seed=2**31 + 29):
    tiny_program(monkeypatch)
    monkeypatch.setattr(harness, "SAMPLE_ITEMS", 256)
    return harness.run(cell or tiny_cell(), seed, 0.5, False, time.perf_counter(),
                       fault=fault, log=lambda m: None)


def test_dien_bulk_runs_correct(monkeypatch):
    res = _run(monkeypatch)
    assert res["correct"] is True, res["compared"]
    assert res["compared"]["score_gap"]["value"] < 1e-5
    assert res["items_compared"] >= 256
    assert set(res["metrics"]) == {"items_per_s", "setup_s"}


@pytest.mark.parametrize("fault", harness.FAULTS)
def test_dien_broken_timed_path_is_not_correct(monkeypatch, fault):
    res = _run(monkeypatch, fault)
    assert res["correct"] is False, (fault, res["compared"])


def test_dien_refuses_a_misspelled_history_key(monkeypatch):
    cell = tiny_cell()
    cell.mix["distributions"]["history_sigm"] = cell.mix["distributions"].pop("history_sigma")
    with pytest.raises(SystemExit, match="history_sigm"):
        _run(monkeypatch, cell=cell)


@pytest.mark.parametrize("key, value", [("embedding_dim", 16), ("history", 100),
                                        ("attn_mlp", [80, 40, 20]), ("top_mlp", [200]),
                                        ("table_rows", [1000000, 1000000, 100000]),
                                        ("hidden", 18), ("use_gru", False)])
def test_program_config_refuses_other_sizes(key, value):
    cfg = copy.deepcopy(CFG)
    cfg[key] = value
    with pytest.raises(SystemExit, match=key):
        dien_family.program_config(cfg)


@pytest.mark.parametrize("key", sorted(dien_family.SCALES))
def test_program_config_refuses_other_weight_scales(key):
    """The family scales the program's weights by ``SCALES``; a file that
    states other scales would have its reference draw other weights."""
    cfg = copy.deepcopy(CFG)
    cfg["weights"][key] = 1.0
    with pytest.raises(SystemExit, match=key):
        dien_family.program_config(cfg)


@pytest.mark.parametrize("row_pad, rows", [(512, 2_110_464), (1024, 2_110_464),
                                           (2048, 2_111_488), (1000, 2_110_000)])
def test_program_config_checks_the_padded_rows(row_pad, rows):
    """DIEN's program pads its [rows, 18] table (one row a line) to a
    multiple of 512 rows, 2,110,464; a file whose reference pads to another
    count would draw other weights, and is refused with both counts."""
    cfg = copy.deepcopy(CFG)
    cfg["weights"]["row_pad"] = row_pad
    if rows == 2_110_464:
        assert dien_family.program_config(cfg).embedding.total_rows == rows
    else:
        with pytest.raises(SystemExit, match=f"2110464 rows.* to {rows}"):
            dien_family.program_config(cfg)


def _pool(seed, n=300):
    dist = traffic.Distributions.from_mix(MIX, dien_family.TRAFFIC_KEYS)
    return dien_family.make_pool(seed, n, CFG, dist)


def test_pool_is_the_seeds():
    a, b, c = _pool(2**31 + 3), _pool(2**31 + 3), _pool(2**31 + 4)
    for k in dien_family.INPUTS:
        np.testing.assert_array_equal(a.arrays[k], b.arrays[k])
    np.testing.assert_array_equal(a.counts, b.counts)
    assert not np.array_equal(a.arrays["history_ids"], c.arrays["history_ids"])


def test_pool_draws_histories_and_counts_every_valid_lookup():
    pool = _pool(2**31 + 11, 4000)
    hist = pool.arrays["history_ids"]
    lengths = (hist >= 0).sum(1)
    # -1 only past each history's length, lengths in [1, 200] around 100
    np.testing.assert_array_equal(hist >= 0, np.arange(200)[None, :] < lengths[:, None])
    assert lengths.min() >= 1 and lengths.max() == 200
    assert 90 <= np.median(lengths) <= 110
    assert hist.max() < CFG["table_rows"][0]
    # each item's category is its id modulo the category table's rows
    cats = CFG["table_rows"][1]
    np.testing.assert_array_equal(pool.arrays["history_cat_ids"], np.where(hist >= 0, hist % cats, -1))
    np.testing.assert_array_equal(pool.arrays["target_cat_id"], pool.arrays["target_id"] % cats)
    prof = pool.arrays["profile_ids"]
    assert prof.shape == (4000, 2) and (prof.max(0) < CFG["table_rows"][2:]).all()
    # an item and a category row for each valid step and for the target
    np.testing.assert_array_equal(pool.counts, 2 * (lengths + 1) + 2)
    assert pool.lookups(3900, 200) == pool.counts[np.r_[3900:4000, 0:100]].sum()
