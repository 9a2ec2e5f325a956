"""The benchmark's generator draws from the distributions of the program's
click-log generator, and repeats exactly for a seed."""
from __future__ import annotations

import numpy as np
import pytest

from chipbench_tiny import BENCH, dlrm_family, harness
from chipbench import traffic

N = 4096


@pytest.fixture(scope="module")
def rmc1():
    return harness.load_json(BENCH / "configs" / "dlrm-rmc1.json")


@pytest.fixture(scope="module")
def program_gen():
    from repro.configs.paper_models import rmc1
    from repro.data.clicklog import ClickLogGenerator

    return ClickLogGenerator(rmc1(prod=False), seed=11)


def test_pool_repeats_for_a_seed(rmc1):
    dist = traffic.Distributions()
    a = dlrm_family.make_pool(2**31 + 3, 256, rmc1, dist)
    b = dlrm_family.make_pool(2**31 + 3, 256, rmc1, dist)
    c = dlrm_family.make_pool(2**31 + 4, 256, rmc1, dist)
    for k in ("sparse_ids", "dense"):
        np.testing.assert_array_equal(a.arrays[k], b.arrays[k])
    np.testing.assert_array_equal(a.counts, b.counts)
    assert not np.array_equal(a.arrays["sparse_ids"], c.arrays["sparse_ids"])
    assert ((a.arrays["sparse_ids"] >= 0).sum(axis=2) == a.counts).all()


def test_ids_and_pooling_match_the_program_generator(rmc1, program_gen):
    pool = dlrm_family.make_pool(5, N, rmc1, traffic.Distributions())
    theirs = program_gen.sparse_ids(N)
    ids = pool.arrays["sparse_ids"]
    ours_ids, their_ids = ids[ids >= 0], theirs[theirs >= 0]
    for q in (0.1, 0.25, 0.5, 0.75, 0.9, 0.99):
        a, b = np.quantile(ours_ids, q), np.quantile(their_ids, q)
        assert abs(np.log1p(a) - np.log1p(b)) < 0.05 * np.log1p(1e6), q
    assert (ours_ids < 1_000_000).all() and (ours_ids >= 0).all()
    ours_c = np.bincount(pool.counts.ravel(), minlength=81) / pool.counts.size
    their_c = np.bincount((theirs >= 0).sum(axis=2).ravel(), minlength=81) / pool.counts.size
    assert np.abs(np.cumsum(ours_c) - np.cumsum(their_c)).max() < 0.02
    assert abs(pool.counts.mean() - (theirs >= 0).sum(axis=2).mean()) < 0.5


def test_query_sizes_match_and_every_seed_gets_the_same_work(program_gen):
    dist = traffic.Distributions()
    due_a, sizes_a = traffic.open_loop(1, N, 40.0, dist)
    due_b, sizes_b = traffic.open_loop(2**31 + 9, N, 40.0, dist)
    theirs = program_gen.query_sizes(N)
    for q in (0.1, 0.25, 0.5, 0.75, 0.9):
        assert np.quantile(sizes_a, q) == pytest.approx(np.quantile(theirs, q), rel=0.08)
    assert sizes_a.min() >= 1 and sizes_a.max() <= 1024
    np.testing.assert_array_equal(np.sort(sizes_a), np.sort(sizes_b))
    assert not np.array_equal(sizes_a, sizes_b)
    gaps_a, gaps_b = (np.diff(due, prepend=0.0) for due in (due_a, due_b))
    np.testing.assert_allclose(np.sort(gaps_a), np.sort(gaps_b), atol=1e-9)
    assert due_a[-1] == pytest.approx(due_b[-1])
    assert np.all(gaps_a > 0) and gaps_a.mean() == pytest.approx(1 / 40.0, rel=0.02)
    again = traffic.open_loop(1, N, 40.0, dist)
    np.testing.assert_array_equal(again[1], sizes_a)


def test_lookups_count_round_the_pool(rmc1):
    pool = dlrm_family.make_pool(3, 100, rmc1, traffic.Distributions())
    per_item = pool.counts.sum(axis=1)
    idx = (90 + np.arange(250)) % 100
    assert pool.lookups(90, 250) == per_item[idx].sum()
    assert pool.lookups(10, 5) == per_item[10:15].sum()


def _pool_formula(seed, n, cfg, dist):
    """The DLRM pool as the harness drew it when the formula sat in
    ``traffic.make_pool``: one stream, counts, then ids, then dense."""
    rng = np.random.default_rng([seed, 1])
    F, P, V = cfg["num_tables"], cfg["pooling"], cfg["rows_per_table"]
    ln = rng.lognormal(np.log(max(P, 2) * 0.6), dist.pooling_sigma, (n, F))
    counts = np.clip(ln.astype(np.int64), 1, P).astype(np.int32)
    u = rng.random((n, F, P)) ** dist.zipf_alpha
    ids = np.clip(np.floor(np.power(float(V), u)) - 1.0, 0, V - 1).astype(np.int32)
    ids[np.arange(P)[None, None, :] >= counts[..., None]] = -1
    dense = rng.standard_normal((n, cfg["num_dense"]), np.float32)
    return {"dense": dense, "sparse_ids": ids}, counts


@pytest.mark.parametrize("seed", [11, 2**31 + 101])
def test_dlrm_family_pool_is_bit_identical_to_the_formula(rmc1, seed):
    dist = traffic.Distributions.from_mix(harness.load_json(BENCH / "traffic" / "bulk.json"))
    pool = dlrm_family.make_pool(seed, 512, rmc1, dist)
    arrays, counts = _pool_formula(seed, 512, rmc1, dist)
    assert list(pool.arrays) == list(dlrm_family.INPUTS) == list(arrays)
    for k, a in arrays.items():
        assert pool.arrays[k].dtype == a.dtype
        np.testing.assert_array_equal(pool.arrays[k], a)
    np.testing.assert_array_equal(pool.counts, counts)


def test_distributions_keep_a_familys_own_parameters():
    mix = {"distributions": {"zipf_alpha": 1.2, "history_sigma": 0.5}}
    dist = traffic.Distributions.from_mix(mix, ("history_sigma",))
    assert dist.zipf_alpha == 1.2 and dist.extra == {"history_sigma": 0.5}
    assert traffic.Distributions.from_mix({}) == traffic.Distributions()


@pytest.mark.parametrize("key, extra_keys", [("zipf_alph", dlrm_family.TRAFFIC_KEYS),
                                             ("history_sigma", dlrm_family.TRAFFIC_KEYS),
                                             ("history_sigm", ("history_sigma",))])
def test_distributions_refuse_a_parameter_no_pool_reads(key, extra_keys):
    mix = harness.load_json(BENCH / "traffic" / "bulk.json")
    mix["distributions"][key] = 0.5
    with pytest.raises(SystemExit, match=key):
        traffic.Distributions.from_mix(mix, extra_keys)
