"""The benchmark's generator draws from the distributions of the program's
click-log generator, and repeats exactly for a seed."""
from __future__ import annotations

import numpy as np
import pytest

from chipbench_tiny import BENCH, harness
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
    a = traffic.make_pool(2**31 + 3, 256, rmc1, dist)
    b = traffic.make_pool(2**31 + 3, 256, rmc1, dist)
    c = traffic.make_pool(2**31 + 4, 256, rmc1, dist)
    for x, y in ((a.ids, b.ids), (a.dense, b.dense), (a.counts, b.counts)):
        np.testing.assert_array_equal(x, y)
    assert not np.array_equal(a.ids, c.ids)
    assert ((a.ids >= 0).sum(axis=2) == a.counts).all()


def test_ids_and_pooling_match_the_program_generator(rmc1, program_gen):
    pool = traffic.make_pool(5, N, rmc1, traffic.Distributions())
    theirs = program_gen.sparse_ids(N)
    ours_ids, their_ids = pool.ids[pool.ids >= 0], theirs[theirs >= 0]
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
    pool = traffic.make_pool(3, 100, rmc1, traffic.Distributions())
    per_item = pool.counts.sum(axis=1)
    idx = (90 + np.arange(250)) % 100
    assert pool.lookups(90, 250) == per_item[idx].sum()
    assert pool.lookups(10, 5) == per_item[10:15].sum()
