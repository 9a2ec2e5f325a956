"""EmbeddingBag substrate: unit + hypothesis property tests."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # dev-only dep (requirements-dev.txt): skip ONLY the
    # property tests, keep the plain assertions running
    def given(*a, **k):
        return lambda f: pytest.mark.skip(reason="hypothesis not installed")(f)

    def settings(*a, **k):
        return lambda f: f

    class _AnyStrategy:
        def __getattr__(self, name):
            return lambda *a, **k: None

    st = _AnyStrategy()

from repro.models.embedding import (
    EmbeddingConfig,
    HotColdLayout,
    LineTable,
    embedding_bag_hot_cold,
    embedding_bag_local,
    embedding_bag_ragged,
    gather_rows,
    init_embedding,
    make_hot_cold_layout,
    split_hot_cold,
)
from repro.train.optimizer import rowwise_adagrad


def _cfg(vocabs=(50, 100, 30), dim=8, pooling=(4, 2, 1), **kw):
    return EmbeddingConfig(vocab_sizes=vocabs, dim=dim, pooling=pooling,
                           row_pad=8, **kw)


def _ref_bag(table_np, ids, cfg):
    """Numpy oracle for the combined-table multi-hot bag."""
    B, F, P = ids.shape
    out = np.zeros((B, F, cfg.dim), np.float64)
    offs = cfg.row_offsets
    counts = np.zeros((B, F), np.int64)
    for b in range(B):
        for f in range(F):
            for p in range(P):
                i = ids[b, f, p]
                if i >= 0:
                    out[b, f] += table_np[offs[f] + i]
                    counts[b, f] += 1
    if cfg.combine == "mean":
        out = out / np.maximum(counts, 1)[..., None]
    return out


def test_matches_numpy_oracle(rng):
    cfg = _cfg()
    params = init_embedding(jax.random.PRNGKey(0), cfg)
    ids = rng.integers(-1, 30, (6, 3, 4)).astype(np.int32)
    got = embedding_bag_local(params, jnp.asarray(ids), cfg)
    want = _ref_bag(np.asarray(params["table"]), ids, cfg)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_mean_combine(rng):
    cfg = _cfg(combine="mean")
    params = init_embedding(jax.random.PRNGKey(0), cfg)
    ids = rng.integers(-1, 30, (4, 3, 4)).astype(np.int32)
    got = embedding_bag_local(params, jnp.asarray(ids), cfg)
    want = _ref_bag(np.asarray(params["table"]), ids, cfg)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


@settings(max_examples=25, deadline=None)
@given(
    batch=st.integers(1, 8),
    pooling=st.integers(1, 6),
    dim=st.sampled_from([4, 8, 16]),
    seed=st.integers(0, 2**31 - 1),
)
def test_property_padding_invariance(batch, pooling, dim, seed):
    """Appending -1 padding never changes the pooled result."""
    cfg = EmbeddingConfig(vocab_sizes=(40,), dim=dim, pooling=(pooling,),
                          row_pad=8)
    cfg_wide = EmbeddingConfig(vocab_sizes=(40,), dim=dim,
                               pooling=(pooling + 3,), row_pad=8)
    params = init_embedding(jax.random.PRNGKey(seed), cfg)
    r = np.random.default_rng(seed)
    ids = r.integers(0, 40, (batch, 1, pooling)).astype(np.int32)
    ids_padded = np.concatenate(
        [ids, np.full((batch, 1, 3), -1, np.int32)], axis=-1
    )
    a = embedding_bag_local(params, jnp.asarray(ids), cfg)
    b = embedding_bag_local(params, jnp.asarray(ids_padded), cfg_wide)
    np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


@settings(max_examples=25, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), hot_rows=st.integers(0, 40))
def test_property_hot_cold_partition_exact(seed, hot_rows):
    """hot + cold partial sums == unpartitioned bag for any split point."""
    cfg = _cfg(vocabs=(40, 40), pooling=(3, 2))
    params = init_embedding(jax.random.PRNGKey(seed), cfg)
    layout = HotColdLayout(cfg=cfg, hot_rows=(hot_rows, max(40 - hot_rows, 0)))
    split = split_hot_cold(params, layout)
    r = np.random.default_rng(seed)
    ids = r.integers(-1, 40, (5, 2, 3)).astype(np.int32)
    hot, cold = embedding_bag_hot_cold(split, jnp.asarray(ids), layout)
    want = embedding_bag_local(params, jnp.asarray(ids), cfg)
    np.testing.assert_allclose(np.asarray(hot) + np.asarray(cold), want,
                               rtol=1e-5, atol=1e-5)


def test_hot_layout_capacity_budget():
    cfg = _cfg()
    layout = make_hot_cold_layout(cfg, capacity_rows=60)
    assert sum(layout.hot_rows) <= 60
    assert all(h <= v for h, v in zip(layout.hot_rows, cfg.vocab_sizes))


def test_ragged_bag_matches_segments(rng):
    table = jnp.asarray(rng.normal(size=(30, 4)).astype(np.float32))
    ids = jnp.asarray([0, 1, 2, 5, 5, 7], jnp.int32)
    seg = jnp.asarray([0, 0, 1, 1, 2, 2], jnp.int32)
    out = embedding_bag_ragged(table, ids, seg, 3)
    want = np.stack([
        np.asarray(table)[[0, 1]].sum(0),
        np.asarray(table)[[2, 5]].sum(0),
        np.asarray(table)[[5, 7]].sum(0),
    ])
    np.testing.assert_allclose(out, want, rtol=1e-6)


def test_qr_compression_storage():
    cfg = EmbeddingConfig(vocab_sizes=(1_000_000, 100), dim=4,
                          pooling=(1, 1), qr_features=(0,), qr_buckets=1024,
                          row_pad=8)
    # storage ~ 1e6/1024 + 1024 + 100 rows, not 1e6
    assert cfg.total_rows < 4000
    params = init_embedding(jax.random.PRNGKey(0), cfg)
    ids = jnp.asarray([[[123456], [7]]], jnp.int32)
    out = embedding_bag_local(params, ids, cfg)
    assert out.shape == (1, 2, 4)
    assert bool(jnp.isfinite(out).all())


def test_grad_only_touches_looked_up_rows():
    cfg = _cfg(vocabs=(20,), pooling=(2,))
    params = init_embedding(jax.random.PRNGKey(0), cfg)
    ids = jnp.asarray([[[3, 5]]], jnp.int32)

    g = jax.grad(lambda p: embedding_bag_local(p, ids, cfg).sum())(params)
    gt = np.asarray(g["table"])
    touched = set(np.nonzero(np.abs(gt).sum(1))[0].tolist())
    assert touched == {3, 5}


# ---------------------------------------------------------------------------
# Packed storage: rows_per_line rows to a 128-lane line
# ---------------------------------------------------------------------------


def _lattice(shape, seed):
    """Values on a 2**-6 grid in [-1, 1]: every bag sum below is exact in
    float32 in any order, so packed and row storage must agree bit for bit
    (the packed pool adds the same values in another order)."""
    r = np.random.default_rng(seed)
    return (r.integers(-64, 65, shape) / 64).astype(np.float32)


def _edge_ids(cfg, batch, seed):
    """-1-padded ids with the first and last row of every feature."""
    r = np.random.default_rng(seed)
    P = cfg.max_pooling
    ids = np.stack([r.integers(-1, v, (batch, P)) for v in cfg.vocab_sizes], axis=1)
    for f, v in enumerate(cfg.vocab_sizes):
        ids[0, f, :2] = (0, v - 1)
        ids[1, f, :] = (v - 1, -1, 0, -1)[:P] if P >= 4 else v - 1
    return jnp.asarray(ids.astype(np.int32))


# dims that divide 128, and 12, 18 and 40, stored padded to 16, 32 and 64 lanes
PACKED_DIMS = [8, 16, 32, 64, 12, 18, 40]


def _width(dim):
    return next(w for w in (8, 16, 32, 64, 128) if w >= dim)


def _assert_pad_is_zero(table, rows):
    """``table``'s lines hold ``rows`` at their width, zeros past them."""
    stored = np.asarray(table.lines).reshape(-1, table.width)
    n, dim = rows.shape
    np.testing.assert_array_equal(stored[:n, :dim], rows)
    assert not stored[:, dim:].any() and not stored[n:].any()


@pytest.mark.parametrize("qr", [False, True], ids=["plain", "qr"])
@pytest.mark.parametrize("combine", ["sum", "mean"])
@pytest.mark.parametrize("dim", PACKED_DIMS)
def test_packed_storage_pools_as_rows(dim, combine, qr):
    cfg = EmbeddingConfig(vocab_sizes=(50, 300, 7), dim=dim, pooling=(4, 3, 2),
                          combine=combine, row_pad=8, qr_buckets=16,
                          qr_features=(1,) if qr else ())
    k = 128 // _width(dim)
    assert cfg.packed and cfg.rows_per_line == k and cfg.row_width == _width(dim)
    assert cfg.total_lines % 8 == 0 and cfg.total_lines * k >= cfg.total_rows
    t = _lattice((cfg.total_rows, dim), dim)
    packed = {"table": LineTable.pack(jnp.asarray(t), cfg.total_lines)}
    _assert_pad_is_zero(packed["table"], t)
    ids = _edge_ids(cfg, 6, dim)
    got = embedding_bag_local(packed, ids, cfg)
    want = embedding_bag_local({"table": jnp.asarray(t)}, ids, cfg)
    np.testing.assert_array_equal(got, want)
    if not qr and combine == "sum":  # the oracle divides in float64
        np.testing.assert_array_equal(got, _ref_bag(t, np.asarray(ids), cfg))


@pytest.mark.parametrize("dim", PACKED_DIMS)
def test_packed_init_holds_the_row_draw(dim):
    """init packs the same [rows, dim] values, pad lanes and lines zero,
    and gather_rows reads them back."""
    cfg = _cfg(vocabs=(50, 70), dim=dim, pooling=(2, 2))
    key = jax.random.PRNGKey(dim)
    table = init_embedding(key, cfg)["table"]
    assert isinstance(table, LineTable)
    assert table.lines.shape == (cfg.total_lines, 128)
    assert table.shape == (cfg.total_rows, dim)
    draw = np.asarray(jax.random.uniform(key, (cfg.total_rows, dim), minval=-1.0,
                                         maxval=1.0))
    scale = np.ones((cfg.total_rows, 1), np.float32)
    for f, v in enumerate(cfg.vocab_sizes):
        scale[cfg.row_offsets[f]:cfg.row_offsets[f + 1]] = 1.0 / np.sqrt(v)
    rows = np.asarray(table)
    np.testing.assert_array_equal(rows, draw * scale)
    _assert_pad_is_zero(table, rows)
    ids = jnp.asarray([[0, 1, 49], [50, 119, cfg.total_rows - 1]], jnp.int32)
    np.testing.assert_array_equal(gather_rows(table, ids), rows[np.asarray(ids)])


@pytest.mark.parametrize("vocabs, dim, rows, lines", [
    ((1_000_000, 10_000, 1_000_000, 100_000), 18, 2_110_464, 527_872),
    ((1_000_000, 10_000, 1_000_000, 100_000), 32, 2_110_464, 527_872),
    ((1_000_000,) * 10, 32, 10_000_384, 2_500_096),
], ids=["dien18", "dien32", "rmc1"])
def test_total_rows_round_the_rows_to_row_pad(vocabs, dim, rows, lines):
    """The rows are the features' rows rounded up to row_pad, as the
    benchmark's references draw them, whatever the row width; the lines
    that hold them are rounded up to row_pad with lines of zeros."""
    cfg = EmbeddingConfig(vocab_sizes=vocabs, dim=dim, pooling=(1,) * len(vocabs))
    assert cfg.total_rows == -(-sum(vocabs) // 512) * 512 == rows
    assert cfg.total_lines == lines and lines % 512 == 0


@pytest.mark.parametrize("dim", [18, 32])
def test_scaled_line_table_scales_its_rows(dim):
    """A LineTable times a scalar, inside jit or not, is the LineTable of
    the scaled rows, its pad lanes still zero."""
    cfg = _cfg(vocabs=(50, 70), dim=dim, pooling=(2, 2))
    table = init_embedding(jax.random.PRNGKey(0), cfg)["table"]
    for scaled in (table * np.float32(10.0), jax.jit(lambda t: t * np.float32(10.0))(table)):
        assert isinstance(scaled, LineTable) and scaled.shape == table.shape
        np.testing.assert_array_equal(np.asarray(scaled),
                                      np.asarray(table) * np.float32(10.0))
        _assert_pad_is_zero(scaled, np.asarray(scaled))


@pytest.mark.parametrize("dim,dtype", [(1, jnp.float32),
                                       (128, jnp.float32), (32, jnp.bfloat16)],
                         ids=["dim1", "dim128", "bf16"])
def test_unpackable_tables_keep_rows(dim, dtype):
    cfg = _cfg(dim=dim, dtype=dtype)
    assert not cfg.packed and cfg.rows_per_line == 1
    table = init_embedding(jax.random.PRNGKey(0), cfg)["table"]
    assert isinstance(table, jax.Array)
    assert table.shape == (cfg.total_rows, dim) and table.dtype == dtype
    ids = jnp.asarray([3, 0, 51], jnp.int32)
    np.testing.assert_array_equal(gather_rows(table, ids), np.asarray(table)[[3, 0, 51]])


@pytest.mark.parametrize("dim", [32, 18])
def test_rowwise_adagrad_on_packed_rows_matches_rows(dim):
    """One accumulator per row, over its dim lanes, whether the table is
    packed or not; the update keeps the pad lanes zero."""
    cfg = _cfg(vocabs=(20,), dim=dim, pooling=(3,))
    packed = init_embedding(jax.random.PRNGKey(0), cfg)
    rows = {"table": jnp.asarray(np.asarray(packed["table"]))}
    ids = jnp.asarray([[[3, 5, 5]], [[7, -1, 4]]], jnp.int32)
    opt = rowwise_adagrad(lr=0.1)

    def step(p):
        g = jax.grad(lambda p: (embedding_bag_local(p, ids, cfg) ** 2).sum())(p)
        return opt.update(p, g, opt.init(p))

    p_packed, s_packed = step(packed)
    p_rows, s_rows = step(rows)
    assert isinstance(p_packed["table"], LineTable)
    assert s_packed["acc"]["table"].shape == (cfg.total_rows, 1)
    np.testing.assert_allclose(np.asarray(p_packed["table"]), p_rows["table"],
                               rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(s_packed["acc"]["table"], s_rows["acc"]["table"],
                               rtol=1e-6, atol=1e-9)
    _assert_pad_is_zero(p_packed["table"], np.asarray(p_packed["table"]))
