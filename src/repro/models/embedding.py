"""Embedding substrate: EmbeddingBag / SparseLengthsSum in pure JAX.

JAX has no native ``nn.EmbeddingBag`` and no CSR sparse — the multi-hot
gather+pool that dominates recommendation inference (the paper's SparseNet)
is built here from row gathers + masked reduction / ``jax.ops.segment_sum``.
This module is single-device semantics; the distributed (model-axis sharded)
lookup lives in ``repro.dist.sharded_embedding`` and the fused TPU kernel in
``repro.kernels.embedding_bag``.

Layout: all feature tables are concatenated row-wise into ONE combined
``[total_rows, dim]`` table (FBGEMM table-batched-embedding style); feature
``f``'s ids are shifted by ``row_offsets[f]``. This gives a single gather for
the whole SparseNet and a single row-sharded array for the model axis.

Storage: 32-bit rows of 8 to 127 lanes are stored in 128-lane lines (a
``LineTable``): each row padded with zero lanes to ``row_width``, the power
of two at or above ``dim`` (18 -> 32), and ``rows_per_line = 128 //
row_width`` rows side by side in a line. The TPU lays a ``[R, 32]`` or
``[R, 18]`` f32 array out rows-minor, so a row's floats are not contiguous
and its gather crawls; a ``[R // 4, 128]`` array keeps rows major, and one
index fetches 512 contiguous bytes. Every reader fetches rows through
``gather_rows``.

Hot/cold split (paper §IV-B, locality-aware partition): ids are assumed
frequency-ranked per table (the synthetic data generator produces them that
way), so "row < hot_rows[f]" identifies the hot set. ``split_hot_cold``
re-lays the combined table into a small hot replica + a cold remainder, and
``embedding_bag_hot_cold`` computes hot and cold partial sums separately —
the Psum dataflow of the paper's Figure 10(d).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.common.init import embedding_init

LANES = 128  # lanes of a TPU vector register: the width of a stored line


@dataclasses.dataclass(frozen=True)
class EmbeddingConfig:
    """Combined multi-table embedding-bag configuration.

    vocab_sizes: rows per sparse feature table.
    dim: shared embedding dimension.
    pooling: max multi-hot pooling factor per feature (ids padded with -1).
    combine: "sum" (SparseLengthsSum) or "mean".
    qr_features: features using the quotient-remainder trick (huge vocabs);
        their storage is ``ceil(V/qr_buckets) + qr_buckets`` rows instead of V.
    """

    vocab_sizes: tuple[int, ...]
    dim: int
    pooling: tuple[int, ...]
    combine: str = "sum"
    qr_features: tuple[int, ...] = ()
    qr_buckets: int = 65536
    dtype: Any = jnp.float32
    # the table's rows, and a packed table's lines, are padded to a
    # multiple of this so the row-wise model-axis shard is always even
    # (512 covers every production mesh).
    row_pad: int = 512

    def __post_init__(self):
        if len(self.vocab_sizes) != len(self.pooling):
            raise ValueError("vocab_sizes and pooling must have equal length")
        if self.combine not in ("sum", "mean"):
            raise ValueError(f"unknown combine mode {self.combine!r}")

    @property
    def num_features(self) -> int:
        return len(self.vocab_sizes)

    def storage_rows(self, f: int) -> int:
        """Physical rows stored for feature f (QR-compressed if enabled)."""
        v = self.vocab_sizes[f]
        if f in self.qr_features:
            q = -(-v // self.qr_buckets)  # ceil
            return q + self.qr_buckets
        return v

    @property
    def row_offsets(self) -> np.ndarray:
        """Start row of each feature in the combined table; len = F+1."""
        sizes = [self.storage_rows(f) for f in range(self.num_features)]
        return np.concatenate([[0], np.cumsum(sizes)]).astype(np.int64)

    @property
    def packed(self) -> bool:
        """Whether the table is stored in 128-lane lines (a ``LineTable``):
        32-bit rows of 8 to 127 lanes. Other tables stay ``[rows, dim]``:
        wider rows fill a line already, 16-bit rows are laid out otherwise,
        and a line of narrower ones (a wide part's dim 1) would read over 16
        rows for the one looked up."""
        return jnp.dtype(self.dtype).itemsize == 4 and 8 <= self.dim < LANES

    @property
    def row_width(self) -> int:
        """Lanes a stored row takes: ``dim`` rounded up to a power of two
        where ``packed`` (32 for dim 18, the lanes past ``dim`` zeros),
        ``dim`` otherwise."""
        return _row_width(self.dim) if self.packed else self.dim

    @property
    def rows_per_line(self) -> int:
        """Rows stored side by side in one 128-lane line: ``128 //
        row_width`` where ``packed`` (4 for dims 17 to 32, 2 for 33 to 64),
        1 otherwise."""
        return LANES // self.row_width if self.packed else 1

    @property
    def total_rows(self) -> int:
        """The table's rows: the features' rows rounded up to ``row_pad``."""
        raw = int(self.row_offsets[-1])
        return -(-raw // self.row_pad) * self.row_pad

    @property
    def total_lines(self) -> int:
        """A packed table's lines: enough for ``total_rows``, rounded up to
        ``row_pad`` with lines of zeros."""
        lines = -(-self.total_rows // self.rows_per_line)
        return -(-lines // self.row_pad) * self.row_pad

    @property
    def max_pooling(self) -> int:
        return max(self.pooling)

    def bytes(self, dtype_bytes: int = 4) -> int:
        return self.total_rows * self.dim * dtype_bytes


def _row_width(dim: int) -> int:
    """The power of two at or above ``dim``."""
    return 1 << (dim - 1).bit_length()


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class LineTable:
    """A ``[num_rows, dim]`` table stored ``k`` rows to a 128-lane line.

    Each row takes ``width`` lanes, ``dim`` rounded up to a power of two,
    the lanes past ``dim`` exact zeros, and ``k = 128 // width``. ``lines``
    is ``[L, 128]``: line ``i`` holds rows ``k*i`` to ``k*i + k - 1`` side
    by side, and the lanes past the last row hold zeros (``L`` is the
    config's ``total_lines``, at least ``num_rows / k``). The values are
    the rows' own; ``np.asarray`` gives them as ``[num_rows, dim]``,
    without the pad.
    """

    lines: jax.Array
    dim: int = dataclasses.field(metadata=dict(static=True))
    num_rows: int = dataclasses.field(metadata=dict(static=True))

    @classmethod
    def pack(cls, rows: jax.Array, num_lines: int) -> LineTable:
        """``rows`` ``[n, dim]`` stored in ``num_lines`` lines, each row
        padded to ``width`` lanes, with zeros past them."""
        n, dim = rows.shape
        width = _row_width(dim)
        padded = jnp.pad(rows, ((0, num_lines * (LANES // width) - n),
                                (0, width - dim)))
        return cls(padded.reshape(num_lines, LANES), dim, n)

    @property
    def width(self) -> int:
        return _row_width(self.dim)

    @property
    def rows_per_line(self) -> int:
        return LANES // self.width

    @property
    def shape(self) -> tuple[int, int]:
        return (self.num_rows, self.dim)

    @property
    def dtype(self):
        return self.lines.dtype

    def rows(self) -> jax.Array:
        """The ``[rows, dim]`` table (a relayout: keep it out of steps)."""
        return self.lines.reshape(-1, self.width)[: self.num_rows, : self.dim]

    def __array__(self, dtype=None, copy=None):
        lines = np.asarray(self.lines, dtype)
        return lines.reshape(-1, self.width)[: self.num_rows, : self.dim]

    def __mul__(self, scale):
        """The table times a scalar, as the rows it stands for would be."""
        if jnp.ndim(scale) != 0:
            return NotImplemented
        return dataclasses.replace(self, lines=self.lines * scale)

    __rmul__ = __mul__


def _gather_lines(table: LineTable, row_ids: jax.Array) -> jax.Array:
    """The lines holding rows ``row_ids`` -> ``row_ids.shape + (128,)``.
    The ids must lie in the table: nothing is filled or clamped."""
    return table.lines.at[row_ids // table.rows_per_line].get(
        mode="promise_in_bounds")


def gather_rows(table, row_ids: jax.Array) -> jax.Array:
    """Rows ``row_ids`` (any int shape) of a ``[rows, dim]`` array or a
    ``LineTable`` -> ``row_ids.shape + (dim,)``.

    A ``LineTable``'s row is picked from its line by a lane mask (a sum of
    the row with exact zeros), so the values are the stored ones."""
    if not isinstance(table, LineTable):
        return jnp.take(table, row_ids, axis=0)
    k = table.rows_per_line
    blocks = _gather_lines(table, row_ids).reshape(*row_ids.shape, k, table.width)
    pick = (row_ids % k)[..., None, None] == jnp.arange(k)[:, None]
    return jnp.where(pick, blocks[..., : table.dim], 0).sum(axis=-2)


def init_embedding(key, cfg: EmbeddingConfig):
    """One combined [total_rows, dim] table, DLRM uniform init per table,
    stored as a ``LineTable`` of ``cfg.total_lines`` where ``cfg.packed``."""
    # Init the whole combined table in one draw with a per-table scale:
    # equivalent in distribution to per-table U(-1/sqrt(V), 1/sqrt(V)).
    table = jax.random.uniform(
        key, (cfg.total_rows, cfg.dim), minval=-1.0, maxval=1.0, dtype=jnp.float32
    )
    offsets = cfg.row_offsets
    scales = np.ones((cfg.total_rows, 1), np.float32)
    for f in range(cfg.num_features):
        v = cfg.vocab_sizes[f]
        scales[offsets[f] : offsets[f + 1]] = 1.0 / np.sqrt(v)
    table = (table * jnp.asarray(scales)).astype(cfg.dtype)
    if cfg.packed:
        return {"table": LineTable.pack(table, cfg.total_lines)}
    return {"table": table}


def _feature_row_index(cfg: EmbeddingConfig, ids: jax.Array) -> jax.Array:
    """Map per-feature logical ids [B, F, P] to combined physical row ids.

    Padding ids (< 0) map to row 0 (they are masked out of the pool anyway).
    For QR features each logical id expands *virtually*: we fold quotient and
    remainder into two gathers handled by ``embedding_bag`` directly, so here
    plain features only; QR handled in the caller.
    """
    offsets = jnp.asarray(cfg.row_offsets[:-1], jnp.int32)  # [F]
    safe = jnp.maximum(ids, 0)
    return safe + offsets[None, :, None]


def embedding_bag(params, ids: jax.Array, cfg: EmbeddingConfig) -> jax.Array:
    """Multi-hot gather + pool. ids: [B, F, Pmax] int32, -1-padded.

    Returns pooled embeddings [B, F, dim]. Under a mesh context the lookup
    routes through the model-axis-sharded Psum dataflow
    (repro.dist.sharded_embedding); single-device semantics otherwise.
    """
    from repro.dist import logical

    if logical.model_axis_name() is not None:
        from repro.dist.sharded_embedding import embedding_bag_sharded

        return embedding_bag_sharded(params, ids, cfg)
    return embedding_bag_local(params, ids, cfg)


def embedding_bag_local(params, ids: jax.Array, cfg: EmbeddingConfig) -> jax.Array:
    """Single-shard EmbeddingBag (row gather + masked pool), under the
    ``gather`` and ``pool`` scopes."""
    table = params["table"]
    B, F, P = ids.shape
    if F != cfg.num_features:
        raise ValueError(f"expected {cfg.num_features} features, got {F}")
    if isinstance(table, LineTable) and not cfg.qr_features:
        return _embedding_bag_lines(table, ids, cfg)

    with jax.named_scope("gather"):
        if not cfg.qr_features:
            rows = gather_rows(
                table, _feature_row_index(cfg, ids).reshape(-1)
            ).reshape(B, F, P, cfg.dim)
        else:
            rows = _gather_with_qr(table, ids, cfg)

    with jax.named_scope("pool"):
        mask = (ids >= 0).astype(table.dtype)[..., None]  # [B, F, P, 1]
        pooled = (rows * mask).sum(axis=2)  # [B, F, dim]
        if cfg.combine == "mean":
            counts = jnp.maximum(mask.sum(axis=2), 1.0)
            pooled = pooled / counts
        return pooled


def _embedding_bag_lines(table: LineTable, ids: jax.Array,
                         cfg: EmbeddingConfig) -> jax.Array:
    """EmbeddingBag over a ``LineTable``: gather whole lines, then pool.

    The pool keeps lane ``l`` of a looked-up line where the row sits at
    lane block ``l // width`` (and the id is not padding), sums the bag's
    lines, and folds the ``k`` lane blocks: only exact zeros are added to
    the rows' values, all on the vector unit (no matrix product).
    """
    B, F, P = ids.shape
    k, width, dim = table.rows_per_line, table.width, table.dim
    with jax.named_scope("gather"):
        row = _feature_row_index(cfg, ids)
        lines = _gather_lines(table, row)  # [B, F, P, 128]

    with jax.named_scope("pool"):
        valid = ids >= 0
        keep = ((row % k)[..., None] == jnp.arange(LANES) // width) & valid[..., None]
        pooled = jnp.where(keep, lines, 0).sum(axis=2)  # [B, F, 128]
        pooled = pooled.reshape(B, F, k, width).sum(axis=2)[..., :dim]  # [B, F, dim]
        if cfg.combine == "mean":
            counts = jnp.maximum(valid.astype(table.dtype).sum(axis=2), 1.0)
            pooled = pooled / counts[..., None]
        return pooled


def _gather_with_qr(table, ids, cfg: EmbeddingConfig):
    """Gather rows where some features use quotient-remainder compression.

    QR feature f of vocab V stores ``q = ceil(V/Q)`` quotient rows followed by
    ``Q`` remainder rows; emb(id) = quot[id // Q] * rem[id % Q] (Hadamard,
    per the QR-embedding paper's best-performing combiner).
    """
    B, F, P = ids.shape
    offsets = cfg.row_offsets
    safe = jnp.maximum(ids, 0)
    per_feature = []
    for f in range(cfg.num_features):
        fid = safe[:, f, :]  # [B, P]
        base = int(offsets[f])
        if f in cfg.qr_features:
            q_rows = -(-cfg.vocab_sizes[f] // cfg.qr_buckets)
            quot = gather_rows(table, base + fid // cfg.qr_buckets)
            rem = gather_rows(table, base + q_rows + fid % cfg.qr_buckets)
            per_feature.append(quot * rem)
        else:
            per_feature.append(gather_rows(table, base + fid))
    return jnp.stack(per_feature, axis=1)  # [B, F, P, dim]


def embedding_bag_ragged(
    table: jax.Array,
    ids: jax.Array,
    segment_ids: jax.Array,
    num_segments: int,
    combine: str = "sum",
) -> jax.Array:
    """Ragged EmbeddingBag: flat ids + segment ids -> [num_segments, dim].

    This is the ``jnp.take`` + ``jax.ops.segment_sum`` form used where bags
    are genuinely variable-length (GNN aggregation, ragged serving path).
    """
    rows = jnp.take(table, ids, axis=0)
    out = jax.ops.segment_sum(rows, segment_ids, num_segments=num_segments)
    if combine == "mean":
        ones = jnp.ones((ids.shape[0], 1), dtype=rows.dtype)
        counts = jax.ops.segment_sum(ones, segment_ids, num_segments=num_segments)
        out = out / jnp.maximum(counts, 1.0)
    return out


# ---------------------------------------------------------------------------
# Hot/cold locality-aware partition (paper §IV-B, Figure 10)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class HotColdLayout:
    """Physical layout after locality-aware partition.

    hot_rows[f]: number of hottest rows of feature f replicated in the hot
    table (``G_s.hot``); the remainder stays in the sharded cold table
    (``G_s``). Row offsets are recomputed for both tables.
    """

    cfg: EmbeddingConfig
    hot_rows: tuple[int, ...]

    @property
    def hot_offsets(self) -> np.ndarray:
        return np.concatenate([[0], np.cumsum(self.hot_rows)]).astype(np.int64)

    @property
    def cold_rows(self) -> tuple[int, ...]:
        return tuple(
            self.cfg.storage_rows(f) - self.hot_rows[f]
            for f in range(self.cfg.num_features)
        )

    @property
    def cold_offsets(self) -> np.ndarray:
        return np.concatenate([[0], np.cumsum(self.cold_rows)]).astype(np.int64)

    @property
    def total_hot(self) -> int:
        return int(self.hot_offsets[-1])

    @property
    def total_cold(self) -> int:
        return int(self.cold_offsets[-1])


def make_hot_cold_layout(
    cfg: EmbeddingConfig, capacity_rows: int, access_freq: Sequence[np.ndarray] | None = None
) -> HotColdLayout:
    """Size the hot set under a row-capacity budget (memory capacity /
    co-location degree, per the paper).

    With frequency-ranked ids, the optimal hot set under a shared budget fills
    tables proportionally to their access mass; ``access_freq`` (per-feature
    access counts, optional) weights the split, else pooling factors are used
    as the access-mass proxy (a table looked up P times per query is P times
    hotter).
    """
    F = cfg.num_features
    if access_freq is not None:
        mass = np.array([float(np.sum(a)) for a in access_freq], np.float64)
    else:
        mass = np.array(cfg.pooling, np.float64)
    mass = mass / mass.sum()
    hot = [
        int(min(cfg.storage_rows(f), np.floor(mass[f] * capacity_rows)))
        for f in range(F)
    ]
    return HotColdLayout(cfg=cfg, hot_rows=tuple(hot))


def split_hot_cold(params, layout: HotColdLayout):
    """Re-lay the combined table into {hot, cold} ``[rows, dim]`` tables per
    the layout (a ``LineTable`` is unpacked here, once, outside any step)."""
    cfg = layout.cfg
    table = params["table"]
    if isinstance(table, LineTable):
        table = table.rows()
    hots, colds = [], []
    off = cfg.row_offsets
    for f in range(cfg.num_features):
        t = table[int(off[f]) : int(off[f + 1])]
        hots.append(t[: layout.hot_rows[f]])
        colds.append(t[layout.hot_rows[f] :])
    return {
        "hot": jnp.concatenate(hots, axis=0) if layout.total_hot else jnp.zeros((0, cfg.dim), table.dtype),
        "cold": jnp.concatenate(colds, axis=0),
    }


def embedding_bag_hot_cold(
    split_params, ids: jax.Array, layout: HotColdLayout
) -> tuple[jax.Array, jax.Array]:
    """Pooled lookup returning separate (hot_psum, cold_psum), each [B, F, D].

    The caller adds them; keeping them separate mirrors the paper's pipeline
    where the hot partial sum is produced on the accelerator and the cold
    partial sum (Psum) arrives from the host/sharded side.  Runs under the
    same ``gather`` / ``pool`` scopes as ``embedding_bag_local``.
    """
    cfg = layout.cfg
    B, F, P = ids.shape
    dim = cfg.dim
    with jax.named_scope("gather"):
        hot_rows = jnp.asarray(layout.hot_rows, jnp.int32)[None, :, None]
        hot_off = jnp.asarray(layout.hot_offsets[:-1], jnp.int32)[None, :, None]
        cold_off = jnp.asarray(layout.cold_offsets[:-1], jnp.int32)[None, :, None]

        valid = ids >= 0
        safe = jnp.maximum(ids, 0)
        is_hot = valid & (safe < hot_rows)
        is_cold = valid & ~(safe < hot_rows)

        # masked slots index row 0 of the right table; clip because fully-hot
        # (or fully-cold) features leave the other table's offset out of range
        # (jnp.take's default OOB mode is 'fill' = NaN).
        n_hot = max(layout.total_hot, 1)
        n_cold = max(layout.total_cold, 1)
        hot_idx = jnp.clip(jnp.where(is_hot, safe, 0) + hot_off, 0, n_hot - 1)
        cold_idx = jnp.clip(jnp.where(is_cold, safe - hot_rows, 0) + cold_off, 0,
                            n_cold - 1)
        if layout.total_hot:
            hot_rows_g = jnp.take(split_params["hot"], hot_idx.reshape(-1),
                                  axis=0).reshape(B, F, P, dim)
        cold_rows_g = jnp.take(split_params["cold"], cold_idx.reshape(-1),
                               axis=0).reshape(B, F, P, dim)

    with jax.named_scope("pool"):
        if layout.total_hot:
            hot_psum = (hot_rows_g * is_hot[..., None].astype(hot_rows_g.dtype)
                        ).sum(axis=2)
        else:
            hot_psum = jnp.zeros((B, F, dim), split_params["cold"].dtype)
        cold_psum = (cold_rows_g * is_cold[..., None].astype(cold_rows_g.dtype)
                     ).sum(axis=2)
        return hot_psum, cold_psum
