"""Traffic for the chip benchmark: item features, query sizes and arrivals.

The distributions are those of the program's click-log generator (the
paper's Fig. 2), drawn here in bulk with numpy so that a pool of items is
made in set-up in about a second.  Each configuration family draws its
items' arrays from these (``make_pool`` in ``chipbench/families/``):

- ids: frequency-ranked power law, ``id = floor(V ** (u ** alpha)) - 1``
  for ``u ~ U(0, 1)``, so id 0 is the hottest row of each table;
- pooling (valid ids per bag): lognormal around 0.6 x nominal, truncated
  to an integer and clipped to ``[1, nominal]``;
- query sizes (items per query): lognormal, truncated and clipped to
  ``[1, max]``;
- dense features: standard normal.

Open-loop queries are drawn so that every seed gets the same multiset of
query sizes and of inter-arrival gaps, in its own order: sizes at the
quantiles ``(i + 1/2) / n`` of their distribution, gaps at those of the
exponential one.  The seed then moves the order (and so the bursts), not
the amount of work.
"""
from __future__ import annotations

import dataclasses
import statistics

import numpy as np


@dataclasses.dataclass(frozen=True)
class Distributions:
    zipf_alpha: float = 1.05
    pooling_sigma: float = 0.6
    query_size_mu: float = float(np.log(64))
    query_size_sigma: float = 1.1
    query_size_max: int = 1024
    # further parameters of the traffic file, read by a family's pool
    # (chipbench/families/<family>.py), such as a history's length
    extra: dict = dataclasses.field(default_factory=dict)

    @classmethod
    def from_mix(cls, mix: dict, extra_keys=()) -> "Distributions":
        """The traffic file's distributions; ``extra_keys`` are the further
        parameters the family's pool reads (its ``TRAFFIC_KEYS``).  Any
        other key is refused, so that a misspelled one does not run the
        defaults."""
        given = dict(mix.get("distributions", {}))
        known = {f.name for f in dataclasses.fields(cls)} - {"extra"}
        unknown = set(given) - known - set(extra_keys)
        if unknown:
            raise SystemExit(f"traffic parameters {sorted(unknown)} are read by nothing; "
                             f"known: {sorted(known | set(extra_keys))}")
        return cls(**{k: given.pop(k) for k in known & set(given)}, extra=given)


def zipf_ids(rng: np.random.Generator, vocab: int, size, alpha: float):
    u = rng.random(size) ** alpha
    ids = np.floor(np.power(float(vocab), u)) - 1.0
    return np.clip(ids, 0, vocab - 1).astype(np.int32)


def pooling_counts(rng: np.random.Generator, nominal: int, size,
                   sigma: float) -> np.ndarray:
    if nominal <= 1:
        return np.ones(size, np.int32)
    ln = rng.lognormal(np.log(max(nominal, 2) * 0.6), sigma, size)
    return np.clip(ln.astype(np.int64), 1, nominal).astype(np.int32)


def query_sizes(rng: np.random.Generator, n: int, dist: Distributions):
    s = rng.lognormal(dist.query_size_mu, dist.query_size_sigma, n)
    return np.clip(s.astype(np.int64), 1, dist.query_size_max)


@dataclasses.dataclass
class Pool:
    """Candidate items to score: row ``i`` of every array is item ``i``."""

    arrays: dict[str, np.ndarray]  # the step's inputs by name, each [N, ...]
    counts: np.ndarray             # [N, ...] valid table lookups of each item

    def __post_init__(self):
        per_item = self.counts.reshape(len(self.counts), -1).sum(axis=1, dtype=np.int64)
        self._cum = np.concatenate([[0], np.cumsum(per_item)])

    def __len__(self) -> int:
        return len(self.counts)

    def lookups(self, start: int, n: int) -> int:
        """Valid ids of ``n`` items from ``start``, round the pool."""
        N, cum = len(self), self._cum
        laps, rest = divmod(n, N)
        s = start % N
        e = s + rest
        part = cum[e] - cum[s] if e <= N else (cum[N] - cum[s]) + cum[e - N]
        return int(laps * cum[N] + part)


def _stratified(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def open_loop(seed: int, n: int, rate_qps: float, dist: Distributions):
    """(due times in s from the window's start, sizes) of ``n`` queries."""
    rng = np.random.default_rng([seed, 2])
    z = np.array([statistics.NormalDist().inv_cdf(p) for p in _stratified(n)])
    sizes = np.exp(dist.query_size_mu + dist.query_size_sigma * z)
    sizes = np.clip(sizes.astype(np.int64), 1, dist.query_size_max)
    gaps = -np.log1p(-_stratified(n)) / rate_qps
    return np.cumsum(rng.permutation(gaps)), rng.permutation(sizes)
