"""Corpus and query generation from a seed.

The corpus is near-uniform b-bit sketches, as b-bit minhash yields
(paper §V; the same draw as ``benchmarks/common.make_dataset`` and
``chip_smoke.py``).  A fixed base draw is turned into the seed's corpus
by two maps that keep every shape the index builds:

* each sketch position gets its own permutation of the alphabet, which
  keeps Hamming distances and the number of distinct prefixes at every
  trie level;
* rows are shuffled inside each block of ``delta_cap`` rows, the unit
  that one seal turns into a segment, so every segment (and every merge
  of segments) holds the same set of rows up to those relabelings.

So every seed builds tries, column stores and programs of the same
shapes, the persistent compile cache serves every seed after the first,
and the seed changes the rows, the ids that answer and the queries.
"""

from __future__ import annotations

from typing import Tuple

import numpy as np

# the base draw; the seed only relabels and reorders it (module docstring)
BASE_SEED = 1910_08278


def _rng(seed: int, stream: int) -> np.random.Generator:
    """Independent generator per (seed, stream); any non-negative seed,
    however large."""
    return np.random.default_rng([int(seed) & ((1 << 64) - 1), stream])


def make_corpus(n: int, L: int, b: int, delta_cap: int,
                seed: int) -> np.ndarray:
    """(n, L) uint8 corpus over [0, 2^b) for ``seed``."""
    base = np.random.default_rng(BASE_SEED).integers(
        0, 1 << b, size=(n, L), dtype=np.uint8)
    rng = _rng(seed, 0)
    db = np.empty_like(base)
    for j in range(L):
        perm = rng.permutation(1 << b).astype(np.uint8)
        db[:, j] = perm[base[:, j]]
    del base
    for lo in range(0, n, delta_cap):
        hi = min(n, lo + delta_cap)
        db[lo:hi] = db[lo:hi][rng.permutation(hi - lo)]
    return db


def make_queries(db: np.ndarray, b: int, count: int, perturbed_share: float,
                 flips: int, seed: int, stream: int = 1) -> np.ndarray:
    """(count, L) uint8 queries: ``round(count * perturbed_share)``
    corpus rows with ``flips`` positions redrawn (near neighbours exist),
    the rest uniform random rows, shuffled together."""
    n, L = db.shape
    rng = _rng(seed, stream)
    n_pert = int(round(count * perturbed_share))
    q = np.empty((count, L), np.uint8)
    q[:n_pert] = db[rng.integers(0, n, n_pert)]
    for i in range(n_pert):
        pos = rng.integers(0, L, size=flips)
        q[i, pos] = rng.integers(0, 1 << b, size=flips)
    q[n_pert:] = rng.integers(0, 1 << b, size=(count - n_pert, L),
                              dtype=np.uint8)
    return q[rng.permutation(count)]


def arrival_offsets(count: int, seconds: float, order: int) -> np.ndarray:
    """(count,) ascending send offsets in [0, seconds): Poisson-like
    arrivals whose gaps are the exponential distribution's quantiles at
    (i + 0.5) / count, scaled to sum to ``seconds``, shuffled by the
    generator seeded with ``order``.  This is one realization of a
    Poisson process, the same for every run seed (which request goes
    when is the seed's): the bursts a tail latency depends on belong to
    the traffic mix, which names ``order``, not to the run."""
    u = (np.arange(count) + 0.5) / count
    gaps = -np.log1p(-u)
    gaps *= seconds / gaps.sum()
    gaps = gaps[np.random.default_rng(order).permutation(count)]
    return np.concatenate([[0.0], np.cumsum(gaps)[:-1]])


def arrivals(traffic: dict, count: int, seconds: float) -> np.ndarray:
    """Send offsets of a traffic mix's requests, by its ``arrivals``."""
    if traffic["arrivals"] != "poisson":
        raise ValueError(f"unknown arrivals {traffic['arrivals']!r}")
    return arrival_offsets(count, seconds, int(traffic["arrival_order"]))


def split_blocks(n: int, chunk: int) -> Tuple[Tuple[int, int], ...]:
    """Row ranges of the insert chunks that load the corpus."""
    return tuple((lo, min(n, lo + chunk)) for lo in range(0, n, chunk))
