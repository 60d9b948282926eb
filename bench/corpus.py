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
shapes, and the persistent compile cache serves every seed after the
first.  The queries are one fixed set drawn against the base draw and
carried through the same two maps, so each query finds the same
distances, candidates and τ-ladder rungs under every seed: the seed
changes the labels, the ids that answer and the order in which the
queries are sent, never the amount of work.
"""

from __future__ import annotations

import functools
from typing import Tuple

import numpy as np

# the base draw; the seed only relabels and reorders it (module docstring)
BASE_SEED = 1910_08278


def _rng(seed: int, stream: int) -> np.random.Generator:
    """Independent generator per (seed, stream); any non-negative seed,
    however large."""
    return np.random.default_rng([int(seed) & ((1 << 64) - 1), stream])


@functools.lru_cache(maxsize=2)
def _relabeling(n: int, L: int, b: int, delta_cap: int, seed: int):
    """The seed's two maps of the base draw: ``perms[j]`` relabels
    position j's alphabet, and ``shuffles[i]`` reorders the rows of the
    i-th block of ``delta_cap`` rows (module docstring)."""
    rng = _rng(seed, 0)
    perms = np.stack([rng.permutation(1 << b).astype(np.uint8)
                      for _ in range(L)])
    shuffles = tuple(rng.permutation(min(n, lo + delta_cap) - lo)
                     for lo in range(0, n, delta_cap))
    return perms, shuffles


def make_corpus(n: int, L: int, b: int, delta_cap: int,
                seed: int) -> np.ndarray:
    """(n, L) uint8 corpus over [0, 2^b) for ``seed``."""
    base = np.random.default_rng(BASE_SEED).integers(
        0, 1 << b, size=(n, L), dtype=np.uint8)
    perms, shuffles = _relabeling(n, L, b, delta_cap, seed)
    db = np.empty_like(base)
    for j in range(L):
        db[:, j] = perms[j][base[:, j]]
    del base
    for lo, shuffle in zip(range(0, n, delta_cap), shuffles):
        db[lo:lo + len(shuffle)] = db[lo:lo + len(shuffle)][shuffle]
    return db


def make_queries(db: np.ndarray, b: int, count: int, perturbed_share: float,
                 flips: int, seed: int, stream: int = 1, *,
                 delta_cap: int) -> np.ndarray:
    """(count, L) uint8 queries for the corpus ``make_corpus`` made from
    ``seed`` and ``delta_cap``: ``round(count * perturbed_share)`` corpus
    rows with ``flips`` positions redrawn (near neighbours exist), the
    rest uniform random rows.  The rows, positions and characters are
    drawn once for ``stream`` against the base draw, and the seed's maps
    carry them over (module docstring); the seed then shuffles them."""
    n, L = db.shape
    perms, shuffles = _relabeling(n, L, b, delta_cap, seed)
    rng = _rng(BASE_SEED, stream)       # the same set for every seed
    n_pert = int(round(count * perturbed_share))
    src = rng.integers(0, n, n_pert)    # base rows
    at = np.empty(n_pert, np.int64)     # where the seed's corpus holds them
    for lo, shuffle in zip(range(0, n, delta_cap), shuffles):
        here = (src >= lo) & (src < lo + len(shuffle))
        if here.any():
            inv = np.empty_like(shuffle)
            inv[shuffle] = np.arange(len(shuffle))
            at[here] = lo + inv[src[here] - lo]
    q = np.empty((count, L), np.uint8)
    q[:n_pert] = db[at]
    for i in range(n_pert):
        pos = rng.integers(0, L, size=flips)
        q[i, pos] = perms[pos, rng.integers(0, 1 << b, size=flips)]
    rest = rng.integers(0, 1 << b, size=(count - n_pert, L))
    q[n_pert:] = perms[np.arange(L), rest]
    return q[_rng(seed, stream).permutation(count)]


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
