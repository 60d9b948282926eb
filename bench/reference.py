"""The plain reference: exact top-k by brute-force Hamming scan.

Nothing here imports the program.  ``DeviceReference`` scans every row
with ``jax.numpy`` in blocks of queries and takes the k smallest
(distance, id) keys, each packed into one int32, so that a window's
answers are checked in seconds at published corpus sizes; ``numpy_topk`` is
the same semantics in NumPy (the brute force of ``chip_smoke.py``),
the second witness the tests hold the device scan to.

Semantics (the program's top-k contract): the k live rows nearest to the
query in Hamming distance over the L characters, ascending by
(distance, id), where a row's id is its position in insertion order.

The control (``drop_low_bit=True``) is the same scan one step down in
precision: each character is compared on its high b - 1 bits only, so
two characters that differ in their lowest bit count as equal.  It
breaks the configuration's exactness guarantee, and the output check
must fail it.
"""

from __future__ import annotations

import functools

import numpy as np

WORD = 32


def words_per_row(L: int, b: int) -> int:
    return -(-L * b // WORD)


def pack_rows(rows: np.ndarray, b: int) -> np.ndarray:
    """(n, L) uint8 -> (W, n) uint32: character j at bit (j·b) % 32 of
    word (j·b) // 32."""
    n, L = rows.shape
    out = np.zeros((words_per_row(L, b), n), np.uint32)
    for j in range(L):
        w, s = divmod(j * b, WORD)
        out[w] |= rows[:, j].astype(np.uint32) << np.uint32(s)
    return out


def _char_masks(L: int, b: int, drop_low_bit: bool):
    """Per word: the mask of each character's lowest kept bit, and the
    mask of the bits compared."""
    W = words_per_row(L, b)
    low = np.zeros(W, np.uint64)
    keep = np.zeros(W, np.uint64)
    char = (1 << b) - 1
    if drop_low_bit:
        char &= ~1
    for j in range(L):
        w, s = divmod(j * b, WORD)
        low[w] |= (1 << s)
        keep[w] |= (char << s)
    return low.astype(np.uint32), keep.astype(np.uint32)


def numpy_dists(packed: np.ndarray, q: np.ndarray, L: int, b: int,
                drop_low_bit: bool = False) -> np.ndarray:
    """(n,) int32 Hamming distance of every packed row to query ``q``."""
    low, keep = _char_masks(L, b, drop_low_bit)
    qw = pack_rows(q[None], b)[:, 0]
    d = np.zeros(packed.shape[1], np.int32)
    for w in range(packed.shape[0]):
        x = (packed[w] ^ qw[w]) & keep[w]
        diff = x
        for i in range(1, b):
            diff = diff | (x >> np.uint32(i))
        d += np.bitwise_count(diff & low[w]).astype(np.int32)
    return d


def numpy_topk(packed: np.ndarray, q: np.ndarray, k: int, L: int, b: int,
               drop_low_bit: bool = False):
    """(k,) ids, (k,) distances ascending by (distance, id)."""
    d = numpy_dists(packed, q, L, b, drop_low_bit)
    kth = np.partition(d, k - 1)[k - 1]
    cand = np.flatnonzero(d <= kth)
    top = cand[np.lexsort((cand, d[cand]))][:k]
    return top.astype(np.int64), d[top].astype(np.int64)


ID_BITS = 24          # (distance, id) as one int32: d << ID_BITS | id
NONE = (1 << 31) - 1  # a key above every row's


@functools.lru_cache(maxsize=None)
def _block_fn(k: int, L: int, b: int, drop_low_bit: bool):
    """The jitted scan of one block of queries over every row: each
    row's distance and id as one key, ``d << ID_BITS | id``, whose k
    smallest are the k nearest rows ascending by (distance, id), taken
    one at a time by k minimum reductions.  (``lax.top_k`` over a
    corpus-wide row asks for the whole operand in VMEM on TPU and fails
    at published sizes.)"""
    import jax
    import jax.numpy as jnp

    low, keep = _char_masks(L, b, drop_low_bit)

    @jax.jit
    def run(words, qw):
        # words (W, n) uint32, qw (m, W) uint32
        n = words.shape[1]
        x = (words[None] ^ qw[:, :, None]) & jnp.asarray(keep)[None, :, None]
        diff = x
        for i in range(1, b):
            diff = diff | (x >> i)
        d = jax.lax.population_count(
            diff & jnp.asarray(low)[None, :, None]).astype(jnp.int32)
        d = d.sum(axis=1)                                   # (m, n)
        key = (d << ID_BITS) | jnp.arange(n, dtype=jnp.int32)[None]
        least = []
        for _ in range(k):                  # keys are distinct
            least.append(key.min(axis=1))
            key = jnp.where(key == least[-1][:, None], NONE, key)
        key = jnp.stack(least, axis=1)
        return key & ((1 << ID_BITS) - 1), key >> ID_BITS
    return run


class DeviceReference:
    """Exact top-k over ``rows`` on the default JAX device, ``block``
    queries per call."""

    def __init__(self, rows: np.ndarray, b: int, *, block: int = 8,
                 drop_low_bit: bool = False):
        import jax.numpy as jnp
        self.n, self.L = rows.shape
        if self.n >= 1 << ID_BITS or self.L >= 1 << (31 - ID_BITS):
            raise ValueError("(distance, id) does not fit one int32 key")
        self.b = b
        self.block = block
        self.drop_low_bit = drop_low_bit
        self.words = jnp.asarray(pack_rows(rows, b))

    def topk(self, qs: np.ndarray, k: int):
        """(m, L) queries -> (m, k) int64 ids, (m, k) int64 distances,
        ascending by (distance, id)."""
        m = len(qs)
        fn = _block_fn(int(k), self.L, self.b, self.drop_low_bit)
        ids = np.empty((m, k), np.int64)
        dists = np.empty((m, k), np.int64)
        for lo in range(0, m, self.block):
            blk = qs[lo:lo + self.block]
            pad = np.zeros((self.block, self.L), np.uint8)
            pad[:len(blk)] = blk
            qw = pack_rows(pad, self.b).T.copy()             # (block, W)
            i, d = fn(self.words, qw)
            ids[lo:lo + len(blk)] = np.asarray(i)[:len(blk)]
            dists[lo:lo + len(blk)] = np.asarray(d)[:len(blk)]
        return ids, dists
