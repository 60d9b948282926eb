"""b-Bit Sketch Trie (bST) and baseline succinct tries, as JAX pytrees.

Every index is a stack of per-level *encodings* with one uniform traced
operation

    children(parent_ids: int32[F]) -> (ids, labels, exists): int32[F, 2^b]

— i.e. the paper's ``children(u)`` but over a whole frontier at once
(see DESIGN.md §2: DFS -> level-synchronous traversal).  The encodings
gather with (2^b, F) index arrays and transpose the results: the TPU
compiler emits a gather whose index array has a short minor axis (2^b)
as straight-line code that grows with F — minutes of compile time per
program at serving frontier widths — and a long minor axis as a loop.
Encodings:

  * ``DenseLevel``  — complete 2^b-ary level: children are arithmetic,
                      storage is *zero bits* (paper §V-A).
  * ``TableLevel``  — bitmap H_ℓ of length 2^b·t_{ℓ-1}; existence is
                      ``H.get``, the child id is ``H.rank`` (paper §V-B).
  * ``ListLevel``   — labels C_ℓ + first-sibling bitvector B_ℓ; the child
                      range is two ``select`` calls (paper §V-B).
  * ``LoudsLevel``  — labels C_ℓ + unary degree sequence U_ℓ with
                      ``select0`` child ranges — the LOUDS-trie baseline.
  * ``SparseTail``  — collapsed root-to-leaf suffix paths P (stored
                      directly in the *vertical bit-plane format* the
                      Pallas kernel streams) + leftmost-leaf bitvector D
                      (paper §V-C).

``build_bst`` assembles dense/table-or-list/sparse per the paper's density
rules; ``build_louds`` / ``build_fst_style`` assemble the comparison
structures of Table III from the same TrieLevels scan.
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .bitvector import BitVector
from .hamming import pack_vertical
from .trie_builder import TrieLevels, build_trie_levels, pick_layers, table_or_list

BIG = jnp.int32(1 << 20)


# ---------------------------------------------------------------------------
# level encodings
# ---------------------------------------------------------------------------

@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class DenseLevel:
    b: int
    t_prev: int

    def tree_flatten(self):
        return (), (self.b, self.t_prev)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*aux)

    def children(self, u: jnp.ndarray):
        A = 1 << self.b
        c = jnp.arange(A, dtype=jnp.int32)[None, :]
        ids = u[:, None] * A + c
        labels = jnp.broadcast_to(c, ids.shape)
        exists = jnp.ones(ids.shape, dtype=bool)
        return ids, labels, exists

    def model_bits(self) -> int:
        return 64  # just the level number (paper: O(log ℓ_m))

    def array_bytes(self) -> int:
        return 8


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class TableLevel:
    H: BitVector
    b: int
    t_prev: int

    def tree_flatten(self):
        return (self.H,), (self.b, self.t_prev)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], *aux)

    def children(self, u: jnp.ndarray):
        A = 1 << self.b
        c = jnp.arange(A, dtype=jnp.int32)[:, None]
        u_safe = jnp.clip(u, 0, self.t_prev - 1)
        pos = u_safe[None, :] * A + c                    # (A, F)
        exists = (self.H.get(pos) == 1).T
        ids = self.H.rank(pos).T                         # ones before pos = child index
        labels = jnp.broadcast_to(c.T, ids.shape)
        return ids, labels, exists

    def model_bits(self) -> int:
        n = (1 << self.b) * self.t_prev
        return n + int(self.H.cum.shape[0]) * 32  # payload + rank dir (o(n) modeled as actual)

    def array_bytes(self) -> int:
        return int(self.H.words.nbytes + self.H.cum.nbytes)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class ListLevel:
    C: jnp.ndarray        # (t,) uint8 edge labels
    B: BitVector          # (t,) first-sibling flags
    b: int
    t_prev: int

    def tree_flatten(self):
        return (self.C, self.B), (self.b, self.t_prev)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], children[1], *aux)

    def children(self, u: jnp.ndarray):
        A = 1 << self.b
        t = self.C.shape[0]
        u_safe = jnp.clip(u, 0, self.t_prev - 1)
        start = self.B.select(u_safe + 1)                # (F,)
        end = self.B.select(u_safe + 2)                  # t for the last parent
        j = jnp.arange(A, dtype=jnp.int32)[:, None]
        ids = start[None, :] + j                         # (A, F)
        exists = ids < end[None, :]
        labels = self.C[jnp.clip(ids, 0, t - 1)].astype(jnp.int32)
        return ids.T, labels.T, exists.T

    def model_bits(self) -> int:
        t = int(self.C.shape[0])
        return (self.b + 1) * t + int(self.B.cum.shape[0]) * 32

    def array_bytes(self) -> int:
        return int(self.C.nbytes + self.B.words.nbytes + self.B.cum.nbytes)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class LoudsLevel:
    C: jnp.ndarray        # (t,) uint8 edge labels
    U: BitVector          # (t_prev + t,) unary degrees: 1^deg 0 per parent
    b: int
    t_prev: int

    def tree_flatten(self):
        return (self.C, self.U), (self.b, self.t_prev)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], children[1], *aux)

    def children(self, u: jnp.ndarray):
        A = 1 << self.b
        t = self.C.shape[0]
        u_safe = jnp.clip(u, 0, self.t_prev - 1)
        # ones before the u-th zero = cumulative degree of parents < u
        s0 = self.U.select0(jnp.maximum(u_safe, 1))
        start = jnp.where(u_safe == 0, 0, s0 - u_safe + 1)
        end = self.U.select0(u_safe + 1) - u_safe
        j = jnp.arange(A, dtype=jnp.int32)[:, None]
        ids = start[None, :] + j                         # (A, F)
        exists = ids < end[None, :]
        labels = self.C[jnp.clip(ids, 0, t - 1)].astype(jnp.int32)
        return ids.T, labels.T, exists.T

    def model_bits(self) -> int:
        t = int(self.C.shape[0])
        # labels b bits + 2 topology bits per node (unary seq has t ones, ~t zeros)
        return self.b * t + (self.t_prev + t) + int(self.U.cum.shape[0]) * 32

    def array_bytes(self) -> int:
        return int(self.C.nbytes + self.U.words.nbytes + self.U.cum.nbytes)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class SparseTail:
    paths_vert: jnp.ndarray   # (b, W_sfx, t_L) uint32 — kernel-ready layout
    D: BitVector              # (t_L,) leftmost-leaf flags per ℓ_s subtrie
    leaf_root: jnp.ndarray    # (t_L,) int32 — leaf -> its ℓ_s ancestor id
    b: int
    suffix_len: int
    t_root: int               # t[ℓ_s]

    def tree_flatten(self):
        return (self.paths_vert, self.D, self.leaf_root), (self.b, self.suffix_len, self.t_root)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], children[1], children[2], *aux)

    def model_bits(self) -> int:
        t_L = int(self.leaf_root.shape[0])
        return self.b * self.suffix_len * t_L + t_L + int(self.D.cum.shape[0]) * 32

    def array_bytes(self) -> int:
        return int(self.paths_vert.nbytes + self.D.words.nbytes
                   + self.D.cum.nbytes + self.leaf_root.nbytes)


# ---------------------------------------------------------------------------
# index container
# ---------------------------------------------------------------------------

@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class SketchIndex:
    """A trie index over one database (shard) of b-bit sketches."""

    levels: Tuple        # encodings for ℓ = 1 .. depth (ℓ_s for bST, L otherwise)
    tail: Optional[SparseTail]
    id_leaf: jnp.ndarray  # (n,) original id -> leaf index
    # static metadata
    L: int
    b: int
    n: int
    t: Tuple[int, ...]   # node counts per level 0..L
    lm: int
    ls: int
    kinds: Tuple[str, ...]

    def tree_flatten(self):
        return (self.levels, self.tail, self.id_leaf), (
            self.L, self.b, self.n, self.t, self.lm, self.ls, self.kinds)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], children[1], children[2], *aux)

    # -- space accounting (drives Table III / Table IV benchmarks) -------
    def model_bits(self) -> int:
        bits = sum(lv.model_bits() for lv in self.levels)
        if self.tail is not None:
            bits += self.tail.model_bits()
        return bits

    def array_bytes(self, include_ids: bool = True) -> int:
        by = sum(lv.array_bytes() for lv in self.levels)
        if self.tail is not None:
            by += self.tail.array_bytes()
        if include_ids:
            by += int(self.id_leaf.nbytes)
        return by


# ---------------------------------------------------------------------------
# builders
# ---------------------------------------------------------------------------

def _build_table_level(trie: TrieLevels, lev: int) -> TableLevel:
    A = 1 << trie.b
    t_prev = trie.t[lev - 1]
    bits = np.zeros(A * t_prev, dtype=np.uint8)
    pos = trie.parents[lev] * A + trie.labels[lev].astype(np.int64)
    bits[pos] = 1
    return TableLevel(H=BitVector.from_bits(bits), b=trie.b, t_prev=t_prev)


def _build_list_level(trie: TrieLevels, lev: int) -> ListLevel:
    par = trie.parents[lev]
    first = np.concatenate([[True], par[1:] != par[:-1]]) if len(par) > 1 else np.ones(len(par), bool)
    return ListLevel(C=jnp.asarray(trie.labels[lev]),
                     B=BitVector.from_bits(first.astype(np.uint8)),
                     b=trie.b, t_prev=trie.t[lev - 1])


def _build_louds_level(trie: TrieLevels, lev: int) -> LoudsLevel:
    par = trie.parents[lev]
    t_prev = trie.t[lev - 1]
    deg = np.bincount(par, minlength=t_prev)
    u_bits = np.zeros(t_prev + len(par), dtype=np.uint8)
    # 1^deg 0 per parent: ones everywhere except at terminator positions
    term = np.cumsum(deg + 1) - 1
    u_bits[:] = 1
    u_bits[term] = 0
    return LoudsLevel(C=jnp.asarray(trie.labels[lev]),
                      U=BitVector.from_bits(u_bits), b=trie.b, t_prev=t_prev)


def _build_sparse_tail(trie: TrieLevels, ls: int) -> SparseTail:
    t_L = trie.t[trie.L]
    sfx = trie.L - ls
    leaf_root = trie.node_of_leaf[ls]
    if sfx > 0:
        suffixes = trie.uniq[:, ls:]
        planes = pack_vertical(suffixes, trie.b)            # (t_L, b, W)
        paths_vert = np.transpose(planes, (1, 2, 0)).copy() # (b, W, t_L)
    else:
        paths_vert = np.zeros((trie.b, 1, t_L), dtype=np.uint32)
    d_bits = np.concatenate([[1], (leaf_root[1:] != leaf_root[:-1]).astype(np.uint8)]) \
        if t_L > 1 else np.ones(1, np.uint8)
    return SparseTail(paths_vert=jnp.asarray(paths_vert),
                      D=BitVector.from_bits(d_bits),
                      leaf_root=jnp.asarray(leaf_root, dtype=jnp.int32),
                      b=trie.b, suffix_len=sfx, t_root=trie.t[ls])


def build_bst(sketches: np.ndarray, b: int, lam: float = 0.5,
              trie: Optional[TrieLevels] = None) -> SketchIndex:
    """The paper's bST: dense prefix + adaptive TABLE/LIST middle + collapsed
    sparse tail.

    sketches: (n, L) uint8 over Σ=[0, 2^b); returns a queryable
    ``SketchIndex`` pytree (ids are row positions in ``sketches``)."""
    trie = trie or build_trie_levels(sketches, b)
    lm, ls = pick_layers(trie, lam)
    levels: List = []
    kinds: List[str] = []
    for lev in range(1, ls + 1):
        if lev <= lm:
            levels.append(DenseLevel(b=b, t_prev=trie.t[lev - 1]))
            kinds.append("dense")
        elif table_or_list(trie, lev) == "table":
            levels.append(_build_table_level(trie, lev))
            kinds.append("table")
        else:
            levels.append(_build_list_level(trie, lev))
            kinds.append("list")
    tail = _build_sparse_tail(trie, ls)
    return SketchIndex(levels=tuple(levels), tail=tail,
                       id_leaf=jnp.asarray(trie.id_leaf, dtype=jnp.int32),
                       L=trie.L, b=b, n=trie.n, t=tuple(trie.t),
                       lm=lm, ls=ls, kinds=tuple(kinds))


def build_louds(sketches: np.ndarray, b: int,
                trie: Optional[TrieLevels] = None) -> SketchIndex:
    """LOUDS-trie baseline: every level as (labels, unary-degree bitvector),
    no dense shortcut, no path collapse (Table III comparison).
    sketches: (n, L) uint8 -> ``SketchIndex``."""
    trie = trie or build_trie_levels(sketches, b)
    levels = tuple(_build_louds_level(trie, lev) for lev in range(1, trie.L + 1))
    return SketchIndex(levels=levels, tail=None,
                       id_leaf=jnp.asarray(trie.id_leaf, dtype=jnp.int32),
                       L=trie.L, b=b, n=trie.n, t=tuple(trie.t),
                       lm=0, ls=trie.L, kinds=tuple(["louds"] * trie.L))


def build_fst_style(sketches: np.ndarray, b: int,
                    trie: Optional[TrieLevels] = None) -> SketchIndex:
    """FST-style two-layer baseline: bitmap-encoded (LOUDS-DENSE-like) top
    levels while the density rule favours TABLE, list-encoded
    (LOUDS-SPARSE-like) below; no path collapse (Table III comparison).
    sketches: (n, L) uint8 -> ``SketchIndex``."""
    trie = trie or build_trie_levels(sketches, b)
    levels: List = []
    kinds: List[str] = []
    in_top = True
    for lev in range(1, trie.L + 1):
        if in_top and table_or_list(trie, lev) == "table":
            levels.append(_build_table_level(trie, lev))
            kinds.append("table")
        else:
            in_top = False
            levels.append(_build_list_level(trie, lev))
            kinds.append("list")
    return SketchIndex(levels=tuple(levels), tail=None,
                       id_leaf=jnp.asarray(trie.id_leaf, dtype=jnp.int32),
                       L=trie.L, b=b, n=trie.n, t=tuple(trie.t),
                       lm=0, ls=trie.L, kinds=tuple(kinds))
