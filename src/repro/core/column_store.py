"""Tiered suffix column store: the layout + placement layers of the
segment data plane (DESIGN.md §7).

The PR-5 arena (`segments._ColumnArena`) keeps one *full-length*
(b, W, R) verify column per sealed row device-resident.  That is
redundant: the fused program's traversal already computes the exact
prefix distance down to every segment's collapse depth ℓ_s, and the
verify kernel receives it through the root base plane — so the
columns only need the **suffix** below ℓ_s.  This module owns that
observation end to end, split into two layers:

**Layout** — per-segment packed suffix columns.  Each sealed segment
gets a `_Block` whose geometry depends on its own ℓ_s: when the b bit
planes of the S = L - ℓ_s suffix symbols fit one 32-bit word
(b·S <= 32 — every paper dataset with b <= 2), the whole row packs into
a single uint32 (`hamming.pack_suffix_words`, kernel
`sparse_verify_arena_packed`); otherwise the block falls back to
plane-packed (b, ceil(S/32), n) columns consumed by the unchanged
full-length arena kernel with W = ceil(S/32).  Blocks with equal
geometry share one kernel call inside the ONE jitted program per rung —
the dispatch contract (`_DISPATCH_STATS`) counts program launches, not
kernel bodies, so heterogeneous ℓ_s still costs one fused dispatch.

**Placement** — per-block tier policy.  Hot blocks keep their columns
device-resident (closed over by the compiled program, exactly like the
PR-5 arena).  Cold blocks keep them host-packed only; before a rung
executes, `stage()` copies every cold block's columns ahead into a
device staging slab (one async `jax.device_put` per geometry group,
bounded by the cold bytes of the current plan) that the program takes
as a *traced* argument.  Demotion is LRU under the `hot_bytes` budget
(`None` = unlimited: everything stays hot, byte-for-byte the PR-5
behavior); freed budget promotes the most recently used cold block
back.  Tier flips bump `gen`, which keys the fused-program cache — a
stale program can never read a moved block.

The store keeps the arena's maintenance surface (`serials`, `live`,
`col_off`, `col_ids`, `array_bytes`) so `SegmentedIndex.delete` flips
device liveness lanes in place and incremental flush appends work
unchanged; `segments._ColumnArena` survives as the bit-identical
full-length reference (`layout="full"`).
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .hamming import n_words, pack_suffix_words, pack_vertical
from ..kernels.hamming_kernel import DEFAULT_BLOCK_N, plan_windows
from ..obs.trace import span as _obs_span

WORD_BYTES = 4
TIER_HOT = "hot"
TIER_COLD = "cold"

# Process-wide placement counters (mirrors segments._DISPATCH_STATS):
# promotions/demotions count tier flips, prefetches the cold blocks
# staged to device, staged_bytes the bytes those copies moved
# (staged_payload_bytes the payload-bitmap share, DESIGN.md §10).
_TIER_STATS = {"promotions": 0, "demotions": 0, "prefetches": 0,
               "staged_bytes": 0, "staged_payload_bytes": 0}


def tier_stats() -> Dict[str, int]:
    """Placement counters of the tiered column store: ``promotions`` /
    ``demotions`` (tier flips under the ``hot_bytes`` budget),
    ``prefetches`` (cold blocks copied ahead to the device staging slab)
    and ``staged_bytes`` (bytes those copies moved)."""
    return dict(_TIER_STATS)


def reset_tier_stats() -> None:
    for k in _TIER_STATS:
        _TIER_STATS[k] = 0


class SuffixGeometry(NamedTuple):
    """Column geometry of one segment's suffix block: ``suffix_len`` =
    L - ℓ_s symbols below the collapse depth; ``packed`` when all b bit
    planes fit one uint32 word per row (b·suffix_len <= 32);
    ``row_words`` the uint32 words per column (1 packed, b·ceil(S/32)
    plane-packed)."""

    suffix_len: int
    packed: bool
    row_words: int


def geometry_for(L: int, b: int, ls: int) -> SuffixGeometry:
    """Pick the layout for a segment collapsing at depth ``ls``."""
    S = int(L) - int(ls)
    if b * S <= 32:
        return SuffixGeometry(S, True, 1)
    return SuffixGeometry(S, False, b * n_words(S))


@dataclasses.dataclass
class _Block:
    """One sealed segment's suffix columns + placement state.

    ``cols_hot`` (device) and ``cols_cold`` (host) are mutually
    exclusive — exactly one is set, per the block's ``tier``.  Packed
    geometry stores (n,) uint32 words, plane geometry (b, W_sfx, n)
    uint32.  ``roots`` (host, immutable once appended) is each row's ℓ_s
    root within its own segment — rows are in trie order, so it rises
    in steps of 0 or 1 from 0 to ``t_root`` - 1; the plan adds the
    segment's offset in the base plane."""

    serial: int
    n: int
    geom: SuffixGeometry
    roots: np.ndarray
    t_root: int
    cols_hot: Optional[jnp.ndarray] = None
    cols_cold: Optional[np.ndarray] = None
    last_used: int = 0
    # exact re-rank payload bitmaps (DESIGN.md §10): (Wp, n) uint32,
    # same tier as the sketch columns — a tier flip moves both, so the
    # re-rank program's closure/staged split always matches the verify's
    pays_hot: Optional[jnp.ndarray] = None
    pays_cold: Optional[np.ndarray] = None
    pay_words: int = 0

    @property
    def tier(self) -> str:
        return TIER_HOT if self.cols_hot is not None else TIER_COLD

    @property
    def col_bytes(self) -> int:
        return self.n * self.geom.row_words * WORD_BYTES

    @property
    def pay_bytes(self) -> int:
        return self.n * self.pay_words * WORD_BYTES

    @property
    def block_bytes(self) -> int:
        """Placement-budget charge: sketch columns + payload bitmaps
        (both move together on a tier flip)."""
        return self.col_bytes + self.pay_bytes


class _Group(NamedTuple):
    """One geometry group of the current plan: the per-rung program runs
    one verify kernel per group (inside the single fused dispatch).
    ``perm`` maps the group's column order (hot blocks in stack order,
    then cold blocks in stack order) back to global stack positions.
    The base plane lays the segments' roots out in the same group-major
    order, so ``base_idx`` rises in steps of 0 or 1 over the whole group
    and ``windows`` (``plan_windows``: start, steps) holds each column
    block's window of it; ``window_roots`` the roots they span."""

    geom: SuffixGeometry
    cols_hot: Optional[jnp.ndarray]   # concatenated hot columns (device)
    base_idx: jnp.ndarray             # (n_group,) int32 device lane
    windows: Tuple[jnp.ndarray, jnp.ndarray]  # (n_blocks,) int32 each
    window_roots: int
    perm: np.ndarray                  # (n_group,) int64 stack positions
    cold_blocks: Tuple[int, ...]      # indexes into store.blocks
    cold_bytes: int
    pays_hot: Optional[jnp.ndarray] = None  # (Wp, n_hot) payload bitmaps
    pay_cold_bytes: int = 0


class ColumnStore:
    """Tiered suffix column store for one segment stack (bst backend).

    Maintenance mirrors ``_ColumnArena``: a flush *appends* a block (and
    its liveness/gid/id lanes) without touching existing ones; a merge
    or compact changes the serial fingerprint non-monotonically and the
    owner rebuilds from scratch.  ``delete`` flips the shared ``live``
    lanes in place through ``col_off`` — liveness is a traced program
    argument, so tier state never changes on delete.
    """

    def __init__(self, L: int, b: int, hot_bytes: Optional[int] = None,
                 payload_words: Optional[int] = None):
        self.L, self.b = int(L), int(b)
        self.hot_bytes = hot_bytes
        # uint32 words per re-rank payload bitmap (None = no payloads)
        self.payload_words = payload_words
        self.serials: Tuple[int, ...] = ()
        self.blocks: List[_Block] = []
        self.live: jnp.ndarray = jnp.zeros((0,), bool)
        self.gids: jnp.ndarray = jnp.zeros((0,), jnp.int32)
        self.col_ids = np.zeros((0,), np.int64)
        self.col_off: Dict[int, int] = {}
        self.gen = 0                   # bumped on every tier flip
        self._tick = 0                 # LRU clock
        self._plan: Optional[Tuple[_Group, ...]] = None
        self._order: Tuple[Optional[jnp.ndarray], Optional[jnp.ndarray]] = (
            None, None)
        self._root_order: Tuple[int, ...] = ()

    @property
    def n_cols(self) -> int:
        return int(self.col_ids.shape[0])

    # -- maintenance -----------------------------------------------------

    def append_segment(self, seg) -> None:
        """Append one sealed segment's block: suffix columns sliced below
        its own ℓ_s, packed per :func:`geometry_for`, its root lane, plus
        the shared gid/liveness/id lanes.  New blocks start hot; the
        budget is enforced at :meth:`seal`."""
        ls = int(seg.index.ls)
        geom = geometry_for(self.L, self.b, ls)
        sfx = seg.sketches[:, ls:]
        if geom.packed:
            cols = pack_suffix_words(sfx, self.b)            # (n,)
        else:
            cols = np.ascontiguousarray(
                np.transpose(pack_vertical(sfx, self.b), (1, 2, 0)))
        leaf_root = np.asarray(seg.index.tail.leaf_root)
        roots = leaf_root[np.asarray(seg.index.id_leaf)].astype(np.int32)
        pays_hot = None
        pay_words = 0
        if self.payload_words is not None:
            if getattr(seg, "payloads", None) is None:
                raise ValueError(
                    "payload_words is set but the segment holds no payloads")
            pay_words = int(self.payload_words)
            pays_hot = jnp.asarray(np.ascontiguousarray(
                seg.payloads.T.astype(np.uint32)))       # (Wp, n)
        self._tick += 1
        self.blocks.append(_Block(
            serial=seg.serial, n=seg.n, geom=geom, roots=roots,
            t_root=int(seg.index.tail.t_root), cols_hot=jnp.asarray(cols),
            last_used=self._tick, pays_hot=pays_hot, pay_words=pay_words))
        self.col_off[seg.serial] = self.n_cols
        self.live = jnp.concatenate([self.live, jnp.asarray(seg.live)])
        self.gids = jnp.concatenate(
            [self.gids, jnp.asarray(seg.ids.astype(np.int32))])
        self.col_ids = np.concatenate([self.col_ids, seg.ids])
        self._plan = None

    def seal(self, serials: Tuple[int, ...]) -> None:
        """Stamp the stack fingerprint and enforce the placement budget
        (LRU demotion under pressure, promotion into freed room)."""
        self.serials = serials
        self._enforce_budget()

    def _demote(self, blk: _Block) -> None:
        blk.cols_cold = np.asarray(blk.cols_hot)
        blk.cols_hot = None
        if blk.pays_hot is not None:
            blk.pays_cold = np.asarray(blk.pays_hot)
            blk.pays_hot = None
        _TIER_STATS["demotions"] += 1
        self.gen += 1
        self._plan = None

    def _promote(self, blk: _Block) -> None:
        blk.cols_hot = jnp.asarray(blk.cols_cold)
        blk.cols_cold = None
        if blk.pays_cold is not None:
            blk.pays_hot = jnp.asarray(blk.pays_cold)
            blk.pays_cold = None
        self._tick += 1
        blk.last_used = self._tick
        _TIER_STATS["promotions"] += 1
        self.gen += 1
        self._plan = None

    def _enforce_budget(self) -> None:
        if self.hot_bytes is None:
            return
        budget = int(self.hot_bytes)
        hot = lambda: [blk for blk in self.blocks if blk.tier == TIER_HOT]
        used = sum(blk.block_bytes for blk in hot())
        while used > budget:
            victims = hot()
            if not victims:
                break
            lru = min(victims, key=lambda blk: blk.last_used)
            self._demote(lru)
            used -= lru.block_bytes
        # freed room (a merge shrank R, or the budget grew): pull the
        # most recently used cold blocks back while they fit
        cold = sorted((blk for blk in self.blocks if blk.tier == TIER_COLD),
                      key=lambda blk: -blk.last_used)
        for blk in cold:
            if used + blk.block_bytes > budget:
                continue
            self._promote(blk)
            used += blk.block_bytes

    # -- plan / staging --------------------------------------------------

    def plan(self) -> Tuple[_Group, ...]:
        """Group blocks by geometry (one kernel call per group inside the
        fused program): hot columns pre-concatenated device-side, cold
        blocks listed for :meth:`stage`, base-offset lanes and their
        window plans as device constants, and the stack-position
        permutation that restores the global column order.  Roots are
        laid out in the plan's block order (:meth:`root_order`), so each
        group's lane rises in steps of 0 or 1 — ``plan_windows`` refuses
        it otherwise.  Cached until the stack or a tier changes."""
        if self._plan is not None:
            return self._plan
        order: Dict[SuffixGeometry, List[int]] = {}
        for bi, blk in enumerate(self.blocks):
            order.setdefault(blk.geom, []).append(bi)
        groups: List[_Group] = []
        root_order: List[int] = []
        root0 = 0
        for geom, idxs in order.items():
            hot = [i for i in idxs if self.blocks[i].tier == TIER_HOT]
            cold = [i for i in idxs if self.blocks[i].tier == TIER_COLD]
            perm = np.concatenate([
                self.col_off[self.blocks[i].serial]
                + np.arange(self.blocks[i].n)
                for i in hot + cold]).astype(np.int64)
            lanes = []
            for i in hot + cold:
                lanes.append(root0 + self.blocks[i].roots)
                root0 += self.blocks[i].t_root
            root_order += hot + cold
            base_idx = np.concatenate(lanes).astype(np.int32)
            windows = plan_windows(base_idx, DEFAULT_BLOCK_N)
            axis = 0 if geom.packed else -1
            cols_hot = (jnp.concatenate(
                [self.blocks[i].cols_hot for i in hot], axis=axis)
                if hot else None)
            pays_hot = None
            if self.payload_words is not None and hot:
                pays_hot = jnp.concatenate(
                    [self.blocks[i].pays_hot for i in hot], axis=-1)
            groups.append(_Group(
                geom=geom, cols_hot=cols_hot,
                base_idx=jnp.asarray(base_idx),
                windows=(jnp.asarray(windows.start),
                         jnp.asarray(windows.steps)),
                window_roots=windows.roots, perm=perm,
                cold_blocks=tuple(cold),
                cold_bytes=sum(self.blocks[i].col_bytes for i in cold),
                pays_hot=pays_hot,
                pay_cold_bytes=sum(self.blocks[i].pay_bytes
                                   for i in cold)))
        self._plan = tuple(groups)
        self._root_order = tuple(root_order)
        order = np.concatenate([g.perm for g in groups]) if groups else \
            np.zeros((0,), np.int64)
        if np.array_equal(order, np.arange(self.n_cols)):
            self._order = (None, None)
        else:
            self._order = (jnp.asarray(order.astype(np.int32)),
                           jnp.asarray(np.argsort(order).astype(np.int32)))
        return self._plan

    def root_order(self) -> Tuple[int, ...]:
        """Block indexes (stack positions) in the order the base plane
        lays out their roots: the plan's groups, each hot blocks then
        cold.  The fused program concatenates the segments' root planes
        in this order."""
        self.plan()
        return self._root_order

    def order(self) -> Tuple[Optional[jnp.ndarray], Optional[jnp.ndarray]]:
        """Device int32 permutations between the plan's group-major column
        order (groups concatenated, each in its ``perm`` order) and global
        stack order: ``(order, inverse)`` with ``order[j]`` the stack
        position of group-major column j.  ``(None, None)`` when the two
        orders coincide (one group, or groups already in stack order)."""
        self.plan()
        return self._order

    def stage(self) -> Tuple[Optional[jnp.ndarray], ...]:
        """Copy-ahead: upload every cold block's columns into one device
        staging slab per geometry group (async ``jax.device_put`` — the
        transfers overlap the traversal that runs before the verify
        consumes them).  Returns one traced-arg slab per plan group
        (None where the group is fully hot); call once per fused query,
        before the rung loop."""
        slabs: List[Optional[jnp.ndarray]] = []
        for g in self.plan():
            if not g.cold_blocks:
                slabs.append(None)
                continue
            axis = 0 if g.geom.packed else -1
            cols = np.concatenate(
                [self.blocks[i].cols_cold for i in g.cold_blocks], axis=axis)
            with _obs_span("tier_stage", cat="device",
                           blocks=len(g.cold_blocks), bytes=int(cols.nbytes)):
                slabs.append(jax.device_put(cols))
            _TIER_STATS["prefetches"] += len(g.cold_blocks)
            _TIER_STATS["staged_bytes"] += int(cols.nbytes)
        return tuple(slabs)

    def stage_payloads(self) -> Tuple[Optional[jnp.ndarray], ...]:
        """Copy-ahead for the re-rank pass: upload every cold block's
        payload bitmaps into one (Wp, n_cold) device slab per plan group
        (None where the group is fully hot, or when the store holds no
        payloads).  Same async ``jax.device_put`` discipline as
        :meth:`stage`; counted under ``staged_bytes`` plus the dedicated
        ``staged_payload_bytes`` ledger."""
        slabs: List[Optional[jnp.ndarray]] = []
        for g in self.plan():
            if self.payload_words is None or not g.cold_blocks:
                slabs.append(None)
                continue
            pays = np.concatenate(
                [self.blocks[i].pays_cold for i in g.cold_blocks], axis=-1)
            with _obs_span("tier_stage_payloads", cat="device",
                           blocks=len(g.cold_blocks), bytes=int(pays.nbytes)):
                slabs.append(jax.device_put(pays))
            _TIER_STATS["staged_bytes"] += int(pays.nbytes)
            _TIER_STATS["staged_payload_bytes"] += int(pays.nbytes)
        return tuple(slabs)

    # -- accounting ------------------------------------------------------

    def array_bytes(self) -> int:
        """Resident device bytes: hot columns + the shared gid/liveness
        lanes + the per-group base-offset lanes (the staging slab is
        transient and accounted by ``tier_stats()['staged_bytes']``)."""
        by = int(self.live.nbytes + self.gids.nbytes)
        by += sum(blk.block_bytes for blk in self.blocks
                  if blk.tier == TIER_HOT)
        by += sum(blk.roots.nbytes for blk in self.blocks)
        return by

    def host_bytes(self) -> int:
        """Resident host bytes: cold columns and cold payload bitmaps
        (the host master copies)."""
        return sum(blk.block_bytes for blk in self.blocks
                   if blk.tier == TIER_COLD)

    def col_bytes(self, tier: Optional[str] = None) -> int:
        """Sketch-column bytes, optionally restricted to one tier —
        the bytes-per-row numerator of the capacity benchmarks
        (payload bitmaps are ledgered separately, :meth:`pay_bytes`)."""
        return sum(blk.col_bytes for blk in self.blocks
                   if tier is None or blk.tier == tier)

    def pay_bytes(self, tier: Optional[str] = None) -> int:
        """Re-rank payload-bitmap bytes, optionally per tier."""
        return sum(blk.pay_bytes for blk in self.blocks
                   if tier is None or blk.tier == tier)

    def tier_summary(self) -> Dict[str, int]:
        """Per-store placement snapshot for ``SegmentedIndex.stats()``."""
        hot = [blk for blk in self.blocks if blk.tier == TIER_HOT]
        cold = [blk for blk in self.blocks if blk.tier == TIER_COLD]
        return {"hot_blocks": len(hot), "cold_blocks": len(cold),
                "hot_bytes": sum(blk.col_bytes for blk in hot),
                "cold_bytes": sum(blk.col_bytes for blk in cold)}
