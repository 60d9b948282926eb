"""Dynamic segmented bST index: streaming insert/delete with background
merge, never blocking search (DESIGN.md §4).

The paper's bST is static — ``build_trie_levels`` consumes the whole
sketch matrix up front — but the trie family supports incremental
maintenance (Kanda & Tabei's follow-up, arXiv 2009.11559).  This module
adds the LSM-style construction on top of the *unchanged* static
machinery:

  * a small mutable **delta buffer** absorbs inserts and answers queries
    by brute-force Hamming scan (the batch verify kernel's
    ``ops.hamming_distances`` — exact distances at any τ for free);
  * sealed **segments** are immutable bSTs (or MI-bST / sharded-bST
    stacks) with a per-segment **tombstone bitmap**: ``delete`` flips a
    bit, and the liveness bitmap is a *traced* argument of the cached
    compiled searcher (``get_searcher(..., with_live=True)``), so
    deletes never re-jit and dead leaves are pruned inside the verify
    stage (``ops.sparse_verify*(..., live=...)``);
  * a size-tiered ``merge()`` rebuilds two segments into one via
    ``build_trie_levels`` (dropping tombstones as it goes) and
    ``compact()`` rebuilds a single segment to reclaim tombstoned rows;
  * queries run through the **fused one-dispatch segment arena**
    (DESIGN.md §6): a device-resident column arena holds one verify
    column per sealed physical row (plus base-offset, global-id, and
    liveness lanes), and ONE jitted program per τ rung runs every
    segment's traversal, the delta scan, the arena verify kernel, and
    the on-device (distance, id) selection — serving latency is flat in
    segment count, and the only per-request transfer is the final
    (m, k) ids/dists (plus two ladder scalars per rung).  The
    per-segment fan-out survives as the reference path
    (``use_arena=False``); both are bit-identical to each other and to
    a static bST built from the surviving sketches (ties by id, and
    global ids are assigned monotonically, so the tie order matches the
    static build's insertion order).

Ids are **stable**: ``insert`` assigns monotonically increasing global
ids that survive merges and compactions.  Internally everything is
column-compressed — fan-out planes are (m, R) over the *physical* rows
currently held, labeled by global id, so churn cost tracks the live
corpus (R is reclaimed by merge/compact).  The primary range-search
contract is the column-compressed ``search_columns_batch``
(``ColumnSearchResult``); only the opt-in dense contract
(``search_batch``'s (m, n_ids) mask/dist planes) materializes the full
ever-assigned id axis, and ``topk*`` never does.

Shapes and dtypes: sketches are (n, L) uint8 over Σ=[0, 2^b); result
masks are (m, n_ids) bool, distances (m, n_ids) int32 with BIG
(= 1 << 20) on non-results; ids returned by ``insert``/``topk`` are
int64 / int32 global ids.
"""

from __future__ import annotations

import dataclasses
import functools
import itertools
import re
import threading
import time
import weakref
from typing import Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels import ops
from ..kernels.hamming_kernel import DEFAULT_BLOCK_M, WindowPlan
from ..kernels.ref import RERANK_METRICS
from .bst import BIG, build_bst
from .trie_builder import build_trie_levels
from .column_store import ColumnStore, tier_stats
from .cost_model import cost_single, frontier_capacities, tau_for_k
from .distributed_search import (build_sharded_bst, make_sharded_searcher,
                                 sharded_column_dists, topk_from_dists)
from .hamming import (n_words, pack_suffix_words_jax, pack_vertical,
                      pack_vertical_jax, unpack_vertical)
from .multi_index import (build_multi_index, mi_column_dists, mi_search_batch,
                          mi_trace_params)
from .search import (CAP_MAX_DEFAULT, LADDER_CAP_MAX, TopKResult,
                     _CACHE_STATS, _note_trace, _pad_rows, _pad_topk,
                     _pin_cache_get, _traverse_frontier_batch, bucket_m,
                     get_searcher, scatter_root_plane, searcher_cache_info,
                     select_topk_columns, select_topk_scores)
from ..obs.explain import QueryExplain, RungExplain
from ..obs.trace import span as _obs_span

BIG_I = int(BIG)

BACKENDS = ("bst", "multi", "sharded")

# Column-store layouts of the fused arena path (bst backend,
# DESIGN.md §7): "suffix" (default) stores per-segment packed suffix
# columns below each segment's ℓ_s in the tiered ``ColumnStore``;
# "full" keeps the PR-5 full-length ``_ColumnArena`` — the bit-identical
# always-hot reference.
LAYOUTS = ("suffix", "full")

# Monotonic segment serials: every sealed Segment gets the next value,
# and merged/compacted replacements get fresh ones.  Serials key every
# per-segment compiled-artifact cache (the sharded searcher pin, the
# fused arena programs) — unlike ``id()``, a serial is never reused, so
# a merged-away segment can never alias a live one's cached searcher.
_SEG_SERIALS = itertools.count()

# Host->device program launches issued by the segmented query path:
# "fanout" counts the per-segment reference path (one per segment
# searcher call, capacity-ladder retries included, plus one per
# delta-buffer scan), "fused" the single-dispatch arena path (one per
# τ-ladder rung), "rerank" the exact re-rank pass (one per
# ``topk(rerank=...)`` request, regardless of segment count —
# DESIGN.md §10).  The serving metrics snapshot exposes these —
# dispatch accounting replaces per-segment accounting (DESIGN.md §6).
# Per fused launch the windowed arena verify also tallies
# "windowed_cols" (columns verified through the in-kernel base-window
# expansion), "windows" (column blocks expanded, per query tile) and
# "window_roots" (the roots those windows span).
_DISPATCH_STATS = {"total": 0, "fused": 0, "fanout": 0, "rerank": 0,
                   "windowed_cols": 0, "windows": 0, "window_roots": 0}
# the counters are bumped from every scheduler worker thread — guard the
# read-modify-write (plain ``+=`` on a dict slot is not atomic)
_DISPATCH_LOCK = threading.Lock()


def _dispatch(kind: str) -> None:
    with _DISPATCH_LOCK:
        _DISPATCH_STATS["total"] += 1
        _DISPATCH_STATS[kind] += 1


def _tally_windows(cols: int, windows: int, roots: int) -> None:
    with _DISPATCH_LOCK:
        _DISPATCH_STATS["windowed_cols"] += cols
        _DISPATCH_STATS["windows"] += windows
        _DISPATCH_STATS["window_roots"] += roots


def dispatch_stats() -> Dict[str, int]:
    """Device-dispatch counters of the segmented query path: ``total``
    host->device program launches, split into ``fused`` (arena path —
    one per τ rung, independent of segment count), ``fanout``
    (per-segment reference path — one per segment per rung), and
    ``rerank`` (exact re-rank pass — one per ``topk(rerank=...)``
    request, never per segment).  The fused launches' windowed verify
    adds ``windowed_cols`` (columns whose base came through the
    in-kernel window expansion), ``windows`` (column blocks expanded,
    once per query tile) and ``window_roots`` (roots those windows
    span: ``window_roots / windows`` is the mean roots per window)."""
    with _DISPATCH_LOCK:
        return dict(_DISPATCH_STATS)


def reset_dispatch_stats() -> None:
    with _DISPATCH_LOCK:
        for k in _DISPATCH_STATS:
            _DISPATCH_STATS[k] = 0


def next_serial() -> int:
    """A segment serial never handed out before in this process."""
    return next(_SEG_SERIALS)


def ensure_serial_floor(floor: int) -> None:
    """Advance the global segment-serial counter to at least ``floor``.
    Recovery calls this with ``max(persisted serial) + 1`` so serials
    restored from disk can never collide with serials minted later in
    this process — the invariant every compiled-artifact cache key
    relies on (a serial is never reused)."""
    global _SEG_SERIALS
    with _DISPATCH_LOCK:
        cur = next(_SEG_SERIALS)
        _SEG_SERIALS = itertools.count(max(cur, int(floor)))


def _rows_of(id_arr: np.ndarray, ids: np.ndarray,
             sorter: Optional[np.ndarray] = None) -> np.ndarray:
    """Positions in ``id_arr`` of those ``ids`` it holds; ``sorter``
    sorts ``id_arr`` (None: it is ascending already)."""
    if id_arr.size == 0:
        return np.zeros((0,), np.int64)
    pos = np.minimum(np.searchsorted(id_arr, ids, sorter=sorter),
                     id_arr.size - 1)
    rows = pos if sorter is None else sorter[pos]
    return rows[id_arr[rows] == ids]


def _ties_by_id(order: np.ndarray, leaf: np.ndarray,
                ids: np.ndarray) -> np.ndarray:
    """Reorder each run of equal rows (one leaf) of ``order`` by id.
    Such runs are rare, so only their rows are sorted."""
    tie = np.flatnonzero(leaf[1:] == leaf[:-1])
    if tie.size == 0:
        return order
    rows = np.union1d(tie, tie + 1)
    order = order.copy()
    order[rows] = order[rows[np.lexsort((ids[order[rows]], leaf[rows]))]]
    return order


def tombstone_bits(n: int) -> int:
    """Storage cost in bits of one tombstone bitmap over ``n`` ids,
    accounted exactly like ``BitVector.nbits`` (payload words + the
    32-bit-per-word rank directory a succinct liveness bitmap carries):
    word-padded payload + cumulative-popcount table.

    >>> tombstone_bits(64)      # 2 payload words + 3 table entries
    160
    """
    n_words = max(1, (int(n) + 31) // 32)
    return n_words * 32 + (n_words + 1) * 32


@dataclasses.dataclass
class Segment:
    """One immutable sealed segment: a static index + host-side metadata.

    Attributes:
      index:    the queryable structure (``SketchIndex``, ``MultiIndex``,
                or ``ShardedBST`` depending on the stack's backend).
      packed:   (n_seg, b, W) uint32 — the sealed sketches retained
                host-side in ``pack_vertical`` bit-plane form (b bits per
                symbol instead of 8 — an 8/b× host-RAM saving,
                DESIGN.md §7); merges/compacts unpack on demand through
                :attr:`sketches`.
      ids:      (n_seg,) int64 global ids.  On the bst backend rows are
                stored in the trie's leaf order (ties by id), so a
                segment's ``leaf_root[id_leaf]`` lane rises in steps of
                0 or 1 (the windowed verify, DESIGN.md §6); the other
                backends keep the order rows were sealed in (a merge
                concatenates its inputs), so ids follow no order there.
                ``rows_of`` is the one id -> row lookup.
      live:     (n_seg,) bool tombstone bitmap (False = deleted).
      L, b:     the sketch geometry ``packed`` was packed with.
      serial:   process-monotonic id (auto-assigned); keys every cached
                compiled artifact for this segment — never reused, unlike
                ``id()``.
      payloads: optional (n_seg, Wp) uint32 — the rows' original
                token-set bitmaps (``hamming.pack_sets``), retained
                host-side for the exact re-rank plane (DESIGN.md §10);
                row order matches ``ids``.
    """

    index: object
    packed: np.ndarray
    ids: np.ndarray
    live: np.ndarray
    L: int
    b: int
    serial: int = dataclasses.field(
        default_factory=next_serial)
    payloads: Optional[np.ndarray] = None
    # id -> row lookup (argsort of ``ids``), built on the first delete
    _by_id: Optional[np.ndarray] = dataclasses.field(
        default=None, repr=False, compare=False)

    def rows_of(self, ids: np.ndarray) -> np.ndarray:
        """Rows holding the given global ids (ids this segment does not
        hold are skipped): O(k log n) through a host argsort of ``ids``,
        built once per segment."""
        if self._by_id is None:
            self._by_id = np.argsort(self.ids, kind="stable")
        return _rows_of(self.ids, ids, self._by_id)

    @property
    def sketches(self) -> np.ndarray:
        """(n_seg, L) uint8 — unpacked on demand (merge/compact rebuilds
        and the suffix column slicing are the only consumers)."""
        return unpack_vertical(self.packed, self.b, self.L)

    @property
    def n(self) -> int:
        return int(self.ids.shape[0])

    @property
    def n_live(self) -> int:
        return int(self.live.sum())


class SegmentedSearchResult(NamedTuple):
    mask: np.ndarray      # (m, n_ids) bool — live ids within τ per query
    dist: np.ndarray      # (m, n_ids) int32 — exact distance where mask, BIG off
    overflow: int         # total dropped frontier entries (0 = exact)


class ColumnSearchResult(NamedTuple):
    """Column-compressed range-search result — the primary contract
    (DESIGN.md §6): one column per *physical* row currently held (every
    segment's rows in stack order, then the delta buffer's), labeled by
    stable global id.  O(m · R) where R shrinks with merge/compact — it
    never grows with ids-ever-assigned, unlike the opt-in dense plane of
    ``search_batch``."""

    mask: np.ndarray      # (m, R) bool — live columns within τ per query
    dist: np.ndarray      # (m, R) int32 — exact distance where mask, BIG off
    ids: np.ndarray       # (R,) int64 — global id per column
    overflow: int         # total dropped frontier entries (0 = exact)


class _ColumnArena:
    """Device-resident verify state for the sealed segment stack
    (DESIGN.md §6): everything the fused one-dispatch program streams,
    maintained across queries and updated incrementally on lifecycle
    writes instead of re-uploaded per query.

    Attributes (R = total sealed physical rows, T = Σ per-segment
    ℓ_s-root counts + 1 — the last slot is the delta buffer's trivial
    base, so delta columns appended after the sealed ones keep the lane
    rising in steps of 0 or 1):
      cols:      (b, W, R) uint32 — full-length vertical verify columns,
                 segment blocks concatenated in stack order;
      base_idx:  (R,) int32 device — per-column index into the
                 concatenated root base plane (the segment-offset lane):
                 ``root_offset[s] + leaf_root[id_leaf[row]]``, rising
                 in steps of 0 or 1 (rows in trie order);
      gids:      (R,) int32 device — global id per column (selection
                 labels);
      live:      (R,) bool device — liveness lanes; ``delete`` flips
                 lanes in place (one scatter), never rebuilding;
      col_ids:   (R,) int64 host — global id per column (result labels);
      col_off:   dict serial -> first column of that segment's block;
      t_root_total: Σ per-segment root counts (the delta's slot);
      serials:   the segment-stack fingerprint this arena matches.
    """

    def __init__(self):
        self.serials: Tuple[int, ...] = ()
        self.cols: Optional[jnp.ndarray] = None
        self.base_idx: Optional[jnp.ndarray] = None
        self.gids: Optional[jnp.ndarray] = None
        self.live: Optional[jnp.ndarray] = None
        self.col_ids = np.zeros((0,), np.int64)
        self.col_off: Dict[int, int] = {}
        self.t_root_total = 0
        self._idx_host = np.zeros((0,), np.int32)
        self._windows: Dict[int, WindowPlan] = {}

    def windows(self, ndb: int) -> WindowPlan:
        """Window plan of the verify lane — sealed columns, then ``ndb``
        delta columns at the delta's slot — memoized per delta bucket."""
        plan = self._windows.get(ndb)
        if plan is None:
            plan = self._windows[ndb] = ops.plan_windows(np.concatenate(
                [self._idx_host,
                 np.full(ndb, self.t_root_total, np.int32)]))
        return plan

    @property
    def n_cols(self) -> int:
        """Columns currently held (the shared maintenance surface with
        ``column_store.ColumnStore``)."""
        return int(self.col_ids.shape[0])

    def array_bytes(self) -> int:
        """Device bytes held by the arena (space accounting, §6)."""
        if self.cols is None:
            return 0
        return int(self.cols.nbytes + self.base_idx.nbytes
                   + self.gids.nbytes + self.live.nbytes)

    def host_bytes(self) -> int:
        """The full-length arena keeps no host master copies (it is the
        always-hot reference layout)."""
        return 0

    def col_bytes(self, tier: Optional[str] = None) -> int:
        """Column payload bytes (all device-resident — the full-length
        baseline of the bytes-per-row benchmarks)."""
        if self.cols is None or tier == "cold":
            return 0
        return int(self.cols.nbytes)

    def tier_summary(self) -> Dict[str, int]:
        n_blocks = len(self.col_off)
        return {"hot_blocks": n_blocks, "cold_blocks": 0,
                "hot_bytes": self.col_bytes(), "cold_bytes": 0}


# make_sharded_searcher has no process-level cache of its own (the static
# pipeline jits once per program); segment stacks re-enter it per search,
# so pin compiled sharded searchers here with the same discipline as
# search._SEARCHER_CACHE.
_SHARDED_SEARCHER_CACHE: Dict[tuple, tuple] = {}
_SHARDED_SEARCHER_CACHE_CAP = 64

# Fused one-dispatch arena programs, keyed on (index instance,
# segment-serial fingerprint, kind, τ, capacity rung, k, block_m) —
# serials are monotonic, so a rebuilt stack can never alias a stale
# program; each entry pins the segment indexes and arena arrays it
# streams, and an index drops its own dead-generation entries the moment
# its fingerprint changes (``_fused_fn``).
_FUSED_CACHE: Dict[tuple, object] = {}
_FUSED_CACHE_CAP = 32


def clear_fused_cache() -> None:
    """Drop every compiled fused arena program (and its pinned arrays)."""
    _FUSED_CACHE.clear()


class _BoundProgram:
    """A jitted fused program plus the device-resident corpus it reads
    (trie levels, hot columns, lanes, permutations), passed as the
    program's first argument on every call.  A device array the traced
    function closes over becomes an HLO constant — a copy of the corpus
    inside every compiled program, paid in compile time and device
    memory; an argument is only a buffer reference."""

    def __init__(self, fn, corpus):
        self.jit = jax.jit(fn)
        self.corpus = corpus

    def __call__(self, *args):
        return self.jit(self.corpus, *args)

    def lower(self, *args):
        return self.jit.lower(self.corpus, *args)


# Named device stages of the fused rung program (``jax.named_scope`` in
# ``_build_fused_bst[_suffix]``).  A TPU trace names each device op by
# its HLO instruction alone, so while a span is attached ``_fused_call``
# records, per compiled program variant, which scope each instruction of
# the optimized module came from (``hlo_scopes``) and labels the
# ``rung_dispatch`` span with the variant (``args["program"]``): a trace
# op inside that span resolves through ``fused_scope_tables()[label]``.
# JAX's persistent compile cache keys a program without its debug info,
# so an executable it returns keeps the metadata of whichever program
# compiled it first: a table of a program compiled without scopes (an
# older checkout sharing the cache) holds none.
RUNG_SCOPES = ("rung.traverse", "rung.verify", "rung.delta_scan",
               "rung.select")
_SCOPE_LABELS: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()
_SCOPE_TABLES: Dict[str, Dict[str, str]] = {}
_SCOPE_SERIALS = itertools.count()
_SCOPE_LOCK = threading.Lock()
_HLO_COMPUTATION = re.compile(r"^(?:ENTRY )?%([^\s(]+) ")
_HLO_INSTRUCTION = re.compile(r"^\s+(ROOT )?%([^\s=]+) = (.*)$")
_HLO_SCOPE = re.compile(r'op_name="[^"]*?(rung\.[a-z_]+)')
_HLO_CALLS = re.compile(r"calls=%([^\s,]+)")
_HLO_REF = re.compile(r"%([^\s,)}]+)")


def hlo_scopes(hlo_text: str) -> Dict[str, str]:
    """``{instruction: scope}`` for the instructions of an optimized HLO
    module's text that belong to a ``rung.*`` scope.  An instruction's
    own ``op_name`` metadata decides; one without metadata (a fusion may
    carry none) takes the scope of the computation it calls (its root's,
    else the one scope inside it); one the compiler made from nothing
    (the reduce-windows of a scan, a pad) takes the one scope of its
    users, else of its operands."""
    body: Dict[str, List[str]] = {}
    refs: Dict[str, List[str]] = {}
    own: Dict[str, str] = {}
    calls: Dict[str, str] = {}
    roots: Dict[str, str] = {}
    comp = None
    for line in hlo_text.splitlines():
        m = _HLO_COMPUTATION.match(line)
        if m:
            comp = m.group(1)
            body[comp] = []
            continue
        m = _HLO_INSTRUCTION.match(line)
        if m is None or comp is None:
            continue
        root, name, rest = m.groups()
        if " parameter(" in rest:
            continue
        body[comp].append(name)
        refs[name] = _HLO_REF.findall(rest.split(", metadata=", 1)[0])
        hit = _HLO_SCOPE.search(rest)
        if hit:
            own[name] = hit.group(1)
        callee = _HLO_CALLS.search(rest)
        if callee:
            calls[name] = callee.group(1)
        if root:
            roots[comp] = name
    scope = dict(own)
    for name, callee in calls.items():
        inner = {own[i] for i in body.get(callee, ()) if i in own}
        if name not in scope and (roots.get(callee) in own
                                  or len(inner) == 1):
            scope[name] = own.get(roots.get(callee)) or inner.pop()
    users: Dict[str, List[str]] = {name: [] for name in refs}
    for name, rs in refs.items():
        for r in rs:
            if r in users:
                users[r].append(name)
    changed = True
    while changed:
        changed = False
        for name in refs:
            if name in scope:
                continue
            for near in (users[name], refs[name]):
                found = {scope[n] for n in near if n in scope}
                if len(found) == 1:
                    scope[name] = found.pop()
                    changed = True
                    break
    return scope


def _scope_label(fn, args: tuple) -> str:
    """The label of the compiled variant of ``fn`` that ``args`` ran,
    recording its scope table on first sight (a lowering of an already
    compiled signature: a re-trace, no backend compile)."""
    leaves, tree = jax.tree_util.tree_flatten(args)
    sig = (tree, tuple((a.shape, str(a.dtype)) for a in leaves))
    with _SCOPE_LOCK:
        labels = _SCOPE_LABELS.setdefault(fn, {})
        label = labels.get(sig)
    if label is None:
        table = hlo_scopes(fn.lower(*args).compile().as_text())
        label = f"fused.{next(_SCOPE_SERIALS)}"
        with _SCOPE_LOCK:
            _SCOPE_TABLES[label] = table
            labels[sig] = label
    return label


def fused_scope_tables() -> Dict[str, Dict[str, str]]:
    """Every scope table recorded so far: ``{label: {instruction:
    scope}}``, the label being a traced ``rung_dispatch`` span's
    ``args["program"]``."""
    with _SCOPE_LOCK:
        return dict(_SCOPE_TABLES)


def _take_columns(plane: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    """plane[:, idx], one 1-D gather per row (``lax.map``): a single
    column gather lays its slices out row-minor and pads the short row
    axis to 128 lanes on TPU."""
    return jax.lax.map(lambda row: row[idx], plane)


def _ladder_topk(columns_fn, n_live: int, b: int, L: int, qs: np.ndarray,
                 k: int, tau0: Optional[int]) -> TopKResult:
    """The shared kNN ladder over column-compressed fan-out results.

    ``columns_fn(qs, tau)`` -> ((m, R) int32 distances over the physical
    columns — BIG on non-results, (R,) int64 global id per column,
    overflow).  Escalates τ (seeded by ``cost_model.tau_for_k`` over the
    live count) until every query has ≥ min(k, n_live) survivors, then
    runs the shard-merge selection with the global-id labels.  Working
    memory is O(m·R) where R is the *physical* row count (reclaimed by
    merge/compact), not the ever-assigned global id space."""
    m = qs.shape[0]
    if n_live == 0:
        return TopKResult(ids=jnp.full((m, k), -1, jnp.int32),
                          dists=jnp.full((m, k), BIG_I, jnp.int32),
                          tau=0, overflow=0)
    kk = min(int(k), n_live)
    tau = tau0 if tau0 is not None else tau_for_k(b, L, n_live, kk)
    tau = min(max(int(tau), 0), L)
    while True:
        dist, col_ids, overflow = columns_fn(qs, tau)
        if int((dist < BIG_I).sum(axis=1).min()) >= kk or tau >= L:
            break
        tau = min(L, max(tau + 1, 2 * tau))
    with _obs_span("topk_readback", cat="device", k=int(k)):
        ids, dists = topk_from_dists(dist, int(k), ids=col_ids)
    return TopKResult(ids=jnp.asarray(ids), dists=jnp.asarray(dists),
                      tau=tau, overflow=overflow)


class _PayloadArena:
    """Device-resident payload plane for the non-suffix configurations
    (bst ``layout="full"``, multi, sharded): one (Wp, R) uint32 bitmap
    column per sealed physical row, stack order, maintained with the
    arena's incremental discipline — a flush appends one block, a
    merge/compact rebuilds.  (The suffix layout keeps payloads inside
    the tiered ``ColumnStore`` blocks instead, DESIGN.md §10.)"""

    def __init__(self, pay_words: int):
        self.pay_words = int(pay_words)
        self.serials: Tuple[int, ...] = ()
        self.pays: jnp.ndarray = jnp.zeros((self.pay_words, 0), jnp.uint32)

    def refresh(self, segments: List[Segment],
                serials: Tuple[int, ...]) -> "jnp.ndarray":
        if self.serials == serials:
            return self.pays
        if not (len(serials) > len(self.serials)
                and serials[:len(self.serials)] == self.serials):
            self.pays = jnp.zeros((self.pay_words, 0), jnp.uint32)
            self.serials = ()
        new_segs = segments[len(self.serials):]
        if new_segs:
            blocks = [np.ascontiguousarray(
                seg.payloads.T.astype(np.uint32)) for seg in new_segs]
            self.pays = jnp.concatenate(
                [self.pays, jnp.asarray(np.concatenate(blocks, axis=-1))],
                axis=-1)
        self.serials = serials
        return self.pays

    def array_bytes(self) -> int:
        return int(self.pays.nbytes)


@functools.partial(jax.jit,
                   static_argnames=("metric", "kk", "block_m"))
def _rerank_select(dist, pay_vert, q_pay, col_ids, *, metric: str, kk: int,
                   block_m: int):
    """One-launch exact re-rank + selection for the host-assembled
    (reference / sharded) paths: survivors of the final-τ dist plane are
    scored by ``ops.exact_rerank`` and selected by
    ``search.select_topk_scores`` — the same kernel and sort the fused
    arena's re-rank program runs, so every path is bit-identical."""
    _note_trace()
    surv = (dist < BIG).astype(jnp.int32)
    scores = ops.exact_rerank(pay_vert, q_pay, surv, metric=metric,
                              block_m=block_m)
    return select_topk_scores(scores, dist, col_ids, kk)


def _pad_topk_scores(ids: np.ndarray, dists: np.ndarray,
                     scores: np.ndarray, k: int):
    """Pad re-ranked (m, kk) planes out to (m, k): (-1, BIG, -1.0)."""
    kk = ids.shape[-1]
    if kk == k:
        return ids, dists, scores
    pad = [(0, 0)] * (ids.ndim - 1) + [(0, k - kk)]
    return (np.pad(ids, pad, constant_values=-1),
            np.pad(dists, pad, constant_values=BIG_I),
            np.pad(scores, pad, constant_values=np.float32(-1.0)))


def _empty_topk_rerank(m: int, k: int) -> TopKResult:
    return TopKResult(ids=jnp.full((m, k), -1, jnp.int32),
                      dists=jnp.full((m, k), BIG_I, jnp.int32),
                      tau=0, overflow=0,
                      scores=jnp.full((m, k), -1.0, jnp.float32))


def _ladder_topk_rerank(columns_fn, payload_rows_fn, n_live: int, b: int,
                        L: int, block_m: int, qs: np.ndarray, k: int,
                        tau0: Optional[int], metric: str,
                        q_pay: np.ndarray) -> TopKResult:
    """The shared reference two-stage ladder (the fan-out analogue of
    ``_ladder_topk``): escalate τ until every query has ≥ min(k, n_live)
    survivors, then ONE ``_rerank_select`` launch scores the final
    survivor plane against ``payload_rows_fn()``'s (R, Wp) host rows and
    selects the k best (score desc, id asc)."""
    m = qs.shape[0]
    if n_live == 0:
        return _empty_topk_rerank(m, int(k))
    kk = min(int(k), n_live)
    tau = tau0 if tau0 is not None else tau_for_k(b, L, n_live, kk)
    tau = min(max(int(tau), 0), L)
    while True:
        dist, col_ids, overflow = columns_fn(qs, tau)
        if int((dist < BIG_I).sum(axis=1).min()) >= kk or tau >= L:
            break
        tau = min(L, max(tau + 1, 2 * tau))
    pay_vert = jnp.asarray(np.ascontiguousarray(payload_rows_fn().T))
    _dispatch("rerank")
    with _obs_span("rerank", cat="device", metric=metric, kk=kk):
        ids, dists, scores = _rerank_select(
            jnp.asarray(dist), pay_vert,
            jnp.asarray(np.ascontiguousarray(q_pay.T)),
            jnp.asarray(col_ids.astype(np.int32)),
            metric=metric, kk=kk, block_m=block_m)
        ids, dists, scores = (np.asarray(ids), np.asarray(dists),
                              np.asarray(scores))
    ids, dists, scores = _pad_topk_scores(ids, dists, scores, int(k))
    return TopKResult(ids=jnp.asarray(ids), dists=jnp.asarray(dists),
                      tau=tau, overflow=int(overflow),
                      scores=jnp.asarray(scores))


class _ExplainRecorder:
    """Explain-mode bookkeeping (DESIGN.md §11): wraps a ``columns_fn``
    so every τ-ladder rung it serves is recorded as a ``RungExplain``
    (survivor/pruned counts off the rung's own distance plane, device-
    launch deltas, wall-clock), and snapshots the process-level cache /
    dispatch / tier counters at construction so ``finish`` can report
    the request's deltas.  The wrapped fn returns the *identical*
    planes — explain-on results are bit-identical to explain-off by
    construction (held by ``tests/test_obs.py``).

    Per-rung counter deltas read the process-global ledgers, so explain
    is a single-request diagnostic: concurrent queries on other threads
    would bleed into the deltas (the counts derived from the distance
    planes themselves are always exact)."""

    def __init__(self, frontier_index=None):
        self.t0 = time.perf_counter()
        self.cache0 = searcher_cache_info()
        self.disp0 = dispatch_stats()
        self.tier0 = tier_stats()
        self.rungs: List[RungExplain] = []
        self._frontier_index = frontier_index

    def wrap(self, columns_fn):
        def fn(qs, tau):
            t0 = time.perf_counter()
            d0 = dispatch_stats()
            dist, col_ids, overflow = columns_fn(qs, tau)
            d1 = dispatch_stats()
            dt = (time.perf_counter() - t0) * 1e3
            dist_np = np.asarray(dist)
            surv = (dist_np < BIG_I).sum(axis=1)
            frontier = None
            if self._frontier_index is not None:
                frontier = self._frontier_index._frontier_widths(qs, tau)
            self.rungs.append(RungExplain(
                tau=int(tau), candidates=int(dist_np.shape[1]),
                survivors=[int(s) for s in surv],
                pruned=[int(dist_np.shape[1] - s) for s in surv],
                overflow=int(overflow),
                dispatches={k: d1[k] - d0[k] for k in d1},
                duration_ms=dt, frontier=frontier))
            return dist_np, col_ids, overflow
        return fn

    def finish(self, *, op: str, backend: str, n_queries: int,
               n_live: int, k: Optional[int], tau0: Optional[int],
               tau_final: int, rerank: Optional[str]) -> QueryExplain:
        cache1 = searcher_cache_info()
        disp1 = dispatch_stats()
        tier1 = tier_stats()
        rerank_surv = None
        if rerank is not None and self.rungs:
            rerank_surv = list(self.rungs[-1].survivors)
        return QueryExplain(
            op=op, backend=backend, n_queries=int(n_queries),
            n_live=int(n_live), k=k, tau0=tau0, tau_final=int(tau_final),
            rungs=self.rungs, rerank=rerank,
            rerank_survivors=rerank_surv,
            cache={key: cache1[key] - self.cache0[key]
                   for key in ("hits", "misses", "traces")},
            dispatch={key: disp1[key] - self.disp0[key] for key in disp1},
            tier={key: tier1[key] - self.tier0[key] for key in tier1},
            duration_ms=(time.perf_counter() - self.t0) * 1e3)


class SegmentedIndex:
    """A dynamic, incrementally maintained index over b-bit sketches.

    Parameters:
      L, b:       sketch length / bits per character (Σ = [0, 2^b)).
      delta_cap:  delta-buffer rows that trigger an automatic ``flush``.
      backend:    "bst" (default) — each segment is one bST;
                  "multi"   — each segment is an MI-bST (``mi_blocks``);
                  "sharded" — each segment is a padded S-shard ShardedBST
                  searched through ``make_sharded_searcher`` (each shard
                  of the SPMD program serves its slice of the segment).
      mi_blocks:  block count for backend="multi".
      n_shards:   shard count for backend="sharded" (clamped to the
                  segment size).
      lam:        the paper's λ collapse parameter, forwarded to builds.
      auto_merge: run the size-tiered merge policy after every automatic
                  flush (manual ``flush()`` never merges implicitly).
      block_m:    query-tile size forwarded to the batched verify kernel.
      use_arena:  serve queries through the fused one-dispatch segment
                  arena (DESIGN.md §6) — one device launch per τ-ladder
                  rung regardless of segment count, bit-identical to the
                  per-segment reference fan-out (False restores it).
      layout:     column layout of the arena path (bst backend,
                  DESIGN.md §7): "suffix" (default) stores packed
                  per-segment suffix columns below each segment's ℓ_s in
                  the tiered ``ColumnStore``; "full" keeps the
                  full-length ``_ColumnArena`` — the bit-identical
                  always-hot reference.
      hot_bytes:  device budget (bytes) for hot suffix-column blocks;
                  cold blocks stay host-packed and are staged per query
                  (LRU demotion under pressure).  None = unlimited
                  (everything hot — the PR-5 placement).
      payload_words: uint32 words per row payload bitmap
                  (``ceil(vocab / 32)``, see ``hamming.pack_sets``).
                  When set, every ``insert`` must supply matching
                  ``payloads`` and ``topk*(rerank=metric)`` runs the
                  exact re-rank plane (DESIGN.md §10); None (default)
                  disables payload storage and re-ranking.

    >>> import numpy as np
    >>> idx = SegmentedIndex(L=8, b=2, delta_cap=4)
    >>> ids = idx.insert(np.zeros((5, 8), np.uint8))   # auto-flush at 4
    >>> (len(ids), idx.n_live, len(idx.segments))
    (5, 5, 1)
    >>> int(idx.delete(ids[:2]))
    2
    >>> idx.n_live
    3
    """

    def __init__(self, L: int, b: int, *, delta_cap: int = 4096,
                 backend: str = "bst", mi_blocks: int = 2, n_shards: int = 4,
                 lam: float = 0.5, auto_merge: bool = True,
                 block_m: int = DEFAULT_BLOCK_M, use_arena: bool = True,
                 layout: str = "suffix",
                 hot_bytes: Optional[int] = None,
                 payload_words: Optional[int] = None):
        if backend not in BACKENDS:
            raise ValueError(f"backend must be one of {BACKENDS}")
        if layout not in LAYOUTS:
            raise ValueError(f"layout must be one of {LAYOUTS}")
        self.L = int(L)
        self.b = int(b)
        self.delta_cap = int(delta_cap)
        self.backend = backend
        self.mi_blocks = int(mi_blocks)
        self.n_shards = int(n_shards)
        self.lam = float(lam)
        self.auto_merge = bool(auto_merge)
        self.block_m = int(block_m)
        self.use_arena = bool(use_arena)
        self.layout = layout
        self.hot_bytes = hot_bytes
        self.payload_words = (None if payload_words is None
                              else int(payload_words))

        self.segments: List[Segment] = []
        self.n_ids = 0                      # global ids ever assigned
        self._delta_sk = np.zeros((0, self.L), np.uint8)
        self._delta_ids = np.zeros((0,), np.int64)
        self._delta_live = np.zeros((0,), bool)
        self._delta_vert: Optional[jnp.ndarray] = None  # cached (b, W, ndb)
        # re-rank payloads (DESIGN.md §10): host delta rows + cached
        # device plane, plus the sealed payload arena of the non-suffix
        # configurations (suffix keeps payloads in the ColumnStore)
        self._delta_pay = (np.zeros((0, self.payload_words), np.uint32)
                           if self.payload_words is not None else None)
        self._delta_pay_vert: Optional[jnp.ndarray] = None  # (Wp, ndb)
        self._pay_arena: Optional[_PayloadArena] = None
        # bst backend only: the tiered suffix ColumnStore (layout
        # "suffix") or the full-length _ColumnArena reference ("full") —
        # both expose the same maintenance surface (serials / live /
        # col_off / col_ids / array_bytes)
        self._arena: Optional[object] = None
        self._fused_id = next_serial()                  # per-index cache scope
        self._fused_stamp: Tuple = ()                   # (serials, gen)
        self.counters = {"flushes": 0, "merges": 0, "compactions": 0,
                         "inserted": 0, "deleted": 0}
        # write hook: fn(event: str, info: dict) fired after every
        # lifecycle write ("insert" / "delete" / "flush" / "merge" /
        # "compact") — the serving layer's metrics tap (DESIGN.md §5).
        # Exceptions are the caller's problem; keep hooks cheap.
        self.event_hook: Optional[object] = None
        # durability binding (repro.store.StackBinding): log-before-apply
        # for insert/delete, checkpoint after flush/merge/compact.  None
        # (default) = ephemeral index, zero overhead.
        self.store: Optional[object] = None

    # -- mutation --------------------------------------------------------

    def _emit(self, event: str, **info) -> None:
        if self.event_hook is not None:
            self.event_hook(event, info)

    def _check_payloads(self, payloads, k: int) -> Optional[np.ndarray]:
        """Validate insert-time payloads against ``payload_words``."""
        if self.payload_words is None:
            if payloads is not None:
                raise ValueError(
                    "payloads supplied but the index was built without "
                    "payload_words")
            return None
        if payloads is None:
            raise ValueError(
                "payload_words is set: insert requires (k, "
                f"{self.payload_words}) uint32 payload bitmaps")
        pay = np.asarray(payloads, dtype=np.uint32)
        if pay.ndim == 1:
            pay = pay[None, :]
        if pay.shape != (k, self.payload_words):
            raise ValueError(f"payloads shape {pay.shape} != "
                             f"({k}, {self.payload_words})")
        return pay

    def insert(self, sketches: np.ndarray,
               payloads: Optional[np.ndarray] = None) -> np.ndarray:
        """Append sketches to the delta buffer; returns their (k,) int64
        global ids.  ``sketches``: (k, L) or (L,) uint8 over [0, 2^b).
        When the index was built with ``payload_words``, ``payloads``
        must carry the rows' (k, Wp) uint32 set bitmaps
        (``hamming.pack_sets``) — the exact re-rank plane's source of
        truth.  Triggers ``flush`` (and, if ``auto_merge``, the
        size-tiered merge policy) once the delta buffer reaches
        ``delta_cap`` rows — search stays available throughout."""
        sk = np.asarray(sketches, dtype=np.uint8)
        if sk.ndim == 1:
            sk = sk[None, :]
        if sk.shape[1] != self.L:
            raise ValueError(f"sketch length {sk.shape[1]} != L={self.L}")
        if sk.size and int(sk.max()) >= (1 << self.b):
            raise ValueError("character exceeds alphabet [0, 2^b)")
        k = sk.shape[0]
        pay = self._check_payloads(payloads, k)
        new_ids = np.arange(self.n_ids, self.n_ids + k, dtype=np.int64)
        if self.store is not None:
            # write-ahead: log, then apply
            self.store.log_insert(new_ids, sk, payloads=pay)
        self.n_ids += k
        self._delta_sk = np.concatenate([self._delta_sk, sk])
        self._delta_ids = np.concatenate([self._delta_ids, new_ids])
        self._delta_live = np.concatenate(
            [self._delta_live, np.ones(k, bool)])
        self._delta_vert = None
        if pay is not None:
            self._delta_pay = np.concatenate([self._delta_pay, pay])
            self._delta_pay_vert = None
        self.counters["inserted"] += k
        self._emit("insert", rows=k)
        if len(self._delta_ids) >= self.delta_cap:
            self.flush()
            if self.auto_merge:
                self.maybe_merge()
        return new_ids

    def delete(self, ids) -> int:
        """Tombstone global ids (scalar or (k,) array-like); returns the
        number of ids newly deleted (already-dead or unknown ids are
        ignored).  O(k log n) per container — a binary search of the
        delta's ascending ids, each segment's id -> row lookup — no
        index is rebuilt and compiled searchers stay valid (liveness is
        traced).  The arena's device liveness lanes are flipped in place
        with one scatter (DESIGN.md §6) — deletes never re-upload
        columns."""
        ids = np.unique(np.atleast_1d(np.asarray(ids, dtype=np.int64)))
        if self.store is not None and ids.size:
            self.store.log_delete(ids)           # write-ahead: log, then apply
        newly = 0
        arena = self._arena
        lanes: List[np.ndarray] = []     # arena columns going dead
        hits = [(_rows_of(self._delta_ids, ids), self._delta_live, None)]
        hits += [
            (seg.rows_of(ids), seg.live,
             arena.col_off.get(seg.serial) if arena is not None else None)
            for seg in self.segments]
        for sel, live_arr, col0 in hits:
            newly += int(live_arr[sel].sum())
            live_arr[sel] = False
            if col0 is not None and sel.size:
                lanes.append(col0 + sel)
        if lanes:
            arena.live = arena.live.at[np.concatenate(lanes)].set(False)
        self.counters["deleted"] += newly
        self._emit("delete", rows=newly)
        return newly

    def flush(self) -> Optional[Segment]:
        """Seal the delta buffer's live rows into a new immutable segment
        (dead delta rows are dropped for free).  Returns the new Segment,
        or None when nothing was live."""
        live = self._delta_live
        seg = None
        if live.any():
            with _obs_span("seal", cat="ingest", rows=int(live.sum())):
                pay = (self._delta_pay[live]
                       if self._delta_pay is not None else None)
                seg = self._seal(self._delta_sk[live], self._delta_ids[live],
                                 pay)
            self.segments.append(seg)
            self.counters["flushes"] += 1
            self._emit("flush", rows=seg.n)
        self._delta_sk = np.zeros((0, self.L), np.uint8)
        self._delta_ids = np.zeros((0,), np.int64)
        self._delta_live = np.zeros((0,), bool)
        self._delta_vert = None
        if self._delta_pay is not None:
            self._delta_pay = np.zeros((0, self.payload_words), np.uint32)
            self._delta_pay_vert = None
        if self.store is not None:
            self.store.checkpoint(self)
        return seg

    def merge(self, i: Optional[int] = None,
              j: Optional[int] = None) -> bool:
        """Rebuild two segments into one via ``build_trie_levels`` (inside
        the backend's builder), dropping tombstoned rows as it goes.
        Defaults to the two smallest segments (size-tiered choice);
        returns False when fewer than two segments exist."""
        if len(self.segments) < 2:
            return False
        if i is None or j is None:
            order = np.argsort([seg.n for seg in self.segments],
                               kind="stable")
            i, j = int(order[0]), int(order[1])
        if i == j:
            raise ValueError("cannot merge a segment with itself")
        a, b_ = self.segments[i], self.segments[j]
        with _obs_span("merge", cat="ingest",
                       rows=int(a.n_live + b_.n_live)):
            sk = np.concatenate([a.sketches[a.live], b_.sketches[b_.live]])
            ids = np.concatenate([a.ids[a.live], b_.ids[b_.live]])
            pay = None
            if self.payload_words is not None:
                pay = np.concatenate([a.payloads[a.live],
                                      b_.payloads[b_.live]])
            lo, hi = min(i, j), max(i, j)
            del self.segments[hi], self.segments[lo]
            if len(ids):
                self.segments.insert(lo, self._seal(sk, ids, pay))
        self.counters["merges"] += 1
        self._emit("merge", rows=int(len(ids)))
        if self.store is not None:
            self.store.checkpoint(self)
        return True

    def maybe_merge(self) -> int:
        """Size-tiered merge policy: while two segments share a size tier
        (⌊log2 n⌋ bucket), merge the two smallest of that tier.  Returns
        the number of merges performed.  Amortized O(log n) rebuilds per
        inserted row, and search never blocks (the old segments answer
        queries until the swap)."""
        merges = 0
        while True:
            tiers: Dict[int, List[int]] = {}
            for si, seg in enumerate(self.segments):
                tiers.setdefault(max(seg.n, 1).bit_length(), []).append(si)
            crowded = [idxs for idxs in tiers.values() if len(idxs) >= 2]
            if not crowded:
                return merges
            idxs = min(crowded, key=lambda g: min(self.segments[s].n
                                                  for s in g))
            pair = sorted(idxs, key=lambda s: self.segments[s].n)[:2]
            self.merge(pair[0], pair[1])
            merges += 1

    def compact(self, i: Optional[int] = None,
                min_dead_frac: float = 0.0) -> int:
        """Rebuild segment ``i`` (or every segment when None) without its
        tombstoned leaves; fully-dead segments are removed outright.
        ``min_dead_frac`` skips segments whose dead fraction is at or
        below the threshold.  Returns the number of segments rebuilt or
        removed."""
        targets = range(len(self.segments)) if i is None else [i]
        out: List[Optional[Segment]] = list(self.segments)
        done = 0
        for si in targets:
            seg = self.segments[si]
            dead = seg.n - seg.n_live
            if dead == 0 or (seg.n and dead / seg.n <= min_dead_frac):
                continue
            if seg.n_live == 0:
                out[si] = None
            else:
                pay = (seg.payloads[seg.live]
                       if seg.payloads is not None else None)
                out[si] = self._seal(seg.sketches[seg.live],
                                     seg.ids[seg.live], pay)
            done += 1
        self.segments = [s for s in out if s is not None]
        self.counters["compactions"] += done
        if done:
            self._emit("compact", segments=done)
            if self.store is not None:
                self.store.checkpoint(self)
        return done

    # -- queries ---------------------------------------------------------

    def search_columns_batch(self, qs: np.ndarray, tau: int,
                             explain: bool = False) -> ColumnSearchResult:
        """Range search, column-compressed — the **primary** result
        contract (DESIGN.md §6): ``qs`` (m, L) uint8 ->
        ``ColumnSearchResult`` with (m, R) mask/dist planes over the
        physical columns plus the (R,) global-id labels.  O(m · R)
        where R = rows currently held (reclaimed by merge/compact) —
        long-lived collections never pay O(ids-ever-assigned) per query;
        the dense global-id plane is the opt-in ``search_batch``.  One
        device dispatch end to end on the arena path.

        ``explain=True`` returns ``(ColumnSearchResult, QueryExplain)``
        — identical planes plus the per-rung pruning record
        (DESIGN.md §11)."""
        qs = np.asarray(qs, dtype=np.uint8)
        if qs.ndim == 1:
            qs = qs[None, :]
        if explain:
            rec = self._explain_recorder()
            dist, col_ids, overflow = rec.wrap(self._columns)(qs, int(tau))
            res = ColumnSearchResult(mask=dist <= tau, dist=dist,
                                     ids=col_ids, overflow=overflow)
            return res, rec.finish(
                op="search", backend=self.backend,
                n_queries=qs.shape[0], n_live=self.n_live, k=None,
                tau0=int(tau), tau_final=int(tau), rerank=None)
        dist, col_ids, overflow = self._columns(qs, int(tau))
        return ColumnSearchResult(mask=dist <= tau, dist=dist, ids=col_ids,
                                  overflow=overflow)

    def search_columns(self, q: np.ndarray, tau: int) -> ColumnSearchResult:
        """Single-query ``search_columns_batch`` (m=1 planes squeezed)."""
        res = self.search_columns_batch(np.asarray(q)[None], tau)
        return ColumnSearchResult(mask=res.mask[0], dist=res.dist[0],
                                  ids=res.ids, overflow=res.overflow)

    def search_batch(self, qs: np.ndarray, tau: int,
                     explain: bool = False) -> SegmentedSearchResult:
        """Range search on the **opt-in dense** contract: ``qs``: (m, L)
        uint8 queries -> (m, n_ids) global mask and exact-distance
        planes (BIG off-mask / on dead ids).  The scatter materializes
        the full ever-assigned id axis — O(m · n_ids) host memory; use
        ``search_columns_batch`` (the primary contract) when the corpus
        is long-lived and churny.

        ``explain=True`` returns ``(SegmentedSearchResult,
        QueryExplain)`` — identical planes plus the pruning record."""
        qs = np.asarray(qs, dtype=np.uint8)
        if qs.ndim == 1:
            qs = qs[None, :]
        if explain:
            rec = self._explain_recorder()
            plane, overflow = self._search_planes(
                qs, int(tau), columns_fn=rec.wrap(self._columns))
            res = SegmentedSearchResult(mask=plane <= tau, dist=plane,
                                        overflow=overflow)
            return res, rec.finish(
                op="search", backend=self.backend,
                n_queries=qs.shape[0], n_live=self.n_live, k=None,
                tau0=int(tau), tau_final=int(tau), rerank=None)
        plane, overflow = self._search_planes(qs, int(tau))
        return SegmentedSearchResult(mask=plane <= tau, dist=plane,
                                     overflow=overflow)

    def search(self, q: np.ndarray, tau: int,
               explain: bool = False) -> SegmentedSearchResult:
        """Single-query ``search_batch`` (m=1 planes squeezed);
        ``explain=True`` appends the ``QueryExplain`` record."""
        out = self.search_batch(np.asarray(q)[None], tau, explain=explain)
        res, ex = out if explain else (out, None)
        res = SegmentedSearchResult(mask=res.mask[0], dist=res.dist[0],
                                    overflow=res.overflow)
        return (res, ex) if explain else res

    def topk_batch(self, qs: np.ndarray, k: int,
                   tau0: Optional[int] = None, *,
                   rerank: Optional[str] = None,
                   q_payloads: Optional[np.ndarray] = None,
                   explain: bool = False) -> TopKResult:
        """Exact k-nearest-neighbors over the live ids: the fused
        one-dispatch arena program on a shared τ-escalation ladder —
        traversal, delta scan, verify, and (distance, id) selection all
        on device, so each rung costs one launch and transfers two
        scalars; the final (m, k) ids/dists are the only per-request
        result transfer (DESIGN.md §6).  ``qs``: (m, L) uint8 -> (m, k)
        int32 global ids / int32 exact distances, ascending by
        (distance, id); (-1, BIG) pads past the live count.
        Bit-identical to ``core.search.topk_batch`` on a static bST of
        the surviving sketches (after the monotone global-id mapping)
        and to the per-segment reference fan-out (``use_arena=False``).
        Works over column-compressed planes — O(m · physical rows), not
        O(m · ids-ever-assigned).

        ``rerank`` ("jaccard" / "cosine" / "containment") switches on
        the two-stage contract (DESIGN.md §10): the final-τ survivor
        plane stays on device and ONE additional fused dispatch gathers
        the survivors' payload bitmaps, scores them exactly against
        ``q_payloads`` ((m, Wp) uint32), and selects the k *largest*
        (score, -id) — ``TopKResult.scores`` carries the exact scores,
        ids/dists re-order to score order, pads are (-1, BIG, -1.0).
        Requires ``payload_words``.

        ``explain=True`` returns ``(TopKResult, QueryExplain)`` — a
        bit-identical result plus the per-rung pruning record
        (DESIGN.md §11); explain serves through the shared ladder over
        the same column planes, so the extra cost is the record itself
        (plus the bst frontier-width sampling launch)."""
        qs = np.asarray(qs, dtype=np.uint8)
        if qs.ndim == 1:
            qs = qs[None, :]
        if explain:
            return self._explain_topk(qs, int(k), tau0, rerank,
                                      q_payloads)
        if rerank is not None:
            q_pay = self._check_rerank(rerank, q_payloads, qs.shape[0])
            if self.use_arena:
                return self._fused_topk_rerank(qs, int(k), tau0, rerank,
                                               q_pay)
            return self._rerank_ladder(qs, int(k), tau0, rerank, q_pay)
        if q_payloads is not None:
            raise ValueError("q_payloads supplied without rerank=")
        if self.use_arena:
            return self._fused_topk(qs, int(k), tau0)
        return _ladder_topk(self._search_columns, self.n_live, self.b,
                            self.L, qs, k, tau0)

    def topk(self, q: np.ndarray, k: int,
             tau0: Optional[int] = None, *,
             rerank: Optional[str] = None,
             q_payloads: Optional[np.ndarray] = None,
             explain: bool = False) -> TopKResult:
        """Single-query ``topk_batch`` (row 0); ``explain=True`` appends
        the ``QueryExplain`` record."""
        qp = None
        if q_payloads is not None:
            qp = np.asarray(q_payloads, np.uint32)
            if qp.ndim == 1:
                qp = qp[None, :]
        out = self.topk_batch(np.asarray(q)[None], k, tau0=tau0,
                              rerank=rerank, q_payloads=qp,
                              explain=explain)
        res, ex = out if explain else (out, None)
        res = TopKResult(ids=res.ids[0], dists=res.dists[0], tau=res.tau,
                         overflow=res.overflow,
                         scores=(None if res.scores is None
                                 else res.scores[0]))
        return (res, ex) if explain else res

    # -- accounting ------------------------------------------------------

    @property
    def n_live(self) -> int:
        """Live (inserted minus deleted) ids across delta + segments."""
        return int(self._delta_live.sum()) + sum(
            seg.n_live for seg in self.segments)

    @property
    def tombstones(self) -> int:
        """Dead rows still physically held (reclaimable by merge/compact)
        across the delta buffer and every segment."""
        dead_delta = int((~self._delta_live).sum())
        return dead_delta + sum(seg.n - seg.n_live for seg in self.segments)

    def __len__(self) -> int:
        return self.n_live

    def space_ledger(self) -> Dict[str, int]:
        """The one consistent space ledger (DESIGN.md §7):

        ``model_bits``   — the succinct model: per-segment index bits +
          tombstone bitmaps, PLUS everything the dynamic machinery
          allocates per row that the old ``space_bits`` drifted away
          from: the arena's base_idx/gids/live lanes (9 bytes per sealed
          column) and the delta verify planes at the power-of-two bucket
          size ``_delta_planes()`` actually allocates (not the raw row
          count).  Deterministic in the lifecycle state — lazily built
          arrays are accounted at their steady-state size.
        ``device_bytes`` — resident device arrays: the column store /
          arena (hot columns + lanes), the materialized delta planes,
          and every segment's static index pytree.
        ``host_bytes``   — resident host arrays: packed sealed sketches,
          id/liveness lanes, raw delta rows, and cold column blocks.
        """
        model = 0
        r_sealed = 0
        for seg in self.segments:
            model += int(seg.index.model_bits()) + tombstone_bits(seg.n)
            r_sealed += seg.n
        nd = len(self._delta_ids)
        W = n_words(self.L)
        if nd:
            model += bucket_m(nd) * self.b * W * 32 + tombstone_bits(nd)
        if r_sealed and self.use_arena and self.backend == "bst":
            model += r_sealed * (4 + 4 + 1) * 8   # base_idx/gids/live lanes
        device = 0
        host = 0
        ar = self._arena
        if ar is not None:
            device += ar.array_bytes()
            host += ar.host_bytes()
        if self._delta_vert is not None:
            device += int(self._delta_vert.nbytes)
        # re-rank payload plane (DESIGN.md §10): the suffix store's
        # payload blocks are already inside ar.array_bytes()/host_bytes()
        # (block_bytes); the non-suffix arena and the delta plane are
        # ledgered here
        if self._delta_pay_vert is not None:
            device += int(self._delta_pay_vert.nbytes)
        if self._pay_arena is not None:
            device += self._pay_arena.array_bytes()
        for seg in self.segments:
            device += int(seg.index.array_bytes())
            host += int(seg.packed.nbytes + seg.ids.nbytes
                        + seg.live.nbytes)
            if seg.payloads is not None:
                host += int(seg.payloads.nbytes)
        host += int(self._delta_sk.nbytes + self._delta_ids.nbytes
                    + self._delta_live.nbytes)
        if self._delta_pay is not None:
            host += int(self._delta_pay.nbytes)
        return {"model_bits": model, "device_bytes": device,
                "host_bytes": host}

    def space_bits(self) -> int:
        """Model-space accounting — ``space_ledger()['model_bits']``:
        per-segment index bits + tombstone bitmaps (DESIGN.md §4) + the
        arena lanes and bucket-padded delta planes the dynamic path
        allocates per row."""
        return self.space_ledger()["model_bits"]

    def cost_hint(self, op: str, *, k: Optional[int] = None,
                  tau: Optional[int] = None, rows: int = 1) -> float:
        """Cost-model estimate of one request against the *current*
        corpus (paper Appendix A, Eq. 2; DESIGN.md §12) — the admission
        controller's currency.  ``op``:

          * ``"topk"``   — cost of the τ ladder seeded by
            ``tau_for_k(b, L, n, k)``;
          * ``"search"`` — cost at the fixed ``tau``;
          * ``"write"``  — ``rows`` delta appends / tombstone flips,
            priced as τ=0 probes (cheap relative to any query; their
            amortized seal/merge cost is the maintenance path's budget,
            not the admission controller's).

        Pure host arithmetic, monotone in k/τ/rows, never raises —
        callable on every submit."""
        n = max(float(self.n_live), 1.0)
        if op == "write":
            return max(float(rows), 1.0) \
                * max(cost_single(self.b, self.L, 0, n), 1e-6)
        if op == "search":
            t = min(max(int(tau) if tau is not None else 0, 0), self.L)
        else:
            t = tau_for_k(self.b, self.L, n,
                          max(int(k) if k is not None else 1, 1))
        return max(cost_single(self.b, self.L, t, n), 1e-6)

    def stats(self) -> Dict[str, object]:
        """Lifecycle counters, per-segment occupancy, and the space
        ledger (for dashboards and the ingest benchmark)."""
        led = self.space_ledger()
        ar = self._arena
        return {
            "n_ids": self.n_ids, "n_live": self.n_live,
            "tombstones": self.tombstones,
            "delta_rows": int(len(self._delta_ids)),
            "delta_live": int(self._delta_live.sum()),
            "n_segments": len(self.segments),
            "segments": [(seg.n, seg.n_live) for seg in self.segments],
            "space_bits": led["model_bits"],
            "device_bytes": led["device_bytes"],
            "host_bytes": led["host_bytes"],
            "arena_bytes": ar.array_bytes() if ar is not None else 0,
            "tier": (ar.tier_summary() if ar is not None else
                     {"hot_blocks": 0, "cold_blocks": 0, "hot_bytes": 0,
                      "cold_bytes": 0}),
            **self.counters,
        }

    # -- internals -------------------------------------------------------

    def _replay_insert(self, ids: np.ndarray, sk: np.ndarray,
                       payloads: Optional[np.ndarray] = None) -> None:
        """Recovery-only: append rows with *preassigned* ids to the delta
        buffer.  No WAL logging and no auto-flush — the store runs the
        maintenance fixpoint once replay completes, so the recovered
        partition matches a never-crashed index."""
        sk = np.asarray(sk, np.uint8)
        ids = np.asarray(ids, np.int64)
        self._delta_sk = np.concatenate([self._delta_sk, sk])
        self._delta_ids = np.concatenate([self._delta_ids, ids])
        self._delta_live = np.concatenate(
            [self._delta_live, np.ones(len(ids), bool)])
        self._delta_vert = None
        if self._delta_pay is not None:
            if payloads is None:
                raise ValueError("replay of a payload index requires the "
                                 "records' payload bitmaps")
            self._delta_pay = np.concatenate(
                [self._delta_pay, np.asarray(payloads, np.uint32)])
            self._delta_pay_vert = None
        if ids.size:
            self.n_ids = max(self.n_ids, int(ids.max()) + 1)

    def _build(self, sk: np.ndarray, ids: np.ndarray):
        """Build one segment's index over rows ``sk`` -> (index, order):
        ``order`` is the row permutation that stores the rows in the
        trie's leaf order, equal rows by ``ids`` (bst backend; None when
        the rows are in that order already, or on the other backends).
        The order is the trie build's own sort; only runs of equal rows
        are sorted again."""
        if self.backend == "multi":
            return build_multi_index(sk, self.b, self.mi_blocks,
                                     self.lam), None
        if self.backend == "sharded":
            return build_sharded_bst(sk, self.b,
                                     max(1, min(self.n_shards, len(sk))),
                                     self.lam), None
        trie = build_trie_levels(sk, self.b)
        order = trie.ids_sorted
        leaf = trie.id_leaf[order]                 # non-decreasing
        order = _ties_by_id(order, leaf, ids)
        if np.array_equal(order, np.arange(len(order))):
            return build_bst(sk, self.b, self.lam, trie=trie), None
        # the index addresses rows through id_leaf only: permuting the
        # rows permutes it into the (non-decreasing) leaf of each row,
        # set before the build so that only that lane goes to the device
        # (``build_bst`` reads the rows themselves only without a trie)
        trie = dataclasses.replace(trie, id_leaf=leaf,
                                   ids_sorted=np.arange(len(leaf)))
        return build_bst(sk, self.b, self.lam, trie=trie), order

    def _seal(self, sk: np.ndarray, ids: np.ndarray,
              pay: Optional[np.ndarray], *, live: Optional[np.ndarray] = None,
              packed: Optional[np.ndarray] = None,
              serial: Optional[int] = None) -> Segment:
        """One sealed segment over rows (sketches, ids, payloads), its
        rows stored in the trie's leaf order (``_build``).  ``live`` /
        ``packed`` / ``serial`` restore a checkpointed segment (all
        live, packed here and a fresh serial otherwise)."""
        with _obs_span("trie_build", cat="ingest", rows=len(sk)):
            index, order = self._build(sk, ids)
        if order is not None:
            sk, ids = sk[order], ids[order]
            pay = pay[order] if pay is not None else None
            live = live[order] if live is not None else None
            packed = packed[order] if packed is not None else None
        return Segment(
            index=index,
            packed=self._traced_pack(sk) if packed is None else packed,
            ids=ids, live=np.ones(len(ids), bool) if live is None else live,
            L=self.L, b=self.b, payloads=pay,
            serial=next_serial() if serial is None else int(serial))

    def _traced_pack(self, sk: np.ndarray) -> np.ndarray:
        with _obs_span("pack_vertical", cat="ingest", rows=len(sk)):
            return pack_vertical(sk, self.b)

    def _delta_planes(self) -> jnp.ndarray:
        """(b, W, ndb) uint32 delta-buffer verify planes, with the row
        axis padded up to the power-of-two bucket ``ndb = bucket_m(nd)``
        (zero columns past nd — masked dead by every caller).  Bucketing
        the brute-force scan's shape means a stream of single-row
        inserts touches O(log delta_cap) compiled scan shapes instead of
        re-jitting ``hamming_distances`` at every delta size."""
        if self._delta_vert is None:
            nd = len(self._delta_ids)
            ndb = bucket_m(nd)
            planes = pack_vertical(self._delta_sk, self.b)   # (nd, b, W)
            vert = np.transpose(planes, (1, 2, 0))            # (b, W, nd)
            if ndb != nd:
                vert = np.concatenate(
                    [vert, np.zeros(vert.shape[:2] + (ndb - nd,),
                                    np.uint32)], axis=-1)
            self._delta_vert = jnp.asarray(vert.copy())
        return self._delta_vert

    def _delta_pay_planes(self) -> jnp.ndarray:
        """(Wp, ndb) uint32 delta-buffer payload plane, bucketed to the
        same ``ndb = bucket_m(nd)`` shape as ``_delta_planes`` (zero
        columns past nd — the survivor mask already kills them), so the
        re-rank program shares the delta shape buckets of the verify
        scan."""
        if self._delta_pay_vert is None:
            nd = len(self._delta_ids)
            ndb = bucket_m(nd)
            vert = np.zeros((self.payload_words, ndb), np.uint32)
            if nd:
                vert[:, :nd] = self._delta_pay.T
            self._delta_pay_vert = jnp.asarray(vert)
        return self._delta_pay_vert

    def _search_columns(self, qs: np.ndarray,
                        tau: int) -> Tuple[np.ndarray, np.ndarray, int]:
        """Per-segment reference fan-out: (m, L) queries -> ((m, R) int32
        distances over the physical columns — BIG on non-results, (R,)
        int64 global id per column, total overflow), where R = rows
        currently held (every segment's rows, then the delta buffer's) —
        R shrinks with merge/compact, unlike the ever-assigned global id
        space.  Every segment contributes exact distances within τ; the
        delta buffer contributes a brute-force scan clamped to the same
        τ so the ladder logic sees one consistent contract.  Costs one
        device dispatch per segment plus one for the delta buffer; the
        fused arena path (``_fused_columns``) is the bit-identical
        single-dispatch replacement (DESIGN.md §6)."""
        m = qs.shape[0]
        dists: List[np.ndarray] = []
        col_ids: List[np.ndarray] = []
        overflow = 0
        qs_j = jnp.asarray(qs)
        for seg in self.segments:
            if seg.live.any():
                with _obs_span("segment_fanout", cat="device",
                               serial=seg.serial, tau=tau):
                    dist, ov = self._search_segment(seg, qs_j, tau)
                overflow += ov
            else:
                dist = np.full((m, seg.n), BIG_I, np.int32)
            dists.append(dist)
            col_ids.append(seg.ids)
        nd = len(self._delta_ids)
        if nd:
            planes = pack_vertical(qs, self.b)                # (m, b, W)
            q_vert = jnp.asarray(np.transpose(planes, (1, 2, 0)).copy())
            _dispatch("fanout")
            with _obs_span("delta_scan", cat="device", rows=nd):
                d = np.asarray(ops.hamming_distances(self._delta_planes(),
                                                     q_vert))[:, :nd]
            d = np.where(self._delta_live[None, :] & (d <= tau), d, BIG_I)
            dists.append(d.astype(np.int32))
            col_ids.append(self._delta_ids)
        if not dists:
            return (np.zeros((m, 0), np.int32), np.zeros((0,), np.int64),
                    0)
        return (np.concatenate(dists, axis=1),
                np.concatenate(col_ids), overflow)

    def _search_planes(self, qs: np.ndarray, tau: int,
                       columns_fn=None) -> Tuple[np.ndarray, int]:
        """(m, L) queries -> ((m, n_ids) int32 global distance plane with
        BIG on non-results, total overflow): the column-compressed
        fan-out scattered onto the full global-id axis (the opt-in dense
        range-search contract — O(m · ids-ever-assigned) memory).
        ``columns_fn`` overrides the column source (the explain path
        passes its recording wrapper)."""
        m = qs.shape[0]
        if columns_fn is None:
            columns_fn = self._columns
        dist, col_ids, overflow = columns_fn(qs, tau)
        plane = np.full((m, self.n_ids), BIG_I, np.int32)
        plane[:, col_ids] = dist
        return plane, overflow

    def _columns(self, qs: np.ndarray,
                 tau: int) -> Tuple[np.ndarray, np.ndarray, int]:
        """Route to the fused arena path or the per-segment reference
        fan-out (identical contracts, bit-identical results)."""
        if self.use_arena:
            return self._fused_columns(qs, tau)
        return self._search_columns(qs, tau)

    # -- query explain (DESIGN.md §11) -----------------------------------

    def _explain_recorder(self) -> _ExplainRecorder:
        """Frontier widths are sampled on the bst backend only (the
        multi/sharded traversals have no single per-level frontier)."""
        frontier_index = self if self.backend == "bst" else None
        return _ExplainRecorder(frontier_index=frontier_index)

    def _explain_topk(self, qs: np.ndarray, k: int, tau0: Optional[int],
                      rerank: Optional[str], q_payloads):
        """The explain-mode kNN: run the *shared* τ ladder over this
        index's column planes with a recording wrapper.  The ladder
        schedule, the column planes, and the (distance, id) / (score,
        -id) selections are the ones every serving path is already
        bit-identical to (``_ladder_topk`` vs ``_fused_topk``,
        ``_ladder_topk_rerank`` vs ``_fused_topk_rerank`` — held by the
        fused-vs-reference tests), so the result is bit-identical to
        ``explain=False``."""
        rec = self._explain_recorder()
        columns_fn = rec.wrap(self._columns)
        if rerank is not None:
            q_pay = self._check_rerank(rerank, q_payloads, qs.shape[0])
            res = _ladder_topk_rerank(
                columns_fn, self._payload_rows, self.n_live, self.b,
                self.L, self.block_m, qs, k, tau0, rerank, q_pay)
        else:
            if q_payloads is not None:
                raise ValueError("q_payloads supplied without rerank=")
            res = _ladder_topk(columns_fn, self.n_live, self.b, self.L,
                               qs, k, tau0)
        return res, rec.finish(
            op="topk", backend=self.backend, n_queries=qs.shape[0],
            n_live=self.n_live, k=int(k),
            tau0=None if tau0 is None else int(tau0),
            tau_final=int(res.tau), rerank=rerank)

    def _frontier_widths(self, qs: np.ndarray,
                         tau: int) -> Optional[List[List[int]]]:
        """Per-query, per-trie-level live frontier widths at this τ,
        summed across the segment stack ((m, L) — levels past a
        segment's collapse depth ℓ_s contribute nothing).  Explain-only:
        one extra cached program launch, deliberately outside the
        serving dispatch ledger."""
        if self.backend != "bst" or not self.segments:
            return None
        m = qs.shape[0]
        mb = bucket_m(m)
        qs_p = jnp.asarray(qs)
        if mb != m:
            qs_p = _pad_rows(qs_p, mb)
        widths = np.asarray(self._widths_fn(int(tau))(qs_p))[:m]
        return [[int(w) for w in row] for row in widths]

    def _widths_fn(self, tau: int):
        """Cache the frontier-width sampling program alongside the fused
        programs (same ``_fused_id`` scope, so the stale-generation
        purge in ``_fused_fn`` also drops it)."""
        serials = self._seg_serials()
        key = (self.backend, self.layout, self._fused_id, serials,
               "widths", tau, self.block_m)
        fn = _FUSED_CACHE.get(key)
        if fn is None:
            fn = self._build_widths(tau)
            while len(_FUSED_CACHE) >= _FUSED_CACHE_CAP:
                _FUSED_CACHE.pop(next(iter(_FUSED_CACHE)))
            _FUSED_CACHE[key] = fn
        return fn

    def _build_widths(self, tau: int):
        """One jitted program: every segment's frontier descent with the
        per-level width taps, summed into an (m, L) plane (same
        traversal arithmetic as the fused programs' first half)."""
        indexes = [seg.index for seg in self.segments]
        caps_list = [frontier_capacities(ix.t, self.b, tau,
                                         CAP_MAX_DEFAULT)
                     for ix in indexes]
        L = self.L

        @jax.jit
        def run(qs):
            _note_trace()
            qsi = qs.astype(jnp.int32)
            m = qsi.shape[0]
            per_level = jnp.zeros((m, L), jnp.int32)
            for ix, caps in zip(indexes, caps_list):
                widths: List[jnp.ndarray] = []
                _traverse_frontier_batch(ix, qsi, tau=tau, caps=caps,
                                         level_widths=widths)
                if widths:
                    w = jnp.stack(widths, axis=-1)        # (m, depth_s)
                    per_level = per_level.at[:, :w.shape[-1]].add(w)
            return per_level
        return run

    def _search_segment(self, seg: Segment, qs_j: jnp.ndarray,
                        tau: int) -> Tuple[np.ndarray, int]:
        """One segment, the whole batch -> ((m, n_seg) int32 exact local
        distances — BIG off-mask and on tombstones, overflow).  Runs the
        backend's cached compiled searcher with the liveness bitmap as a
        traced argument, on the doubled capacity ladder until exact."""
        if self.backend == "multi":
            _dispatch("fanout")
            res = mi_search_batch(seg.index, qs_j, tau,
                                  block_m=self.block_m, id_live=seg.live)
            return (np.asarray(res.dist, dtype=np.int32),
                    int(np.asarray(res.overflow).sum()))
        if self.backend == "sharded":
            idx = seg.index
            cap = 1 << 14
            while True:
                # keyed on the monotonic segment serial, never id(): a
                # serial is never reused, so a merged-away segment can
                # never alias a live one's cached searcher
                key = (seg.serial, tau, cap)

                def build():
                    return make_sharded_searcher(idx, tau, cap_max=cap)
                fn, _ = _pin_cache_get(_SHARDED_SEARCHER_CACHE,
                                       _SHARDED_SEARCHER_CACHE_CAP,
                                       key, idx, build)
                _dispatch("fanout")
                _, dists, ov = fn(qs_j)
                if int(ov) == 0 or cap >= LADDER_CAP_MAX:
                    break
                cap *= 2
            merged = np.asarray(dists)[:, idx.shard_of, idx.pos_of]
            merged = np.where(seg.live[None, :], merged, BIG_I)
            return merged.astype(np.int32), int(ov)
        live_j = jnp.asarray(seg.live)
        cap = CAP_MAX_DEFAULT
        while True:
            fn = get_searcher(seg.index, tau, cap, batch=True,
                              block_m=self.block_m, with_live=True)
            _dispatch("fanout")
            res = fn(qs_j, live_j)
            ov = int(np.asarray(res.overflow).sum())
            if ov == 0 or cap >= LADDER_CAP_MAX:
                break
            cap *= 2
        return np.asarray(res.dist, dtype=np.int32), ov

    # -- fused one-dispatch arena path (DESIGN.md §6) --------------------

    def _seg_serials(self) -> Tuple[int, ...]:
        return tuple(seg.serial for seg in self.segments)

    def _refresh_arena(self) -> _ColumnArena:
        """Bring the device-resident column arena (bst backend) up to
        date with the segment stack.  A flush *appends* the new
        segment's column block, base-offset lanes, id labels, and
        liveness lanes to the existing device arrays (one concat per
        flush, never per query); a merge or compact changes the stack's
        serial fingerprint non-monotonically and triggers a full
        rebuild — the same O(R) work as the index rebuild that caused
        it."""
        serials = self._seg_serials()
        ar = self._arena
        if ar is not None and ar.serials == serials:
            return ar
        incremental = (ar is not None and ar.cols is not None
                       and len(serials) > len(ar.serials)
                       and serials[:len(ar.serials)] == ar.serials)
        if not incremental:
            ar = _ColumnArena()
        new_segs = self.segments[len(ar.serials):]
        W = max(1, (self.L + 31) // 32)
        cols_np, idx_np, gid_np, live_np, cid_np = [], [], [], [], []
        col0 = int(ar.col_ids.shape[0])
        root0 = ar.t_root_total
        for seg in new_segs:
            cols_np.append(np.transpose(seg.packed, (1, 2, 0)))
            leaf_root = np.asarray(seg.index.tail.leaf_root)
            id_leaf = np.asarray(seg.index.id_leaf)
            idx_np.append((root0 + leaf_root[id_leaf]).astype(np.int32))
            gid_np.append(seg.ids.astype(np.int32))
            live_np.append(seg.live.copy())
            cid_np.append(seg.ids)
            ar.col_off[seg.serial] = col0
            col0 += seg.n
            root0 += int(seg.index.tail.t_root)
        empty_cols = jnp.zeros((self.b, W, 0), jnp.uint32)
        old = ((ar.cols, ar.base_idx, ar.gids, ar.live)
               if ar.cols is not None
               else (empty_cols, jnp.zeros((0,), jnp.int32),
                     jnp.zeros((0,), jnp.int32), jnp.zeros((0,), bool)))
        if new_segs:
            ar.cols = jnp.concatenate(
                [old[0], jnp.asarray(np.concatenate(cols_np, axis=-1))],
                axis=-1)
            ar.base_idx = jnp.concatenate(
                [old[1], jnp.asarray(np.concatenate(idx_np))])
            ar.gids = jnp.concatenate(
                [old[2], jnp.asarray(np.concatenate(gid_np))])
            ar.live = jnp.concatenate(
                [old[3], jnp.asarray(np.concatenate(live_np))])
            ar.col_ids = np.concatenate([ar.col_ids] + cid_np)
            ar._idx_host = np.concatenate([ar._idx_host] + idx_np)
            ar._windows = {}
        else:
            ar.cols, ar.base_idx, ar.gids, ar.live = old
        ar.t_root_total = root0
        ar.serials = serials
        self._arena = ar
        return ar

    def _refresh_store(self) -> ColumnStore:
        """Bring the tiered suffix ``ColumnStore`` (bst backend,
        ``layout="suffix"``) up to date with the segment stack — the
        same incremental discipline as ``_refresh_arena``: a flush
        appends one block, a merge/compact triggers a rebuild.  Sealing
        enforces the ``hot_bytes`` placement budget (LRU demotion /
        promotion), so tier flips happen here, between queries — never
        inside a compiled program."""
        serials = self._seg_serials()
        st = self._arena
        if isinstance(st, ColumnStore) and st.serials == serials:
            return st
        incremental = (isinstance(st, ColumnStore)
                       and len(serials) > len(st.serials)
                       and serials[:len(st.serials)] == st.serials)
        if not incremental:
            st = ColumnStore(self.L, self.b, hot_bytes=self.hot_bytes,
                             payload_words=self.payload_words)
        for seg in self.segments[len(st.serials):]:
            st.append_segment(seg)
        st.seal(serials)
        self._arena = st
        return st

    def _fused_fn(self, kind: str, tau: int, rung: int, kk: Optional[int]):
        """Fetch (or build) the compiled fused program for this segment
        stack: ``kind="cols"`` -> f(...) = ((mb, R) int32 dist plane,
        overflow); ``kind="topk"`` -> ((mb, kk) ids, (mb, kk) dists,
        min-survivors, overflow) — selection on device.  jit
        re-specializes per (mb, ndb) shape bucket under one cache
        entry."""
        serials = self._seg_serials()
        suffix_store = self.backend == "bst" and self.layout == "suffix"
        # the placement generation joins the fingerprint: a tier flip
        # moves columns between the hot corpus and the staged slabs, so a
        # pre-flip program must never be reused
        gen = self._refresh_store().gen if suffix_store else 0
        if (serials, gen) != self._fused_stamp:
            # the stack or placement changed generation: this index's
            # programs keyed on the old fingerprint are permanently
            # unreachable (serials/gen are monotonic) — drop them now so
            # dead generations don't pin full column-arena copies until
            # FIFO eviction
            for stale in [k for k in _FUSED_CACHE
                          if k[2] == self._fused_id]:
                del _FUSED_CACHE[stale]
            self._fused_stamp = (serials, gen)
        key = (self.backend, self.layout, self._fused_id, serials, gen,
               kind, tau, rung, kk, self.block_m)
        fn = _FUSED_CACHE.get(key)
        if fn is None:
            build = {"bst": (self._build_fused_bst_suffix if suffix_store
                             else self._build_fused_bst),
                     "multi": self._build_fused_multi,
                     "sharded": self._build_fused_sharded}[self.backend]
            fn = build(kind, tau, rung, kk)
            while len(_FUSED_CACHE) >= _FUSED_CACHE_CAP:
                _FUSED_CACHE.pop(next(iter(_FUSED_CACHE)))
            _FUSED_CACHE[key] = fn
            _CACHE_STATS["misses"] += 1   # same ledger as get_searcher
        else:
            _CACHE_STATS["hits"] += 1
        return fn

    def _build_fused_bst(self, kind: str, tau: int, rung: int,
                         kk: Optional[int]):
        """One jitted program for the whole bst stack: every segment's
        2D-frontier traversal to its ℓ_s roots, a 0/BIG reach scatter
        onto ONE concatenated root plane, the arena verify kernel over
        sealed + delta columns (full-length paths, so the reach plane is
        the only traversal output the verify needs), and the on-device
        (distance, id) selection."""
        arena = self._refresh_arena()
        cap = CAP_MAX_DEFAULT << rung
        indexes = [seg.index for seg in self.segments]
        caps_list = [frontier_capacities(ix.t, self.b, tau, cap)
                     for ix in indexes]
        t_roots = [int(ix.tail.t_root) for ix in indexes]
        cols0, idx0, gids0 = arena.cols, arena.base_idx, arena.gids
        t_slot = arena.t_root_total
        b_, block_m = self.b, self.block_m

        @jax.jit
        def run(qs, live_sealed, delta_vert, delta_live, delta_gids):
            _note_trace()
            qsi = qs.astype(jnp.int32)
            m = qsi.shape[0]
            ndb = delta_vert.shape[-1]
            planes = []
            overflow = jnp.zeros((m,), jnp.int32)
            with jax.named_scope("rung.traverse"):
                for ix, caps, t_root in zip(indexes, caps_list, t_roots):
                    ids, dists, valid, ov, _ = _traverse_frontier_batch(
                        ix, qsi, tau=tau, caps=caps)
                    # full-length columns recompute the prefix in the
                    # XOR: the plane only carries reached (0) / pruned
                    # (BIG)
                    planes.append(scatter_root_plane(
                        ids, jnp.zeros_like(dists), valid, m, t_root))
                    overflow = overflow + ov
                # last slot: the delta columns' trivial base
                planes.append(jnp.zeros((m, 1), jnp.int32))
                base_plane = jnp.concatenate(planes, axis=1)
            with jax.named_scope("rung.verify"):
                cols = jnp.concatenate([cols0, delta_vert], axis=-1)
                live = jnp.concatenate([live_sealed, delta_live])
                base_idx = jnp.concatenate(
                    [idx0, jnp.full((ndb,), t_slot, jnp.int32)])
                win = arena.windows(ndb)
                q_vert = jnp.transpose(pack_vertical_jax(qsi, b_),
                                       (1, 2, 0))
                hm, dist = ops.sparse_verify_arena(
                    cols, q_vert, base_plane, base_idx, live, tau=tau,
                    windows=(win.start, win.steps), block_m=block_m)
                dist = jnp.where(hm > 0, dist, BIG)
            if kind == "cols":
                return dist, overflow.sum()
            with jax.named_scope("rung.select"):
                if kind == "dist":
                    # two-stage stage 1: the dist plane STAYS on device
                    # (the re-rank program consumes it); only the ladder
                    # scalars cross back (DESIGN.md §10)
                    return (dist, (dist < BIG).sum(axis=1).min(),
                            overflow.sum())
                sel_ids, sel_d = select_topk_columns(
                    dist, jnp.concatenate([gids0, delta_gids]), kk)
                min_surv = (dist < BIG).sum(axis=1).min()
            return sel_ids, sel_d, min_surv, overflow.sum()
        return run

    def _build_fused_bst_suffix(self, kind: str, tau: int, rung: int,
                                kk: Optional[int]):
        """The suffix-layout fused program (DESIGN.md §7): same shape as
        ``_build_fused_bst`` — every segment's traversal, ONE root
        plane, verify, selection, one jitted launch — but the scatter
        carries the traversal's exact *prefix distances* (not 0/BIG) and
        the verify runs over per-geometry suffix column groups, so
        prefix + suffix reproduces the full-length Hamming distance bit
        for bit.  The corpus — trie levels, hot group columns, base
        lanes, gids, the group-order permutations — is the program's
        first argument (``_BoundProgram``); cold columns arrive through
        the staged slabs (uploaded by ``ColumnStore.stage`` before the
        rung loop).  Multiple geometry groups mean multiple verify
        kernel bodies INSIDE the one program — still one fused dispatch
        per rung."""
        store = self._refresh_store()
        plan = store.plan()
        order, inv = store.order()
        root_order = store.root_order()
        cap = CAP_MAX_DEFAULT << rung
        indexes = [seg.index for seg in self.segments]
        caps_list = [frontier_capacities(ix.t, self.b, tau, cap)
                     for ix in indexes]
        t_roots = [int(ix.tail.t_root) for ix in indexes]
        geoms = [g.geom for g in plan]
        spans = np.cumsum([0] + [len(g.perm) for g in plan])
        b_, L, block_m = self.b, self.L, self.block_m
        corpus = {"indexes": indexes,
                  "cols": [g.cols_hot for g in plan],
                  "base_idx": [g.base_idx for g in plan],
                  "windows": [g.windows for g in plan],
                  "gids": store.gids, "order": order, "inv": inv}

        def run(corpus, qs, live_sealed, staged, delta_vert, delta_live,
                delta_gids):
            _note_trace()
            qsi = qs.astype(jnp.int32)
            m = qsi.shape[0]
            planes = []
            overflow = jnp.zeros((m,), jnp.int32)
            with jax.named_scope("rung.traverse"):
                for ix, caps, t_root in zip(corpus["indexes"], caps_list,
                                            t_roots):
                    ids, dists, valid, ov, _ = _traverse_frontier_batch(
                        ix, qsi, tau=tau, caps=caps)
                    planes.append(scatter_root_plane(
                        ids, dists, valid, m, t_root))
                    overflow = overflow + ov
                # roots in the plan's column order: each group's lane
                # rises in steps of 0 or 1 through this plane
                base_plane = (jnp.concatenate([planes[i]
                                               for i in root_order], axis=1)
                              if planes else jnp.zeros((m, 0), jnp.int32))
            order, inv = corpus["order"], corpus["inv"]
            dist_parts: List[jnp.ndarray] = []
            with jax.named_scope("rung.verify"):
                for gi, (geom, cols_hot, base_idx, windows, slab) in \
                        enumerate(zip(geoms, corpus["cols"],
                                      corpus["base_idx"],
                                      corpus["windows"], staged)):
                    axis = 0 if geom.packed else -1
                    parts = [p for p in (cols_hot, slab) if p is not None]
                    cols_g = (parts[0] if len(parts) == 1
                              else jnp.concatenate(parts, axis=axis))
                    cols = slice(spans[gi], spans[gi + 1])
                    live_g = live_sealed[cols if order is None
                                         else order[cols]]
                    S = geom.suffix_len
                    if geom.packed:
                        qw = pack_suffix_words_jax(qsi[:, L - S:], b_)
                        hm, d = ops.sparse_verify_arena_packed(
                            cols_g, qw, base_plane, base_idx, live_g, b=b_,
                            S=S, tau=tau, windows=windows, block_m=block_m)
                    else:
                        qv = jnp.transpose(
                            pack_vertical_jax(qsi[:, L - S:], b_),
                            (1, 2, 0))
                        hm, d = ops.sparse_verify_arena(
                            cols_g, qv, base_plane, base_idx, live_g,
                            tau=tau, windows=windows, block_m=block_m)
                    dist_parts.append(jnp.where(hm > 0, d, BIG))
                dist_sealed = (jnp.concatenate(dist_parts, axis=1)
                               if dist_parts
                               else jnp.zeros((m, 0), jnp.int32))
            # the delta buffer scans full-length (its rows have no trie,
            # hence no ℓ_s to slice at) — same arithmetic as the full
            # arena's trivial delta slot
            with jax.named_scope("rung.delta_scan"):
                q_vert = jnp.transpose(pack_vertical_jax(qsi, b_),
                                       (1, 2, 0))
                dd = ops.hamming_distances(delta_vert, q_vert)
                dd = jnp.where(delta_live[None, :] & (dd <= tau), dd, BIG)
                dd = dd.astype(jnp.int32)
            with jax.named_scope("rung.select"):
                if kind == "topk":
                    # selection sorts on (distance, id), so it runs on
                    # the group-major columns with the labels permuted
                    # instead
                    gids = (corpus["gids"] if order is None
                            else corpus["gids"][order])
                    dist = jnp.concatenate([dist_sealed, dd], axis=1)
                    sel_ids, sel_d = select_topk_columns(
                        dist, jnp.concatenate([gids, delta_gids]), kk)
                    min_surv = (dist < BIG).sum(axis=1).min()
                    return sel_ids, sel_d, min_surv, overflow.sum()
                # restore global stack order, so the column contract and
                # the re-rank stage match the full-length arena exactly
                if inv is not None:
                    dist_sealed = _take_columns(dist_sealed, inv)
                dist = jnp.concatenate([dist_sealed, dd], axis=1)
                if kind == "cols":
                    return dist, overflow.sum()
                return (dist, (dist < BIG).sum(axis=1).min(),
                        overflow.sum())
        return _BoundProgram(run, corpus)

    def _build_fused_multi(self, kind: str, tau: int, rung: int,
                           kk: Optional[int]):
        """Fused stack program for MI segments: every segment's batched
        MI trace (per-block traversal + candidate verify) inlined as a
        sub-trace, delta scan and selection fused behind them."""
        segs = list(self.segments)
        cap_max = (1 << 15) << rung
        mis = [seg.index for seg in segs]
        params = []
        for mi in mis:
            caps_pb, cc = mi_trace_params(mi, tau, cap_max)
            params.append((caps_pb, min(cc << rung, mi.n)))
        gids_const = [jnp.asarray(seg.ids.astype(np.int32)) for seg in segs]
        b_, block_m = self.b, self.block_m

        @jax.jit
        def run(qs, seg_lives, delta_vert, delta_live, delta_gids):
            _note_trace()
            qsi = qs.astype(jnp.int32)
            dists: List[jnp.ndarray] = []
            ov = jnp.int32(0)
            for mi, (caps_pb, cc), live in zip(mis, params, seg_lives):
                d, o = mi_column_dists(mi, qsi, tau, caps_pb, cc,
                                       block_m=block_m, id_live=live)
                dists.append(d)
                ov = ov + o.sum()
            q_vert = jnp.transpose(pack_vertical_jax(qsi, b_), (1, 2, 0))
            dd = ops.hamming_distances(delta_vert, q_vert)
            dd = jnp.where(delta_live[None, :] & (dd <= tau), dd, BIG)
            dists.append(dd.astype(jnp.int32))
            dist = jnp.concatenate(dists, axis=1)
            if kind == "cols":
                return dist, ov
            if kind == "dist":
                return dist, (dist < BIG).sum(axis=1).min(), ov
            sel_ids, sel_d = select_topk_columns(
                dist, jnp.concatenate(gids_const + [delta_gids]), kk)
            min_surv = (dist < BIG).sum(axis=1).min()
            return sel_ids, sel_d, min_surv, ov
        return run

    def _build_fused_sharded(self, kind: str, tau: int, rung: int,
                             kk: Optional[int]):
        """Fused stack program for sharded-bST segments: each segment's
        vmapped per-shard traversal+verify runs as a sub-trace and the
        shard->global merge happens on device
        (``sharded_column_dists``), so S shards × n_segments collapse
        into the one launch."""
        segs = list(self.segments)
        cap = (1 << 14) << rung
        idxs = [seg.index for seg in segs]
        capss = []
        for idx in idxs:
            t_max = tuple(int(x) for x in np.asarray(idx.t).max(axis=0))
            capss.append(frontier_capacities(t_max, idx.b, tau, cap))
        gids_const = [jnp.asarray(seg.ids.astype(np.int32)) for seg in segs]
        b_, block_m = self.b, self.block_m

        @jax.jit
        def run(qs, seg_lives, delta_vert, delta_live, delta_gids):
            _note_trace()
            qsi = qs.astype(jnp.int32)
            dists: List[jnp.ndarray] = []
            ov = jnp.int32(0)
            for idx, caps, live in zip(idxs, capss, seg_lives):
                d, o = sharded_column_dists(idx, qsi, tau, caps,
                                            block_m=block_m, live=live)
                dists.append(d.astype(jnp.int32))
                ov = ov + o
            q_vert = jnp.transpose(pack_vertical_jax(qsi, b_), (1, 2, 0))
            dd = ops.hamming_distances(delta_vert, q_vert)
            dd = jnp.where(delta_live[None, :] & (dd <= tau), dd, BIG)
            dists.append(dd.astype(jnp.int32))
            dist = jnp.concatenate(dists, axis=1)
            if kind == "cols":
                return dist, ov
            if kind == "dist":
                return dist, (dist < BIG).sum(axis=1).min(), ov
            sel_ids, sel_d = select_topk_columns(
                dist, jnp.concatenate(gids_const + [delta_gids]), kk)
            min_surv = (dist < BIG).sum(axis=1).min()
            return sel_ids, sel_d, min_surv, ov
        return run

    def _fused_saturated(self, rung: int) -> bool:
        start = {"bst": CAP_MAX_DEFAULT, "multi": 1 << 15,
                 "sharded": 1 << 14}[self.backend]
        if (start << rung) < LADDER_CAP_MAX:
            return False
        if self.backend == "multi":
            # candidate caps floor at 1024 and double per rung alongside
            # the frontier caps (mi_search_batch's ladder discipline)
            return all((1024 << rung) >= seg.index.n
                       for seg in self.segments)
        return True

    def _fused_call(self, kind: str, qs: np.ndarray, tau: int,
                    kk: Optional[int] = None):
        """Dispatch ONE fused program per capacity rung: pads the query
        axis to its power-of-two bucket, assembles the (bucketed) delta
        args, and escalates the frontier-capacity rung until the
        traversal is exact — each retry is again a single launch."""
        m = qs.shape[0]
        mb = bucket_m(m)
        qs_p = jnp.asarray(qs)
        if mb != m:
            qs_p = _pad_rows(qs_p, mb)
        nd = len(self._delta_ids)
        with _obs_span("delta_planes", cat="device", rows=nd):
            if nd:
                delta_vert = self._delta_planes()
                ndb = delta_vert.shape[-1]
                delta_live = np.zeros(ndb, bool)
                delta_live[:nd] = self._delta_live
                delta_gids = np.zeros(ndb, np.int32)
                delta_gids[:nd] = self._delta_ids.astype(np.int32)
            else:
                W = max(1, (self.L + 31) // 32)
                delta_vert = jnp.zeros((self.b, W, 0), jnp.uint32)
                delta_live = np.zeros(0, bool)
                delta_gids = np.zeros(0, np.int32)
        staged = None
        if self.backend == "bst":
            if self.layout == "suffix":
                with _obs_span("store_refresh", cat="device"):
                    store = self._refresh_store()
                    # copy-ahead: upload every cold block's staging slab
                    # ONCE per query, before the rung loop — the async
                    # device_put overlaps the first rung's traversal, and
                    # ladder retries reuse the same slabs
                    staged = store.stage()
                seg_arg = store.live
            else:
                seg_arg = self._refresh_arena().live
        else:
            seg_arg = tuple(jnp.asarray(seg.live) for seg in self.segments)
        rung = 0
        while True:
            # span covers build/fetch + dispatch + the steering-scalar
            # readback (the sync point where device time surfaces)
            with _obs_span("rung_dispatch", cat="device", kind=kind,
                           tau=tau, rung=rung) as sp:
                with _obs_span("rung_program", cat="device"):
                    fn = self._fused_fn(kind, tau, rung, kk)
                _dispatch("fused")
                self._count_windows(mb, delta_vert.shape[-1])
                with _obs_span("rung_launch", cat="device"):
                    args = (jnp.asarray(qs_p), seg_arg)
                    if staged is not None:
                        args += (staged,)
                    args += (delta_vert, jnp.asarray(delta_live),
                             jnp.asarray(delta_gids))
                    out = fn(*args)
                with _obs_span("rung_wait", cat="device"):
                    done = (int(out[-1]) == 0
                            or self._fused_saturated(rung))
                if sp is not None:
                    sp.args["program"] = _scope_label(fn, args)
            if done:
                return out
            rung += 1

    def _count_windows(self, mb: int, ndb: int) -> None:
        """Tally one fused launch's windowed verify (bst backend): every
        arena kernel call wide enough for the kernel path (``ops`` runs
        narrower ones on the oracle) expands its column blocks' windows
        once per query tile of the ``mb``-row bucket."""
        if self.backend != "bst":
            return
        if self.layout == "suffix":
            plans = [(len(g.perm), len(g.windows[0]), g.window_roots)
                     for g in self._refresh_store().plan()]
        else:
            ar = self._refresh_arena()
            win = ar.windows(ndb)
            plans = [(ar.n_cols + ndb, len(win.start), win.roots)]
        tiles = -(-mb // min(self.block_m, mb))
        for n, blocks, roots in plans:
            if ops.arena_uses_kernel(n):
                _tally_windows(n, blocks * tiles, roots * tiles)

    def _fused_columns(self, qs: np.ndarray,
                       tau: int) -> Tuple[np.ndarray, np.ndarray, int]:
        """Arena-path ``_search_columns``: same ((m, R) dist, (R,) ids,
        overflow) contract, one device dispatch per capacity rung."""
        m = qs.shape[0]
        r_sealed = sum(seg.n for seg in self.segments)
        nd = len(self._delta_ids)
        if r_sealed + nd == 0:
            return (np.zeros((m, 0), np.int32), np.zeros((0,), np.int64),
                    0)
        dist, ov = self._fused_call("cols", qs, tau)
        dist = np.asarray(dist)[:m, :r_sealed + nd]
        col_ids = np.concatenate([seg.ids for seg in self.segments]
                                 + [self._delta_ids])
        return dist, col_ids, int(ov)

    def _fused_topk(self, qs: np.ndarray, k: int,
                    tau0: Optional[int]) -> TopKResult:
        """The on-device τ-escalation ladder: each rung is one fused
        launch whose selection already ran on device — the host reads
        back two scalars (min survivor count, overflow) to steer the
        ladder, and only the final (m, k) ids/dists when it stops."""
        m = qs.shape[0]
        n_live = self.n_live
        if n_live == 0:
            return TopKResult(ids=jnp.full((m, k), -1, jnp.int32),
                              dists=jnp.full((m, k), BIG_I, jnp.int32),
                              tau=0, overflow=0)
        kk = min(int(k), n_live)
        tau = tau0 if tau0 is not None else tau_for_k(self.b, self.L,
                                                      n_live, kk)
        tau = min(max(int(tau), 0), self.L)
        while True:
            ids, dists, min_surv, ov = self._fused_call("topk", qs, tau,
                                                        kk=kk)
            if int(min_surv) >= kk or tau >= self.L:
                break
            tau = min(self.L, max(tau + 1, 2 * tau))
        with _obs_span("topk_readback", cat="device", k=int(k)):
            dd, ids = _pad_topk(np.asarray(dists)[:m],
                                np.asarray(ids)[:m], int(k))
        return TopKResult(ids=jnp.asarray(ids), dists=jnp.asarray(dd),
                          tau=tau, overflow=int(ov))

    # -- exact re-rank plane (DESIGN.md §10) -----------------------------

    def _check_rerank(self, metric: str, q_payloads,
                      m: int) -> np.ndarray:
        """Validate the two-stage request: known metric, payload-bearing
        index, (m, Wp) uint32 query bitmaps."""
        if metric not in RERANK_METRICS:
            raise ValueError(f"rerank must be one of {RERANK_METRICS}")
        if self.payload_words is None:
            raise ValueError(
                "rerank requires an index built with payload_words")
        if q_payloads is None:
            raise ValueError("rerank requires q_payloads — the queries' "
                             "(m, Wp) uint32 set bitmaps")
        qp = np.asarray(q_payloads, np.uint32)
        if qp.ndim == 1:
            qp = qp[None, :]
        if qp.shape != (m, self.payload_words):
            raise ValueError(f"q_payloads shape {qp.shape} != "
                             f"({m}, {self.payload_words})")
        return qp

    def _payload_rows(self) -> np.ndarray:
        """(R, Wp) uint32 host payload rows in global column order (every
        segment's rows in stack order, then the delta buffer's) — the
        reference path's re-rank source."""
        parts = [seg.payloads for seg in self.segments]
        if len(self._delta_ids):
            parts.append(self._delta_pay)
        if not parts:
            return np.zeros((0, self.payload_words), np.uint32)
        return np.concatenate(parts, axis=0)

    def _rerank_ladder(self, qs: np.ndarray, k: int, tau0: Optional[int],
                       metric: str, q_pay: np.ndarray) -> TopKResult:
        """Reference two-stage path (``use_arena=False``): the
        per-segment fan-out ladder finds the final-τ survivor plane,
        then ONE ``_rerank_select`` launch scores and selects — same
        kernel, sort, and tie order as the fused path."""
        return _ladder_topk_rerank(
            self._search_columns, self._payload_rows, self.n_live, self.b,
            self.L, self.block_m, qs, k, tau0, metric, q_pay)

    def _rerank_fn(self, metric: str, kk: int):
        """Fetch (or build) the compiled stage-2 program for this stack —
        same cache, fingerprint, and dead-generation discipline as
        ``_fused_fn`` (the stamp purge there also drops stale re-rank
        programs: they share this index's ``_fused_id`` scope)."""
        serials = self._seg_serials()
        suffix_store = self.backend == "bst" and self.layout == "suffix"
        gen = self._refresh_store().gen if suffix_store else 0
        key = (self.backend, self.layout, self._fused_id, serials, gen,
               "rerank", metric, 0, kk, self.block_m)
        fn = _FUSED_CACHE.get(key)
        if fn is None:
            fn = self._build_rerank(metric, kk)
            while len(_FUSED_CACHE) >= _FUSED_CACHE_CAP:
                _FUSED_CACHE.pop(next(iter(_FUSED_CACHE)))
            _FUSED_CACHE[key] = fn
            _CACHE_STATS["misses"] += 1
        else:
            _CACHE_STATS["hits"] += 1
        return fn

    def _build_rerank(self, metric: str, kk: int):
        """ONE jitted stage-2 program: assemble the (Wp, R) payload plane
        in global column order (hot groups from the corpus argument,
        cold through the staged payload slabs, delta through its
        bucketed plane), score the stage-1 survivors with the exact
        re-rank kernel, and select the k best (score desc, id asc) on
        device — the dist plane never leaves the device between stages.
        Payload bitmaps and gids are the program's first argument
        (``_BoundProgram``), never closed over."""
        block_m = self.block_m

        def score_select(pays, gids, dist, q_pay, delta_pay, delta_gids):
            pays = jnp.concatenate([pays, delta_pay], axis=-1)
            surv = (dist < BIG).astype(jnp.int32)
            scores = ops.exact_rerank(pays, q_pay, surv, metric=metric,
                                      block_m=block_m)
            col_ids = jnp.concatenate([gids, delta_gids])
            return select_topk_scores(scores, dist, col_ids, kk)

        if self.backend == "bst" and self.layout == "suffix":
            store = self._refresh_store()
            plan = store.plan()
            corpus = {"pays": [g.pays_hot for g in plan],
                      "gids": store.gids, "inv": store.order()[1]}

            def run(corpus, dist, q_pay, staged_pays, delta_pay,
                    delta_gids):
                _note_trace()
                pay_parts: List[jnp.ndarray] = []
                for pays_hot, slab in zip(corpus["pays"], staged_pays):
                    parts = [p for p in (pays_hot, slab) if p is not None]
                    pay_parts.append(parts[0] if len(parts) == 1
                                     else jnp.concatenate(parts, axis=-1))
                pays = (jnp.concatenate(pay_parts, axis=-1) if pay_parts
                        else delta_pay[:, :0])
                # the same group-major -> stack-order permutation the
                # dist program applied: pay columns land in dist order
                if corpus["inv"] is not None:
                    pays = _take_columns(pays, corpus["inv"])
                return score_select(pays, corpus["gids"], dist, q_pay,
                                    delta_pay, delta_gids)
            return _BoundProgram(run, corpus)

        # non-suffix configurations: sealed payloads live in the
        # incremental device payload arena, already in stack order
        if self._pay_arena is None:
            self._pay_arena = _PayloadArena(self.payload_words)
        pays0 = self._pay_arena.refresh(self.segments, self._seg_serials())
        if self.backend == "bst":
            gids0 = self._refresh_arena().gids
        elif self.segments:
            gids0 = jnp.concatenate(
                [jnp.asarray(seg.ids.astype(np.int32))
                 for seg in self.segments])
        else:
            gids0 = jnp.zeros((0,), jnp.int32)

        def run(corpus, dist, q_pay, delta_pay, delta_gids):
            _note_trace()
            return score_select(corpus["pays"], corpus["gids"], dist, q_pay,
                                delta_pay, delta_gids)
        return _BoundProgram(run, {"pays": pays0, "gids": gids0})

    def _fused_topk_rerank(self, qs: np.ndarray, k: int,
                           tau0: Optional[int], metric: str,
                           q_pay: np.ndarray) -> TopKResult:
        """The fused two-stage ladder: stage 1 re-runs the kind="dist"
        fused program per τ rung (the survivor plane stays device-side;
        only the two ladder scalars transfer), then stage 2 is ONE
        additional re-rank dispatch for the whole request — regardless
        of segment count (DESIGN.md §10)."""
        m = qs.shape[0]
        n_live = self.n_live
        if n_live == 0:
            return _empty_topk_rerank(m, int(k))
        kk = min(int(k), n_live)
        tau = tau0 if tau0 is not None else tau_for_k(self.b, self.L,
                                                      n_live, kk)
        tau = min(max(int(tau), 0), self.L)
        while True:
            dist, min_surv, ov = self._fused_call("dist", qs, tau)
            if int(min_surv) >= kk or tau >= self.L:
                break
            tau = min(self.L, max(tau + 1, 2 * tau))
        mb = int(dist.shape[0])
        qp = np.zeros((mb, self.payload_words), np.uint32)
        qp[:m] = q_pay
        q_pay_vert = jnp.asarray(np.ascontiguousarray(qp.T))
        nd = len(self._delta_ids)
        if nd:
            delta_pay = self._delta_pay_planes()
            ndb = delta_pay.shape[-1]
            delta_gids = np.zeros(ndb, np.int32)
            delta_gids[:nd] = self._delta_ids.astype(np.int32)
        else:
            delta_pay = jnp.zeros((self.payload_words, 0), jnp.uint32)
            delta_gids = np.zeros(0, np.int32)
        fn = self._rerank_fn(metric, kk)
        _dispatch("rerank")
        with _obs_span("rerank", cat="device", metric=metric, kk=kk):
            if self.backend == "bst" and self.layout == "suffix":
                staged_pays = self._refresh_store().stage_payloads()
                ids, dists, scores = fn(dist, q_pay_vert, staged_pays,
                                        delta_pay,
                                        jnp.asarray(delta_gids))
            else:
                ids, dists, scores = fn(dist, q_pay_vert, delta_pay,
                                        jnp.asarray(delta_gids))
            ids, dists, scores = (np.asarray(ids)[:m],
                                  np.asarray(dists)[:m],
                                  np.asarray(scores)[:m])
        ids, dists, scores = _pad_topk_scores(ids, dists, scores, int(k))
        return TopKResult(ids=jnp.asarray(ids), dists=jnp.asarray(dists),
                          tau=tau, overflow=int(ov),
                          scores=jnp.asarray(scores))


class ShardedSegmentedIndex:
    """S independent segment stacks, one per shard — the dynamic analogue
    of ``build_sharded_bst``'s layout: inserts round-robin across shards
    (matching the static builder's ``id % S`` placement), deletes route
    by id, and queries fan out over every shard's stack before the
    shared shard-merge selection.  Per-shard stacks keep every segment
    rebuild bounded by its shard's slice — a merge touches 1/S of the
    data, the same fault/rebuild granularity as the static sharded
    index.

    Same result contract as ``SegmentedIndex`` (global-id planes,
    ``TopKResult`` with global ids).
    """

    def __init__(self, L: int, b: int, n_shards: int = 4, *,
                 delta_cap: int = 4096, backend: str = "bst",
                 lam: float = 0.5, auto_merge: bool = True,
                 block_m: int = DEFAULT_BLOCK_M, use_arena: bool = True,
                 layout: str = "suffix", hot_bytes: Optional[int] = None,
                 payload_words: Optional[int] = None):
        if n_shards < 1:
            raise ValueError("n_shards must be >= 1")
        self.L, self.b = int(L), int(b)
        self.n_shards = int(n_shards)
        self.block_m = int(block_m)
        self.payload_words = (None if payload_words is None
                              else int(payload_words))
        # a per-stack hot budget: the device budget splits evenly across
        # the independent stacks (each stack places its own blocks)
        per_stack = (None if hot_bytes is None
                     else max(0, int(hot_bytes) // self.n_shards))
        self.shards = [
            SegmentedIndex(L, b, delta_cap=delta_cap, backend=backend,
                           lam=lam, auto_merge=auto_merge, block_m=block_m,
                           use_arena=use_arena, layout=layout,
                           hot_bytes=per_stack,
                           payload_words=self.payload_words)
            for _ in range(self.n_shards)]
        self.n_ids = 0
        # global id -> shard is `id % S`; per-shard local ids are dense,
        # so global id maps to local position `id // S`.
        # durability binding: the top level journals one global-id record
        # per write (shard stacks bind with log_writes=False and only
        # snapshot their own segments).
        self.store: Optional[object] = None

    def insert(self, sketches: np.ndarray,
               payloads: Optional[np.ndarray] = None) -> np.ndarray:
        """Round-robin insert; returns (k,) int64 global ids.  With
        ``payload_words`` set, ``payloads`` carries the rows' (k, Wp)
        uint32 set bitmaps, routed to each shard alongside its rows."""
        sk = np.asarray(sketches, dtype=np.uint8)
        if sk.ndim == 1:
            sk = sk[None, :]
        k = sk.shape[0]
        pay = self.shards[0]._check_payloads(payloads, k)
        new_ids = np.arange(self.n_ids, self.n_ids + k, dtype=np.int64)
        if self.store is not None and k:
            # one global-id WAL record
            self.store.log_insert(new_ids, sk, payloads=pay)
            # scope the routing: a shard's auto-flush checkpoint mid-way
            # through must not let the store truncate the WAL (or seal
            # sibling stacks past this record) before every shard has
            # applied its rows
            self.store.begin_write()
        try:
            for s in range(self.n_shards):
                rows = np.flatnonzero(new_ids % self.n_shards == s)
                if rows.size:
                    self.shards[s].insert(
                        sk[rows],
                        payloads=pay[rows] if pay is not None else None)
        finally:
            if self.store is not None and k:
                self.store.end_write()
        self.n_ids += k
        return new_ids

    def delete(self, ids) -> int:
        """Tombstone global ids; returns the number newly deleted."""
        ids = np.atleast_1d(np.asarray(ids, dtype=np.int64))
        ids = ids[(ids >= 0) & (ids < self.n_ids)]
        if self.store is not None and ids.size:
            self.store.log_delete(ids)
        newly = 0
        for s in range(self.n_shards):
            mine = ids[ids % self.n_shards == s]
            if mine.size:
                newly += self.shards[s].delete(mine // self.n_shards)
        return newly

    def flush(self) -> None:
        for shard in self.shards:
            shard.flush()

    def merge(self) -> int:
        """Size-tiered merge inside every shard's stack; returns total
        merges performed."""
        return sum(shard.maybe_merge() for shard in self.shards)

    def compact(self, min_dead_frac: float = 0.0) -> int:
        return sum(shard.compact(min_dead_frac=min_dead_frac)
                   for shard in self.shards)

    @property
    def n_live(self) -> int:
        return sum(shard.n_live for shard in self.shards)

    def __len__(self) -> int:
        return self.n_live

    def space_bits(self) -> int:
        return sum(shard.space_bits() for shard in self.shards)

    def cost_hint(self, op: str, *, k: Optional[int] = None,
                  tau: Optional[int] = None, rows: int = 1) -> float:
        """Sum of the per-shard-stack cost hints (every stack answers
        every read; writes split their rows round-robin)."""
        per_rows = max(rows // len(self.shards), 1) if op == "write" \
            else rows
        return sum(s.cost_hint(op, k=k, tau=tau, rows=per_rows)
                   for s in self.shards)

    @property
    def tombstones(self) -> int:
        return sum(shard.tombstones for shard in self.shards)

    def space_ledger(self) -> Dict[str, int]:
        led = {"model_bits": 0, "device_bytes": 0, "host_bytes": 0}
        for shard in self.shards:
            for k, v in shard.space_ledger().items():
                led[k] += v
        return led

    def stats(self) -> Dict[str, object]:
        led = self.space_ledger()
        return {"n_ids": self.n_ids, "n_live": self.n_live,
                "tombstones": self.tombstones,
                "n_segments": sum(len(s.segments) for s in self.shards),
                "arena_bytes": sum(
                    s._arena.array_bytes() if s._arena is not None else 0
                    for s in self.shards),
                "device_bytes": led["device_bytes"],
                "host_bytes": led["host_bytes"],
                "shards": [shard.stats() for shard in self.shards]}

    def _search_columns(self, qs: np.ndarray,
                        tau: int) -> Tuple[np.ndarray, np.ndarray, int]:
        """Column-compressed fan-out over every shard's stack: local
        column ids relabel to global via ``gid = local * S + s``.  Each
        shard's stack answers through its own fused arena (one dispatch
        per shard, flat in its segment count — DESIGN.md §6); the
        per-shard merge stays on host like the static sharded path."""
        m = qs.shape[0]
        dists: List[np.ndarray] = []
        col_ids: List[np.ndarray] = []
        overflow = 0
        for s, shard in enumerate(self.shards):
            dist, local_ids, ov = shard._columns(qs, tau)
            dists.append(dist)
            col_ids.append(local_ids * self.n_shards + s)
            overflow += ov
        if not dists:
            return (np.zeros((m, 0), np.int32), np.zeros((0,), np.int64),
                    0)
        return (np.concatenate(dists, axis=1),
                np.concatenate(col_ids), overflow)

    def _global_plane(self, qs: np.ndarray,
                      tau: int) -> Tuple[np.ndarray, int]:
        m = qs.shape[0]
        dist, col_ids, overflow = self._search_columns(qs, tau)
        plane = np.full((m, self.n_ids), BIG_I, np.int32)
        plane[:, col_ids] = dist
        return plane, overflow

    def search_batch(self, qs: np.ndarray, tau: int,
                     explain: bool = False) -> SegmentedSearchResult:
        """(m, L) uint8 queries -> global (m, n_ids) mask/dist planes.
        ``explain=True`` appends the ``QueryExplain`` record."""
        qs = np.asarray(qs, dtype=np.uint8)
        if qs.ndim == 1:
            qs = qs[None, :]
        if explain:
            rec = _ExplainRecorder()
            dist, col_ids, overflow = rec.wrap(self._search_columns)(
                qs, int(tau))
            plane = np.full((qs.shape[0], self.n_ids), BIG_I, np.int32)
            plane[:, col_ids] = dist
            res = SegmentedSearchResult(mask=plane <= tau, dist=plane,
                                        overflow=overflow)
            return res, rec.finish(
                op="search", backend="sharded-stacks",
                n_queries=qs.shape[0], n_live=self.n_live, k=None,
                tau0=int(tau), tau_final=int(tau), rerank=None)
        plane, overflow = self._global_plane(qs, int(tau))
        return SegmentedSearchResult(mask=plane <= tau, dist=plane,
                                     overflow=overflow)

    def search(self, q: np.ndarray, tau: int,
               explain: bool = False) -> SegmentedSearchResult:
        out = self.search_batch(np.asarray(q)[None], tau, explain=explain)
        res, ex = out if explain else (out, None)
        res = SegmentedSearchResult(mask=res.mask[0], dist=res.dist[0],
                                    overflow=res.overflow)
        return (res, ex) if explain else res

    def _payload_rows(self) -> np.ndarray:
        """(R, Wp) uint32 payload rows in the global column order of
        ``_search_columns`` (shard 0's columns, then shard 1's, ...)."""
        parts = [shard._payload_rows() for shard in self.shards]
        return np.concatenate(parts, axis=0)

    def topk_batch(self, qs: np.ndarray, k: int,
                   tau0: Optional[int] = None, *,
                   rerank: Optional[str] = None,
                   q_payloads: Optional[np.ndarray] = None,
                   explain: bool = False) -> TopKResult:
        """Exact global kNN: per-shard column-compressed fan-out on one
        shared τ ladder (same contract as ``SegmentedIndex.topk_batch``,
        including the two-stage ``rerank=`` contract — stage 2 is still
        ONE re-rank dispatch over the merged survivor plane, never one
        per shard).  ``explain=True`` appends the ``QueryExplain``
        record (bit-identical result)."""
        qs = np.asarray(qs, dtype=np.uint8)
        if qs.ndim == 1:
            qs = qs[None, :]
        rec = _ExplainRecorder() if explain else None
        columns_fn = (rec.wrap(self._search_columns) if explain
                      else self._search_columns)
        if rerank is not None:
            q_pay = self.shards[0]._check_rerank(rerank, q_payloads,
                                                 qs.shape[0])
            res = _ladder_topk_rerank(
                columns_fn, self._payload_rows, self.n_live,
                self.b, self.L, self.block_m, qs, k, tau0, rerank, q_pay)
        else:
            if q_payloads is not None:
                raise ValueError("q_payloads supplied without rerank=")
            res = _ladder_topk(columns_fn, self.n_live, self.b,
                               self.L, qs, k, tau0)
        if not explain:
            return res
        return res, rec.finish(
            op="topk", backend="sharded-stacks", n_queries=qs.shape[0],
            n_live=self.n_live, k=int(k),
            tau0=None if tau0 is None else int(tau0),
            tau_final=int(res.tau), rerank=rerank)

    def topk(self, q: np.ndarray, k: int,
             tau0: Optional[int] = None, *,
             rerank: Optional[str] = None,
             q_payloads: Optional[np.ndarray] = None,
             explain: bool = False) -> TopKResult:
        qp = None
        if q_payloads is not None:
            qp = np.asarray(q_payloads, np.uint32)
            if qp.ndim == 1:
                qp = qp[None, :]
        out = self.topk_batch(np.asarray(q)[None], k, tau0=tau0,
                              rerank=rerank, q_payloads=qp,
                              explain=explain)
        res, ex = out if explain else (out, None)
        res = TopKResult(ids=res.ids[0], dists=res.dists[0], tau=res.tau,
                         overflow=res.overflow,
                         scores=(None if res.scores is None
                                 else res.scores[0]))
        return (res, ex) if explain else res
