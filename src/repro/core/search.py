"""Level-synchronous similarity search over a SketchIndex (paper Alg. 1,
re-derived for TPU — see DESIGN.md §2).

The paper's recursive DFS visits one node at a time and prunes a subtree
when the accumulated Hamming distance exceeds τ.  Here the *whole frontier
at level ℓ* is a fixed-capacity array of (node id, distance) pairs; one
step expands every node's ≤ 2^b children with one vectorized ``children``
call, masks out children with dist > τ (the paper's pruning), and
compacts survivors with a cumsum-scatter.  The sparse tail is *not*
traversed: pruned ℓ_s-subtries get a +∞ base distance and the Pallas
verify kernel streams every collapsed suffix path in one masked scan —
pruning becomes masking, pointer work becomes bandwidth.

Multi-query is the first-class fast path (DESIGN.md §3): the batched
searcher is NOT a vmap of the single-query trace but a natively batched
``_search_trace_batch`` over a (m, cap) 2D frontier — one shared
``children()`` gather per level for the whole batch, per-query
cumsum-scatter compaction, a batched scatter-min onto (m, t_root)
base-distance planes, and the query-tiled ``sparse_verify_batch`` Pallas
kernel, which streams the collapsed-path array from HBM ⌈m/BLOCK_M⌉
times instead of m.

Exact distances are first-class: the traversal accumulates per-node
Hamming distances anyway, and the verify kernel computes the exact total
before thresholding, so ``SearchResult.dist`` carries the exact distance
of every id inside the τ-ball (BIG elsewhere) at zero extra passes.
``topk`` builds k-nearest-neighbor search on top: a τ-escalation ladder
seeded from the cost model's expected-candidate estimate, followed by a
``jax.lax.top_k`` selection over the distance vector.

Static shapes: frontier capacities come from the cost model
(min(t_ℓ, sigs(b,ℓ,τ), cap_max)).  Exceeding ``cap_max`` is detected and
reported; the host wrapper retries on a doubled ladder.  Compiled
searchers live in a process-level cache keyed on (index, τ, caps) so the
ladder and repeated serving calls never re-jit the common case.
"""

from __future__ import annotations

import functools
from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .bst import BIG, SketchIndex
from .cost_model import frontier_capacities, tau_for_k
from .hamming import pack_vertical_jax
from ..kernels import ops
from ..kernels.hamming_kernel import DEFAULT_BLOCK_M

CAP_MAX_DEFAULT = 1 << 17
LADDER_CAP_MAX = 1 << 22


def bucket_m(m: int) -> int:
    """Power-of-two query-batch shape bucket: the smallest 2^j >= m.
    Batched searchers pad the query axis up to this bucket (and slice the
    results back), so a stream of arbitrary client batch sizes touches
    only O(log m_max) compiled traces instead of one per distinct m."""
    if m < 1:
        raise ValueError("batch must contain at least one query")
    return 1 << (m - 1).bit_length()


def _pad_rows(qs: jnp.ndarray, bucket: int) -> jnp.ndarray:
    """Pad the leading (query) axis up to ``bucket`` rows by repeating the
    last row — a real query, so pad rows can never overflow a frontier
    harder than the rows already present (zeros could)."""
    m = qs.shape[0]
    pad = jnp.broadcast_to(qs[-1:], (bucket - m,) + qs.shape[1:])
    return jnp.concatenate([qs, pad], axis=0)


class SearchResult(NamedTuple):
    mask: jnp.ndarray        # (n,) bool — ids within τ of the query
    dist: jnp.ndarray        # (n,) int32 — exact distance where mask, BIG off
    overflow: jnp.ndarray    # int32 — dropped frontier entries (0 = exact)
    traversed: jnp.ndarray   # int32 — Σ frontier sizes (paper's t_tra)


class TopKResult(NamedTuple):
    ids: jnp.ndarray         # (k,) int32 — ascending (distance, id); -1 pad
    dists: jnp.ndarray       # (k,) int32 — exact distances; BIG on pad
    tau: int                 # final rung of the τ-escalation ladder
    overflow: int            # dropped frontier entries (0 = provably exact)
    scores: jnp.ndarray | None = None  # (k,) f32 exact re-rank scores —
    #   descending (score, -id); -1.0 pad.  None on sketch-only requests;
    #   when set, ids/dists re-order to score order (DESIGN.md §10).


def _compact(ids: jnp.ndarray, dists: jnp.ndarray, valid: jnp.ndarray,
             capacity: int):
    """Stable masked compaction into a fixed-size frontier."""
    total = valid.sum(dtype=jnp.int32)      # 0 for an empty frontier
    pos = jnp.cumsum(valid) - 1
    slot = jnp.where(valid & (pos < capacity), pos, capacity)
    out_ids = jnp.zeros((capacity + 1,), jnp.int32).at[slot].set(ids, mode="drop")
    out_dists = jnp.full((capacity + 1,), BIG, jnp.int32).at[slot].set(dists, mode="drop")
    kept = jnp.minimum(total, capacity)
    out_valid = jnp.arange(capacity + 1, dtype=jnp.int32) < kept
    overflow = jnp.maximum(total - capacity, 0)
    return out_ids[:capacity], out_dists[:capacity], out_valid[:capacity], overflow


def _compact_batch(ids: jnp.ndarray, dists: jnp.ndarray, valid: jnp.ndarray,
                   capacity: int):
    """Row-wise stable masked compaction: (m, K) candidates -> (m,
    capacity) frontier.  Each query compacts independently — ``_compact``
    once per row (``lax.map``): the TPU compiler emits one 2-D (row,
    slot) scatter as code that grows with m and the frontier width, a
    1-D scatter in a loop as one body.  Overflow is counted per query."""
    return jax.lax.map(lambda row: _compact(*row, capacity),
                       (ids, dists, valid))


def _leaf_live(index: SketchIndex, id_live: jnp.ndarray) -> jnp.ndarray:
    """(n,) bool id liveness -> (t_L,) bool leaf liveness: a leaf is live
    iff at least one live id maps to it (duplicates share a leaf).  Used
    by the dynamic segmented index (DESIGN.md §4) to feed the tombstone
    mask into the verify stage."""
    t_L = index.t[index.L]
    return jnp.zeros((t_L,), bool).at[index.id_leaf].max(id_live, mode="drop")


def _search_trace(index: SketchIndex, q: jnp.ndarray, *, tau: int,
                  caps: Tuple[int, ...],
                  id_live: jnp.ndarray | None = None) -> SearchResult:
    """Traced search body.  ``q``: (L,) uint8/int32 query sketch;
    ``id_live``: optional (n,) bool tombstone mask — dead ids never
    survive and fully-dead leaves are pruned at the verify stage."""
    q = q.astype(jnp.int32)
    live = _leaf_live(index, id_live) if id_live is not None else None
    ids = jnp.zeros((1,), jnp.int32)
    dists = jnp.zeros((1,), jnp.int32)
    valid = jnp.ones((1,), bool)
    overflow = jnp.int32(0)
    traversed = jnp.int32(1)

    depth = len(index.levels)
    for lev in range(1, depth + 1):
        enc = index.levels[lev - 1]
        c_ids, c_labels, c_exists = enc.children(ids)            # (F, A)
        c_dists = dists[:, None] + (c_labels != q[lev - 1]).astype(jnp.int32)
        c_valid = valid[:, None] & c_exists & (c_dists <= tau)
        ids, dists, valid, ov = _compact(
            c_ids.reshape(-1), c_dists.reshape(-1), c_valid.reshape(-1),
            caps[lev])
        overflow = overflow + ov
        traversed = traversed + valid.sum(dtype=jnp.int32)

    if index.tail is not None:
        tail = index.tail
        # scatter frontier distances onto ℓ_s roots (+∞ = pruned subtrie)
        base_root = jnp.full((tail.t_root,), BIG, jnp.int32)
        safe_ids = jnp.where(valid, ids, 0)
        base_root = base_root.at[safe_ids].min(
            jnp.where(valid, dists, BIG), mode="drop")
        base_leaf = base_root[tail.leaf_root]                     # (t_L,)
        if tail.suffix_len > 0:
            q_sfx = pack_vertical_jax(q[index.ls:][None], index.b)[0]  # (b, W)
            hit, leaf_dist = ops.sparse_verify(tail.paths_vert, q_sfx,
                                               base_leaf, tau=tau, live=live)
            survive = hit > 0
        else:
            if live is not None:
                base_leaf = jnp.where(live, base_leaf, BIG)
            survive = base_leaf <= tau
            leaf_dist = base_leaf
    else:
        # no collapsed tail (LOUDS/FST baselines): frontier is at level L;
        # scatter-min the frontier distances straight onto the leaves
        t_L = index.t[index.L]
        safe_ids = jnp.where(valid, ids, 0)
        leaf_dist = jnp.full((t_L,), BIG, jnp.int32).at[safe_ids].min(
            jnp.where(valid, dists, BIG), mode="drop")
        if live is not None:
            leaf_dist = jnp.where(live, leaf_dist, BIG)
        survive = leaf_dist <= tau

    mask = survive[index.id_leaf]
    if id_live is not None:
        mask = mask & id_live
    dist = jnp.where(mask, leaf_dist[index.id_leaf], BIG)
    return SearchResult(mask=mask, dist=dist, overflow=overflow,
                        traversed=traversed)


def _traverse_frontier_batch(index: SketchIndex, qs: jnp.ndarray, *,
                             tau: int, caps: Tuple[int, ...],
                             level_widths: Optional[list] = None):
    """The shared 2D-frontier descent (levels 1..depth) of the natively
    batched searcher: ``qs`` is (m, L) int32 and the level-ℓ frontier a
    (m, cap_ℓ) array compacted per query — one ``children()`` gather per
    level for the whole batch.  Returns the final frontier
    ``(ids, dists, valid)`` (each (m, cap_depth)) plus per-query
    ``overflow``/``traversed`` (m,) int32.  Reused by the fused
    segment-arena program (DESIGN.md §6), which stops here and scatters
    every segment's frontier onto one concatenated root plane.

    ``level_widths``: optional list the per-level live frontier widths
    ((m,) int32 each) are appended to during tracing — the explain
    path's frontier-width sampler (DESIGN.md §11) stacks them into its
    per-trie-level report; default callers trace the identical graph
    (the sum already feeds ``traversed``)."""
    m = qs.shape[0]
    ids = jnp.zeros((m, 1), jnp.int32)
    dists = jnp.zeros((m, 1), jnp.int32)
    valid = jnp.ones((m, 1), bool)
    overflow = jnp.zeros((m,), jnp.int32)
    traversed = jnp.ones((m,), jnp.int32)

    depth = len(index.levels)
    for lev in range(1, depth + 1):
        enc = index.levels[lev - 1]
        cap = ids.shape[1]
        c_ids, c_labels, c_exists = enc.children(ids.reshape(-1))  # (m·cap, A)
        A = c_ids.shape[-1]
        c_ids = c_ids.reshape(m, cap, A)
        c_labels = c_labels.reshape(m, cap, A)
        c_exists = c_exists.reshape(m, cap, A)
        q_char = qs[:, lev - 1][:, None, None]
        c_dists = dists[:, :, None] + (c_labels != q_char).astype(jnp.int32)
        c_valid = valid[:, :, None] & c_exists & (c_dists <= tau)
        ids, dists, valid, ov = _compact_batch(
            c_ids.reshape(m, -1), c_dists.reshape(m, -1),
            c_valid.reshape(m, -1), caps[lev])
        overflow = overflow + ov
        width = valid.sum(axis=1, dtype=jnp.int32)
        if level_widths is not None:
            level_widths.append(width)
        traversed = traversed + width
    return ids, dists, valid, overflow, traversed


def scatter_root_plane(ids: jnp.ndarray, vals: jnp.ndarray,
                       valid: jnp.ndarray, m: int,
                       t_root: int) -> jnp.ndarray:
    """Scatter one segment's final frontier onto its (m, t_root) slice of
    the concatenated ℓ_s-root base plane (the fused programs' traversal →
    verify hand-off, DESIGN.md §6/§7): per-root minimum of ``vals`` over
    the valid frontier entries, BIG where the traversal pruned the root.
    The full-length arena passes ``vals = 0`` (reached/pruned only — its
    columns recompute the prefix inside the XOR); the suffix store passes
    ``vals = dists``, the traversal's exact prefix distances, which the
    suffix verify adds to complete the full-length Hamming distance bit
    for bit.  The scratch slot ``t_root`` absorbs ``mode="drop"`` pads
    and is sliced off."""
    def one_row(row):
        ids_r, vals_r, valid_r = row
        return jnp.full((t_root + 1,), BIG, jnp.int32).at[
            jnp.where(valid_r, ids_r, t_root)].min(
                jnp.where(valid_r, vals_r, BIG), mode="drop")[:t_root]
    # one 1-D scatter per query row: the TPU compiler emits a 2-D
    # (row, id) scatter as straight-line code that grows with the
    # frontier width (see ``bst`` on index layouts)
    return jax.lax.map(one_row, (ids, vals, valid))


def select_topk_columns(dist: jnp.ndarray, col_ids: jnp.ndarray, k: int):
    """Traced k-smallest selection over labeled column planes: the
    on-device counterpart of ``distributed_search.topk_from_dists``.

    dist: (m, R) int32 — one distance per (query, column), BIG on
    non-results; col_ids: (R,) int32 global labels per column; returns
    ((m, k) int32 ids, (m, k) int32 dists), each row ascending by
    (distance, label), so tie order matches the host selection bit for
    bit; BIG lanes come back as (-1, BIG) pads.
    Requires k <= R (the caller clamps k to the column count).

    Two lowerings, identical bits: small k runs ``k`` unrolled min /
    tie-break-min reduction passes, large k one lexicographic
    ``lax.sort`` with ``num_keys=2``.  The TPU compiler spends tens of
    seconds on a full-plane sort at R in the millions, the reduction
    passes compile in about two."""
    m, R = dist.shape
    labels = jnp.broadcast_to(col_ids.astype(jnp.int32)[None, :], (m, R))
    if k <= _ITER_SELECT_MAX_K:
        d = dist.astype(jnp.int32)
        picks = []
        for _ in range(k):
            mn = d.min(-1, keepdims=True)
            tie = d == mn
            lab = jnp.where(tie, labels,
                            jnp.int32(2 ** 31 - 1)).min(-1, keepdims=True)
            picks.append((mn[:, 0], lab[:, 0]))
            # picked lanes rise past BIG, so BIG lanes still read as pads
            d = jnp.where(tie & (labels == lab), jnp.int32(BIG + 1), d)
        d_k = jnp.stack([p[0] for p in picks], -1)
        l_k = jnp.stack([p[1] for p in picks], -1)
    else:
        d_sorted, l_sorted = jax.lax.sort((dist, labels), dimension=-1,
                                          num_keys=2)
        d_k, l_k = d_sorted[:, :k], l_sorted[:, :k]
    return jnp.where(d_k < BIG, l_k, -1), jnp.minimum(d_k, BIG)


# crossover between the unrolled reduction selections and the full
# sort: each reduction pick costs a few plane traversals, the sort ~90
# picks' worth on CPU and a far longer TPU compile — stay iterative
# through every serving-sized k
_ITER_SELECT_MAX_K = 32


def select_topk_scores(scores: jnp.ndarray, dist: jnp.ndarray,
                       col_ids: jnp.ndarray, k: int):
    """Traced k-*largest* selection over re-ranked column planes.

    scores: (m, R) float32 exact re-rank scores, -1.0 sentinel on
    non-survivor lanes; dist: (m, R) int32 Hamming distances (carried
    along, BIG off-survivor); col_ids: (R,) int32 global labels; returns
    ((m, k) ids, (m, k) dists, (m, k) f32 scores), each row descending
    by (score, -label) — ties at equal score break toward the smaller
    id, matching the host brute-force ordering bit for bit.

    The sort key is the *bit pattern* of the score: IEEE-754 floats in
    [0, 1] are monotone under an int32 bitcast and the -1.0 sentinel's
    sign bit makes its bitcast negative, so ordering on the bitcast
    needs no float comparator and keeps exact tie semantics.

    Two lowerings, identical bits: small k runs ``k`` unrolled
    max/argmin reduction passes (memory-bound — roughly 5x cheaper than
    a full-plane sort on CPU), large k falls back to one lexicographic
    ``lax.sort``.  Requires k <= R."""
    m, R = scores.shape
    key = jax.lax.bitcast_convert_type(scores.astype(jnp.float32),
                                       jnp.int32)
    labels = jnp.broadcast_to(col_ids.astype(jnp.int32)[None, :], (m, R))
    sc = scores.astype(jnp.float32)
    if k <= _ITER_SELECT_MAX_K:
        picks = []
        for _ in range(k):
            mx = key.max(-1, keepdims=True)
            tie = key == mx
            lab = jnp.where(tie, labels,
                            jnp.int32(2 ** 31 - 1)).min(-1, keepdims=True)
            pick = tie & (labels == lab)
            picks.append((lab[:, 0],
                          jnp.where(pick, dist, -1).max(-1),
                          jnp.where(pick, sc, -jnp.inf).max(-1)))
            key = jnp.where(pick, jnp.int32(-2 ** 31), key)
        l_k = jnp.stack([p[0] for p in picks], -1)
        d_k = jnp.stack([p[1] for p in picks], -1)
        s_k = jnp.stack([p[2] for p in picks], -1)
    else:
        _, l_sorted, d_sorted, s_sorted = jax.lax.sort(
            (-key, labels, dist, sc), dimension=-1, num_keys=2)
        s_k, l_k, d_k = s_sorted[:, :k], l_sorted[:, :k], d_sorted[:, :k]
    hit = s_k >= 0
    return (jnp.where(hit, l_k, -1), jnp.where(hit, d_k, BIG),
            jnp.where(hit, s_k, jnp.float32(-1.0)))


def _search_trace_batch(index: SketchIndex, qs: jnp.ndarray, *, tau: int,
                        caps: Tuple[int, ...],
                        block_m: int = DEFAULT_BLOCK_M,
                        id_live: jnp.ndarray | None = None) -> SearchResult:
    """Natively batched search body: ``qs`` is (m, L) and the frontier is
    a (m, cap) 2D array compacted per query.  Each level issues ONE
    shared ``children()`` gather over the flattened (m·cap,) frontier
    instead of m separate traces (``_traverse_frontier_batch``), the
    tail scatter-min lands on a (m, t_root) base-distance plane, and the
    sparse layer runs through the query-tiled batch verify kernel — the
    collapsed-path array is streamed ⌈m/block_m⌉ times instead of m.
    Per-query masks, exact distances, and overflow counts are
    bit-identical to ``_search_trace`` (compaction is row-independent).
    ``id_live``: optional (n,) bool tombstone mask shared by every query
    (DESIGN.md §4)."""
    qs = qs.astype(jnp.int32)
    live = _leaf_live(index, id_live) if id_live is not None else None
    m = qs.shape[0]
    ids, dists, valid, overflow, traversed = _traverse_frontier_batch(
        index, qs, tau=tau, caps=caps)

    if index.tail is not None:
        tail = index.tail
        # batched scatter of frontier distances onto per-query ℓ_s root
        # planes (+∞ = pruned subtrie)
        base_root = scatter_root_plane(ids, dists, valid, m, tail.t_root)
        base_leaf = base_root[:, tail.leaf_root]                  # (m, t_L)
        if tail.suffix_len > 0:
            q_sfx = pack_vertical_jax(qs[:, index.ls:], index.b)  # (m, b, W)
            q_sfx = jnp.transpose(q_sfx, (1, 2, 0))               # (b, W, m)
            hit, leaf_dist = ops.sparse_verify_batch(
                tail.paths_vert, q_sfx, base_leaf, tau=tau, live=live,
                block_m=block_m)
            survive = hit > 0
        else:
            if live is not None:
                base_leaf = jnp.where(live[None, :], base_leaf, BIG)
            survive = base_leaf <= tau
            leaf_dist = base_leaf
    else:
        # no collapsed tail (LOUDS/FST baselines): frontier is at level L
        leaf_dist = scatter_root_plane(ids, dists, valid, m,
                                       index.t[index.L])
        if live is not None:
            leaf_dist = jnp.where(live[None, :], leaf_dist, BIG)
        survive = leaf_dist <= tau

    mask = survive[:, index.id_leaf]
    if id_live is not None:
        mask = mask & id_live[None, :]
    dist = jnp.where(mask, leaf_dist[:, index.id_leaf], BIG)
    return SearchResult(mask=mask, dist=dist, overflow=overflow,
                        traversed=traversed)


# ---------------------------------------------------------------------------
# compiled-searcher cache
# ---------------------------------------------------------------------------

# key: (id(index), tau, caps, block_m-or-None) -> (index, jitted fn).  The
# last slot is None for the single-query searcher and the verify kernel's
# query-tile size for the natively batched one.  The index is
# held strongly in the value so its id can never be recycled while the
# entry lives; serving processes hold few indexes, so this pins O(1) of
# extra memory per cached rung.  FIFO-bounded so sweeps over many
# (index, τ, cap) combinations (benchmarks) cannot grow without limit.
_SEARCHER_CACHE: Dict[tuple, tuple] = {}
_SEARCHER_CACHE_CAP = 128
_CACHE_STATS = {"hits": 0, "misses": 0, "traces": 0}


def _note_trace() -> None:
    """Call from inside a jitted body: runs only while jit traces, so it
    counts real traces (including per-shape re-specialization of one
    cached fn).  Shared by the searchers here and the fused arena
    programs (``core.segments``), so ``searcher_cache_info()['traces']``
    stays the one number that must freeze once every shape bucket is
    warm."""
    _CACHE_STATS["traces"] += 1


def _pin_cache_get(cache: dict, cap: int, key: tuple, obj, build):
    """id-keyed bounded cache shared by the single- and multi-index
    searchers: the value pins ``obj`` so its id can never be recycled
    while the entry lives; FIFO-evicts beyond ``cap``.  Returns
    (cached_value, hit)."""
    entry = cache.get(key)
    if entry is not None and entry[0] is obj:
        return entry[1], True
    value = build()
    while len(cache) >= cap:
        cache.pop(next(iter(cache)))  # FIFO evict
    cache[key] = (obj, value)
    return value, False


def searcher_cache_info() -> Dict[str, int]:
    """Process-level cache counters.  ``misses`` counts Python-cache
    misses (one per new (index, τ, caps, block_m, with_live) key);
    ``traces`` counts actual jit traces, including jit's own per-shape
    re-specialization — with the power-of-two m-bucketing this stops
    growing after one warmup per bucket, even under a varying-m query
    stream."""
    return {"hits": _CACHE_STATS["hits"], "misses": _CACHE_STATS["misses"],
            "traces": _CACHE_STATS["traces"], "size": len(_SEARCHER_CACHE)}


def clear_searcher_cache() -> None:
    _SEARCHER_CACHE.clear()
    _CACHE_STATS["hits"] = 0
    _CACHE_STATS["misses"] = 0
    _CACHE_STATS["traces"] = 0


def get_searcher(index: SketchIndex, tau: int,
                 cap_max: int = CAP_MAX_DEFAULT, *, batch: bool = False,
                 block_m: int = DEFAULT_BLOCK_M, with_live: bool = False):
    """Cached compiled searcher for this (index, τ, caps).  ``batch=False``
    returns ``fn(q: (L,)) -> SearchResult``; ``batch=True`` the natively
    batched ``fn(qs: (m, L)) -> SearchResult`` with a leading query axis
    (2D-frontier traversal + the query-tiled verify kernel at tile size
    ``block_m``).  ``with_live=True`` compiles the tombstone-aware variant
    ``fn(q_or_qs, id_live: (n,) bool) -> SearchResult`` (dead ids never
    survive; the liveness bitmap is a *traced* argument, so flipping
    tombstones never re-jits — the dynamic segmented index's fast path,
    DESIGN.md §4).

    Batched searchers bucket the query axis: ``qs`` is padded up to
    ``bucket_m(m)`` rows (repeating the last query) before the jitted
    trace and the results are sliced back to m, so any client batch size
    ``m <= bucket`` reuses one compiled trace per power-of-two bucket —
    variable-size serving traffic stops re-jitting after one warmup per
    bucket (DESIGN.md §5)."""
    caps = frontier_capacities(index.t, index.b, tau, cap_max)
    key = (id(index), tau, caps, block_m if batch else None, with_live)

    traced = _note_trace

    def build():
        if batch and with_live:
            @jax.jit
            def run(qs, id_live):
                traced()
                return _search_trace_batch(index, qs, tau=tau, caps=caps,
                                           block_m=block_m, id_live=id_live)
        elif batch:
            @jax.jit
            def run(qs):
                traced()
                return _search_trace_batch(index, qs, tau=tau, caps=caps,
                                           block_m=block_m)
        elif with_live:
            @jax.jit
            def run(q, id_live):
                traced()
                return _search_trace(index, q, tau=tau, caps=caps,
                                     id_live=id_live)
        else:
            @jax.jit
            def run(q):
                traced()
                return _search_trace(index, q, tau=tau, caps=caps)
        return run

    fn, hit = _pin_cache_get(_SEARCHER_CACHE, _SEARCHER_CACHE_CAP, key,
                             index, build)
    _CACHE_STATS["hits" if hit else "misses"] += 1
    if not batch:
        return fn

    def bucketed(qs, *rest):
        qs = jnp.asarray(qs)
        m = qs.shape[0]
        mb = bucket_m(m)
        if mb == m:
            return fn(qs, *rest)
        res = fn(_pad_rows(qs, mb), *rest)
        return SearchResult(*(a[:m] for a in res))

    return bucketed


def make_searcher(index: SketchIndex, tau: int,
                  cap_max: int = CAP_MAX_DEFAULT):
    """Compile (or fetch from the process cache) a single-query searcher
    for this (index, τ).  Returns ``fn(q) -> SearchResult``."""
    return get_searcher(index, tau, cap_max, batch=False)


def make_batch_searcher(index: SketchIndex, tau: int,
                        cap_max: int = CAP_MAX_DEFAULT,
                        block_m: int = DEFAULT_BLOCK_M):
    """Natively batched searcher: (m, L) queries -> SearchResult with a
    leading query axis.  Unlike a vmap of the single-query trace, the
    whole batch shares one traversal (one children() gather per level)
    and one query-tiled verify scan of the collapsed-path array.  The
    query axis is padded to the power-of-two ``bucket_m(m)`` internally
    (results sliced back), so varying client batch sizes reuse one
    compiled trace per bucket."""
    return get_searcher(index, tau, cap_max, batch=True, block_m=block_m)


# ---------------------------------------------------------------------------
# host wrappers: overflow ladder + top-k engine
# ---------------------------------------------------------------------------

def search(index: SketchIndex, q: np.ndarray, tau: int,
           cap_max: int = CAP_MAX_DEFAULT,
           max_cap: int = LADDER_CAP_MAX) -> SearchResult:
    """Host convenience wrapper with the overflow ladder: retries with a
    doubled capacity until the traversal is exact (or ``max_cap`` is hit).
    ``q``: (L,) uint8 -> ``SearchResult`` over the index's n ids.  Every
    rung comes from the process-level searcher cache, so a repeated
    (index, τ) call never re-jits."""
    q = jnp.asarray(q)
    while True:
        res = get_searcher(index, tau, cap_max)(q)
        if int(res.overflow) == 0 or cap_max >= max_cap:
            return res
        cap_max *= 2


def _tau_for_k(index: SketchIndex, k: int) -> int:
    """Ladder seed: the cost model's shared uniform-DB estimator
    (``cost_model.tau_for_k``) over this index's (b, L, n)."""
    return tau_for_k(index.b, index.L, index.n, k)


@functools.lru_cache(maxsize=_SEARCHER_CACHE_CAP)
def _topk_select(k: int):
    """Jitted batched (dist (m, n) -> (dists, ids) (m, k)) k-smallest
    selection.  ``lax.top_k`` breaks ties toward the lower index, so equal
    distances order by id.  Keyed on ``k`` alone (n only shapes the traced
    input, and jit re-specializes per shape anyway) and bounded like
    ``_SEARCHER_CACHE`` so k-sweeps cannot grow it without limit."""
    def sel(dist):
        neg, idx = jax.lax.top_k(-dist, k)
        return -neg, idx.astype(jnp.int32)

    return jax.jit(jax.vmap(sel))


def _pad_topk(dists: np.ndarray, ids: np.ndarray, k: int) -> Tuple[np.ndarray, np.ndarray]:
    kk = ids.shape[-1]
    if kk == k:
        return dists, ids
    pad = [(0, 0)] * (ids.ndim - 1) + [(0, k - kk)]
    return (np.pad(dists, pad, constant_values=int(BIG)),
            np.pad(ids, pad, constant_values=-1))


def topk(index: SketchIndex, q: np.ndarray, k: int,
         tau0: int | None = None, cap_max: int = CAP_MAX_DEFAULT,
         max_cap: int = LADDER_CAP_MAX,
         block_m: int = DEFAULT_BLOCK_M) -> TopKResult:
    """Exact k-nearest-neighbor search: run the compiled range searcher on
    a τ-escalation ladder until ≥ k ids survive, then select the k smallest
    exact distances (ties broken by id).  ``q``: (L,) uint8 ->
    ``TopKResult`` with (k,) int32 ids/dists.

    Correctness: once ``mask.sum() >= k`` at threshold τ with zero frontier
    overflow, every excluded id has distance > τ ≥ the k-th smallest — so
    the selection over ``dist`` (exact inside the ball, BIG outside) is
    globally exact.  A nonzero ``TopKResult.overflow`` (only possible once
    the capacity ladder saturates ``max_cap``) marks a potentially partial
    result.  If ``k > n`` the result is padded with (-1, BIG).
    """
    res = topk_batch(index, jnp.asarray(q)[None], k, tau0=tau0,
                     cap_max=cap_max, max_cap=max_cap, block_m=block_m)
    return TopKResult(ids=res.ids[0], dists=res.dists[0], tau=res.tau,
                      overflow=res.overflow)


def topk_batch(index: SketchIndex, qs: np.ndarray, k: int,
               tau0: int | None = None, cap_max: int = CAP_MAX_DEFAULT,
               max_cap: int = LADDER_CAP_MAX,
               block_m: int = DEFAULT_BLOCK_M) -> TopKResult:
    """Batched ``topk``: (m, L) queries -> (m, k) ids/dists.  One ladder
    for the whole batch — τ escalates until every query has ≥ k survivors,
    so all queries share the same compiled searcher (the natively batched
    2D-frontier trace + query-tiled verify kernel)."""
    qs = jnp.asarray(qs)
    kk = min(k, index.n)
    tau = tau0 if tau0 is not None else _tau_for_k(index, kk)
    tau = min(max(tau, 0), index.L)
    # the escalated capacity carries across tau rungs: a larger tau-ball
    # can only need at least as much frontier as the one that overflowed
    cap = cap_max
    while True:
        while True:
            res = get_searcher(index, tau, cap, batch=True,
                               block_m=block_m)(qs)
            overflow = int(res.overflow.sum())
            if overflow == 0 or cap >= max_cap:
                break
            cap *= 2
        if int(res.mask.sum(axis=1).min()) >= kk or tau >= index.L:
            break
        tau = min(index.L, max(tau + 1, 2 * tau))
    # bucket the selection's query axis too: BIG pad rows select (-1, BIG)
    # lanes that the final slice drops, so selection never re-traces per m
    m, mb = res.dist.shape[0], bucket_m(res.dist.shape[0])
    dist_in = res.dist if mb == m else jnp.concatenate(
        [res.dist, jnp.full((mb - m, res.dist.shape[1]), BIG, jnp.int32)])
    dists, ids = _topk_select(kk)(dist_in)
    dists, ids = _pad_topk(np.asarray(dists)[:m], np.asarray(ids)[:m], k)
    # BIG lanes are non-results (possible when the capacity ladder
    # saturated with overflow): mask their arbitrary ids to the pad value
    ids = np.where(dists >= int(BIG), -1, ids)
    return TopKResult(ids=jnp.asarray(ids), dists=jnp.asarray(dists),
                      tau=tau, overflow=overflow)
