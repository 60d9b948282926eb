"""Pallas TPU kernels: query-tiled vertical-format Hamming scans.

This is the measured hot spot of the paper's pipeline — the sparse-layer
path scan and the multi-index verification step both reduce to "stream a
packed sketch database past a query and popcount the XOR".  The workload
is integer and element-wise: it never touches the MXU, so the kernel is a
pure VPU streaming kernel and its roofline is the HBM bandwidth term.

Layout (see ref.py): the database is *fully vertical* — (b, W, n) uint32
with the sketch index on the last (lane) axis.  A block of
(b, W, BLOCK_N) therefore occupies b·W·BLOCK_N·4 bytes of VMEM and
vectorizes the whole XOR/OR/popcount chain across 128-sketch lanes with
the (tiny) b·W plane/word axes on sublanes.

Query tiling (the batched-serving optimisation): a grid cell loads one
(b, W, BLOCK_N) database block ONCE and plays a whole BLOCK_M-query tile
against it, emitting (BLOCK_M, BLOCK_N) output planes.  HBM traffic for
the database drops from m streams (one per query, the naive vmap) to
⌈m/BLOCK_M⌉ streams, and the arithmetic intensity of the scan scales
~linearly with BLOCK_M until the (BLOCK_M, BLOCK_N) output planes
dominate the byte count (see benchmarks/roofline.py).

Queries sit on **sublanes**: the ``*_pallas`` entry points take the
callers' (b, W, m) planes and hand the kernel (m, b·W) query rows, so a
query block is (BLOCK_M, b·W) — its last dimension is the whole axis,
which Mosaic accepts at any width, and BLOCK_M = 8 fills one sublane
tile.  Inside the kernel one (BLOCK_M, 1) query column broadcasts along
lanes against one (1, BLOCK_N) database row.

Block-shape reasoning (v5e: 128 lanes, 8 sublanes, ~16 MiB VMEM/core):
  * BLOCK_N multiple of 128 (lane width).  Default 2048.
  * BLOCK_M multiple of 8, or the whole (padded) query axis when m < 8;
    default 8 — the per-word XOR intermediate is (BLOCK_M, BLOCK_N) =
    8·2048·4 = 64 KiB of VMEM, leaving room to double-buffer.
  * b·W ≤ 16 for every paper dataset (b=2,W=1 … b=8,W=2); at BLOCK_M=1
    the kernel degenerates to the original memory-bound single-query
    scan at ~1.5 int-ops per byte.

Every verify kernel emits one (m, n) int32 plane — the exact total
distance, clamped to BIG on pruned or dead lanes; the survival mask is
``dist <= tau`` (τ < BIG), derived outside the kernel so XLA fuses it
into its consumer instead of writing a second plane to HBM.

Arena verify reads each column's prefix distance from an (m, T)
per-root base plane through the column's ``base_idx`` lane.  Sealed
rows are stored in trie leaf order, so that lane rises in steps of 0 or
1 and any w consecutive columns need at most w consecutive roots: each
column block DMAs a short window of the base plane (its start known
before the block runs, by scalar prefetch) and expands it inside VMEM
with 128-lane gathers (DESIGN.md §6).  ``plan_windows`` computes the
window starts on the host and refuses a lane that breaks the invariant.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_N = 2048
DEFAULT_BLOCK_M = 8

# Lane width of one vreg: the windowed base expansion works in 128-lane
# sub-blocks, and a window starts on a 128-lane tile.
LANES = 128

# Distance sentinel for pruned lanes.  Matches core.bst.BIG (kernels must
# not import core); verified equal in tests/test_kernels.py.
BIG = 1 << 20

# VMEM the re-rank kernel's double-buffered blocks may claim: v5e's
# default scoped VMEM limit is 16 MiB per core, and the kernel body's
# (BLOCK_M, BLOCK_N) intermediates need the rest.
RERANK_VMEM_BUDGET = 8 << 20


def _query_rows(q_vert: jnp.ndarray) -> jnp.ndarray:
    """(b, W, m) query planes -> (m, b·W) query rows (column i·W + w is
    plane i, word w): the sublane-major query layout of every kernel."""
    b, W, m = q_vert.shape
    return jnp.transpose(q_vert, (2, 0, 1)).reshape(m, b * W)


def _tile_distances(db_ref, q_ref, *, b: int, W: int):
    """(b, W, BLOCK_N) uint32 database block x (BLOCK_M, b·W) uint32
    query rows -> (BLOCK_M, BLOCK_N) int32 Hamming distances: per word,
    OR the b plane XORs, popcount, and sum the words.  b and W are
    python constants so both reductions fully unroll."""
    dist = None
    for w in range(W):
        acc = None
        for i in range(b):
            x = db_ref[i, w:w + 1, :] ^ q_ref[:, i * W + w:i * W + w + 1]
            acc = x if acc is None else acc | x
        pops = jax.lax.population_count(acc).astype(jnp.int32)
        dist = pops if dist is None else dist + pops
    return dist


def _hamming_kernel(db_ref, q_ref, out_ref, *, b: int, W: int):
    """One (query tile j, db block i) cell: (BLOCK_M, BLOCK_N) distances."""
    out_ref[...] = _tile_distances(db_ref, q_ref, b=b, W=W)


def _scan_specs(db_shape, q_width: int, block_m: int, block_n: int):
    """BlockSpecs shared by the query-tiled scans: the database streams
    (..., BLOCK_N) lane blocks along grid axis 1, the query tile is the
    (BLOCK_M, q_width) row block of grid axis 0."""
    lead = (0,) * (len(db_shape) - 1)
    return [pl.BlockSpec(tuple(db_shape[:-1]) + (block_n,),
                         lambda j, i: lead + (i,)),
            pl.BlockSpec((block_m, q_width), lambda j, i: (j, 0))]


def _tile_spec(block_m: int, block_n: int):
    return pl.BlockSpec((block_m, block_n), lambda j, i: (j, i))


@functools.partial(jax.jit, static_argnames=("block_m", "block_n", "interpret"))
def hamming_distances_pallas(db_vert: jnp.ndarray, q_vert: jnp.ndarray,
                             *, block_m: int = DEFAULT_BLOCK_M,
                             block_n: int = DEFAULT_BLOCK_N,
                             interpret: bool = False) -> jnp.ndarray:
    """(b, W, n) x (b, W, m) -> (m, n) int32 distances via pallas_call.

    Grid is (m/block_m, n/block_n): query tiles on the outer axis so each
    tile stays VMEM-resident while database blocks stream past — the
    database is read ⌈m/block_m⌉ times total.  ``n`` must be a multiple
    of ``block_n`` and ``m`` of ``block_m`` (ops.py pads both).
    """
    b, W, n = db_vert.shape
    m = q_vert.shape[-1]
    assert n % block_n == 0, (n, block_n)
    assert m % block_m == 0, (m, block_m)
    kernel = functools.partial(_hamming_kernel, b=b, W=W)
    return pl.pallas_call(
        kernel,
        grid=(m // block_m, n // block_n),
        in_specs=_scan_specs(db_vert.shape, b * W, block_m, block_n),
        out_specs=_tile_spec(block_m, block_n),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.int32),
        interpret=interpret,
    )(db_vert, _query_rows(q_vert))


def _verify_kernel(db_ref, q_ref, base_ref, dist_ref, *, b: int, W: int):
    """Fused query-tiled verify: suffix distance + per-query accumulated
    prefix distance -> the exact int32 total, clamped to BIG on pruned
    lanes (base >= BIG)."""
    dist = _tile_distances(db_ref, q_ref, b=b, W=W)
    dist_ref[...] = jnp.minimum(dist + base_ref[...], BIG)


def _packed_tile_distances(db, q, *, b: int, S: int):
    """(1, BLOCK_N) uint32 packed suffixes x (BLOCK_M, 1) uint32 packed
    query suffixes -> (BLOCK_M, BLOCK_N) int32 Hamming distances over
    the S suffix positions.  All b planes of a row live in ONE word
    (plane i at bit offset i·S, see ``hamming.pack_suffix_words``), so
    the XOR/OR fold runs as b-1 shift+mask+OR word ops before a single
    popcount — the vertical-format identity at 1/W·b of the full-length
    traffic."""
    x = db ^ q                                    # (BLOCK_M, BLOCK_N)
    field = jnp.uint32((1 << S) - 1) if S else jnp.uint32(0)
    acc = x & field
    for i in range(1, b):
        acc = acc | ((x >> jnp.uint32(i * S)) & field)
    return jax.lax.population_count(acc).astype(jnp.int32)


def _verify_call(kernel, db, q_rows, base, *, tau: int, block_m: int,
                 block_n: int, interpret: bool):
    """Launch one verify kernel on the (m/block_m, n/block_n) grid and
    return ((m, n) int32 survival masks, (m, n) int32 BIG-clamped
    totals); the mask is derived from the one emitted plane."""
    m, n = base.shape
    assert n % block_n == 0, (n, block_n)
    assert m % block_m == 0, (m, block_m)
    assert db.shape[-1] == n and q_rows.shape[0] == m, (db.shape,
                                                        q_rows.shape)
    dist = pl.pallas_call(
        kernel,
        grid=(m // block_m, n // block_n),
        in_specs=_scan_specs(db.shape, q_rows.shape[1], block_m, block_n)
        + [_tile_spec(block_m, block_n)],
        out_specs=_tile_spec(block_m, block_n),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.int32),
        interpret=interpret,
    )(db, q_rows, base.astype(jnp.int32))
    return (dist <= tau).astype(jnp.int32), dist


@functools.partial(jax.jit,
                   static_argnames=("tau", "block_m", "block_n", "interpret"))
def sparse_verify_batch_pallas(paths_vert: jnp.ndarray, q_vert: jnp.ndarray,
                               base_dist: jnp.ndarray, *, tau: int,
                               block_m: int = DEFAULT_BLOCK_M,
                               block_n: int = DEFAULT_BLOCK_N,
                               interpret: bool = False):
    """(b, W, n) suffix paths + (b, W, m) query suffixes + (m, n) prefix
    distances -> ((m, n) int32 survival masks, (m, n) int32 totals).

    Grid (m/block_m, n/block_n): each cell loads one (b, W, block_n)
    database block once and XOR/popcounts it against a block_m-query
    tile, so the collapsed-path array is streamed from HBM only
    ⌈m/block_m⌉ times for the whole batch.  Distances are exact
    (prefix + suffix) for every non-pruned lane and clamped to BIG where
    the prefix was pruned (base >= BIG)."""
    b, W, _ = paths_vert.shape
    kernel = functools.partial(_verify_kernel, b=b, W=W)
    return _verify_call(kernel, paths_vert, _query_rows(q_vert), base_dist,
                        tau=tau, block_m=block_m, block_n=block_n,
                        interpret=interpret)


def sparse_verify_pallas(paths_vert: jnp.ndarray, q_vert: jnp.ndarray,
                         base_dist: jnp.ndarray, *, tau: int,
                         block_n: int = DEFAULT_BLOCK_N,
                         interpret: bool = False):
    """Single-query verify: the m=1, block_m=1 degenerate tile of the
    batched kernel.  (b, W, n) + (b, W) + (n,) -> ((n,) mask, (n,) dist)."""
    mask, dist = sparse_verify_batch_pallas(
        paths_vert, q_vert[..., None], base_dist[None, :].astype(jnp.int32),
        tau=tau, block_m=1, block_n=block_n, interpret=interpret)
    return mask[0], dist[0]


class WindowPlan(NamedTuple):
    """Host plan of the windowed base expansion over one ``base_idx``
    lane, padded (with its last value) to a ``block_n`` multiple.

    start: (n_blocks,) int32 — the 128-lane tile of the base plane that
           holds the block's first root (the window start);
    steps: (n_blocks,) int32 — bit s-1 set when 128-lane sub-block s
           starts one tile later than sub-block s-1;
    roots: Σ over blocks of the roots the block's lanes span."""

    start: np.ndarray
    steps: np.ndarray
    roots: int


def plan_windows(base_idx, block_n: int = DEFAULT_BLOCK_N) -> WindowPlan:
    """Window starts of every ``block_n`` column block of an arena lane.

    The lane must rise in steps of 0 or 1 (sealed rows in trie order,
    segments' roots laid out in column order); then a block's lanes
    span at most ``block_n`` roots from its first, and each 128-lane
    sub-block's at most two consecutive 128-lane tiles of the base
    plane.  Raises ValueError on any other lane: there is no gather to
    fall back to."""
    if block_n % LANES or not 0 < block_n // LANES <= 32:
        raise ValueError(f"block_n={block_n} must be 128·k with k <= 32")
    idx = np.asarray(base_idx, np.int64)
    n_sub = block_n // LANES
    if idx.size == 0:
        return WindowPlan(np.zeros(0, np.int32), np.zeros(0, np.int32), 0)
    step = np.diff(idx)
    if idx[0] < 0 or (step.size and (step.min() < 0 or step.max() > 1)):
        raise ValueError(
            "arena base_idx must rise in steps of 0 or 1 (sealed rows in "
            "trie leaf order, roots in column order) for the windowed "
            "verify")
    pad = (-idx.size) % block_n
    if pad:
        idx = np.concatenate([idx, np.full(pad, idx[-1])])
    tiles = (idx[::LANES] // LANES).reshape(-1, n_sub)
    steps = (np.diff(tiles, axis=1) << np.arange(n_sub - 1)).sum(axis=1)
    blocks = idx.reshape(-1, block_n)
    roots = int((blocks[:, -1] - blocks[:, 0] + 1).sum())
    return WindowPlan(tiles[:, 0].astype(np.int32), steps.astype(np.int32),
                      roots)


def _lane_gather(x, idx):
    """(rows, 128) x (rows, 128) in-tile lane indices -> x[r, idx[r, c]]:
    the one gather Mosaic lowers (``tpu.dynamic_gather`` within a vreg
    row), spelled with explicit batching dims so a one-row tile lowers
    too."""
    dn = jax.lax.GatherDimensionNumbers(
        offset_dims=(), collapsed_slice_dims=(1,), start_index_map=(1,),
        operand_batching_dims=(0,), start_indices_batching_dims=(0,))
    return jax.lax.gather(x, idx[..., None], dn, (1, 1),
                          mode=jax.lax.GatherScatterMode.PROMISE_IN_BOUNDS)


def _window_bases(start_ref, steps_ref, lo_ref, hi_ref, idx_ref, live_ref):
    """Expand this block's base-plane window into per-column bases: one
    (BLOCK_M, 128) int32 plane per 128-lane sub-block, BIG on dead
    lanes.  ``lo_ref`` / ``hi_ref`` are the two ``block_n``-wide chunks
    of the base plane that hold the window; sub-block s reads tiles t_s
    and t_s + 1 of them (t_s from the prefetched start and step bits)
    and gathers each column's root within those 256 lanes."""
    i = pl.program_id(1)
    bm = lo_ref.shape[0]
    n_sub = idx_ref.shape[-1] // LANES
    start, steps = start_ref[i], steps_ref[i]
    first = start - start % n_sub          # first tile of the lo chunk
    t = start % n_sub

    def tile(u):
        col = pl.multiple_of((u % n_sub) * LANES, LANES)
        return jnp.where(u < n_sub, lo_ref[:, pl.ds(col, LANES)],
                         hi_ref[:, pl.ds(col, LANES)])

    bases = []
    for s in range(n_sub):
        if s:
            t = t + ((steps >> (s - 1)) & 1)
        lanes = slice(s * LANES, (s + 1) * LANES)
        off = jnp.broadcast_to(idx_ref[:, lanes] - (first + t) * LANES,
                               (bm, LANES))      # in [0, 256)
        pos = off & (LANES - 1)
        base = jnp.where(off < LANES, _lane_gather(tile(t), pos),
                         _lane_gather(tile(t + 1), pos))
        bases.append(jnp.where(live_ref[:, lanes] != 0, base, BIG))
    return bases


def _store_totals(dist_ref, dist, bases):
    """Write prefix + suffix totals, clamped to BIG, sub-block by
    sub-block."""
    for s, base in enumerate(bases):
        lanes = slice(s * LANES, (s + 1) * LANES)
        dist_ref[:, lanes] = jnp.minimum(dist[:, lanes] + base, BIG)


def _verify_window_kernel(start_ref, steps_ref, lo_ref, hi_ref, idx_ref,
                          live_ref, db_ref, q_ref, dist_ref, *, b: int,
                          W: int):
    """Arena verify over (b, W, BLOCK_N) vertical columns: suffix (or
    full-length) distance plus the windowed per-column base."""
    _store_totals(dist_ref, _tile_distances(db_ref, q_ref, b=b, W=W),
                  _window_bases(start_ref, steps_ref, lo_ref, hi_ref,
                                idx_ref, live_ref))


def _verify_packed_kernel(start_ref, steps_ref, lo_ref, hi_ref, idx_ref,
                          live_ref, db_ref, q_ref, dist_ref, *, b: int,
                          S: int):
    """Packed-suffix twin of ``_verify_window_kernel``: the per-column
    payload is one uint32 word (the b bit planes of the S-symbol suffix
    below the segment's ℓ_s collapse depth) instead of (b, W)
    full-length words — the prefix part of the distance arrives through
    the base plane (DESIGN.md §7)."""
    _store_totals(dist_ref,
                  _packed_tile_distances(db_ref[...], q_ref[...], b=b, S=S),
                  _window_bases(start_ref, steps_ref, lo_ref, hi_ref,
                                idx_ref, live_ref))


def _window_call(kernel, db, q_rows, base_plane, base_idx, live, windows,
                 *, tau: int, block_m: int, block_n: int, interpret: bool):
    """Launch one windowed arena verify on the (m/block_m, n/block_n)
    grid.  ``windows`` = (start, steps) of ``plan_windows`` over the
    padded lane; the base plane is padded with BIG to whole ``block_n``
    chunks plus one, so every window's second chunk exists.  Returns
    ((m, n) int32 survival masks, (m, n) int32 BIG-clamped totals)."""
    m, T = base_plane.shape
    n = db.shape[-1]
    start, steps = windows
    n_sub = block_n // LANES
    assert n % block_n == 0, (n, block_n)
    assert m % block_m == 0, (m, block_m)
    assert q_rows.shape[0] == m, (q_rows.shape, m)
    assert base_idx.shape == (n,) and live.shape == (n,), (n,)
    assert start.shape == steps.shape == (n // block_n,), (start.shape, n)
    t_pad = (pl.cdiv(T, block_n) + 1) * block_n
    plane = jnp.pad(base_plane.astype(jnp.int32), ((0, 0), (0, t_pad - T)),
                    constant_values=BIG)
    lead = (0,) * (db.ndim - 1)
    lane = lambda j, i, st, sp: (0, i)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(m // block_m, n // block_n),
        in_specs=[
            pl.BlockSpec((block_m, block_n),
                         lambda j, i, st, sp: (j, st[i] // n_sub)),
            pl.BlockSpec((block_m, block_n),
                         lambda j, i, st, sp: (j, st[i] // n_sub + 1)),
            pl.BlockSpec((1, block_n), lane),
            pl.BlockSpec((1, block_n), lane),
            pl.BlockSpec(tuple(db.shape[:-1]) + (block_n,),
                         lambda j, i, st, sp: lead + (i,)),
            pl.BlockSpec((block_m, q_rows.shape[1]),
                         lambda j, i, st, sp: (j, 0))],
        out_specs=pl.BlockSpec((block_m, block_n),
                               lambda j, i, st, sp: (j, i)))
    dist = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.int32),
        interpret=interpret,
    )(jnp.asarray(start, jnp.int32), jnp.asarray(steps, jnp.int32),
      plane, plane, base_idx.astype(jnp.int32).reshape(1, n),
      live.astype(jnp.int32).reshape(1, n), db, q_rows)
    return (dist <= tau).astype(jnp.int32), dist


@functools.partial(jax.jit,
                   static_argnames=("b", "S", "tau", "block_m", "block_n",
                                    "interpret"))
def sparse_verify_arena_packed_pallas(db_words: jnp.ndarray,
                                      q_words: jnp.ndarray,
                                      base_plane: jnp.ndarray,
                                      base_idx: jnp.ndarray,
                                      live: jnp.ndarray, windows, *,
                                      b: int, S: int, tau: int,
                                      block_m: int = DEFAULT_BLOCK_M,
                                      block_n: int = DEFAULT_BLOCK_N,
                                      interpret: bool = False):
    """Arena verify over **single-word packed suffix columns**
    (DESIGN.md §7; requires b·S <= 32).

    db_words:   (n,) uint32 — one packed suffix word per column;
    q_words:    (m,) uint32 — the query suffixes in the same packing;
    base_plane: (m, T) int32 — concatenated per-(segment, root) *prefix*
                distances (BIG = pruned);
    base_idx:   (n,) int32 segment-offset lane, rising in steps of 0 or
                1; live: (n,) int32;
    windows:    (start, steps) of ``plan_windows(base_idx, block_n)``.

    Same (m/block_m, n/block_n) query-tiled grid and return contract as
    ``sparse_verify_arena_pallas`` — only the column payload shrinks,
    from b·W words to one."""
    n = db_words.shape[-1]
    m = q_words.shape[-1]
    kernel = functools.partial(_verify_packed_kernel, b=b, S=S)
    return _window_call(
        kernel, db_words.astype(jnp.uint32).reshape(1, n),
        q_words.astype(jnp.uint32).reshape(m, 1), base_plane, base_idx,
        live, windows, tau=tau, block_m=block_m, block_n=block_n,
        interpret=interpret)


@functools.partial(jax.jit,
                   static_argnames=("tau", "block_m", "block_n", "interpret"))
def sparse_verify_arena_pallas(paths_vert: jnp.ndarray, q_vert: jnp.ndarray,
                               base_plane: jnp.ndarray,
                               base_idx: jnp.ndarray, live: jnp.ndarray,
                               windows, *, tau: int,
                               block_m: int = DEFAULT_BLOCK_M,
                               block_n: int = DEFAULT_BLOCK_N,
                               interpret: bool = False):
    """Fused multi-segment verify over a **column arena** (DESIGN.md §6).

    paths_vert: (b, W, n) uint32 — concatenated verify columns of every
                segment (plus, in the full-length layout, the delta
                buffer's);
    q_vert:     (b, W, m) uint32 query planes;
    base_plane: (m, T) int32 — the concatenated per-(segment, ℓ_s-root)
                base-distance plane (slot semantics are the caller's:
                the full-length layout stores 0 = reached / BIG =
                pruned, with its last slot the delta buffer's trivial
                base);
    base_idx:   (n,) int32 — per-column index into ``base_plane``'s T
                axis, rising in steps of 0 or 1 (rows in trie order);
    live:       (n,) int32 — per-column liveness lane (0 = tombstoned);
    windows:    (start, steps) of ``plan_windows(base_idx, block_n)``.

    Returns ((m, n) int32 survival masks, (m, n) int32 totals clamped to
    BIG).  One launch sweeps every segment: each column block DMAs its
    window of the base plane and expands it in VMEM — no (m, n) base
    plane is ever built."""
    b, W, _ = paths_vert.shape
    kernel = functools.partial(_verify_window_kernel, b=b, W=W)
    return _window_call(kernel, paths_vert, _query_rows(q_vert),
                        base_plane, base_idx, live, windows, tau=tau,
                        block_m=block_m, block_n=block_n,
                        interpret=interpret)


def _rerank_kernel(pay_ref, q_ref, surv_ref, out_ref, *, Wp: int,
                   metric: str):
    """One (query tile j, column block i) cell of the exact re-rank plane:
    AND/popcount the (Wp, BLOCK_N) payload bitmaps against a
    (BLOCK_M, Wp) query tile one word at a time — a (BLOCK_M, 1) query
    column against a (1, BLOCK_N) payload row, so no intermediate grows
    with Wp — and emit the exact set-similarity score for every survivor
    lane.  Non-survivors (and zero-denominator survivors' 0.0) keep the
    layout of the Hamming plane so the downstream top-k sort needs no
    re-gather.  Wp is a python constant and the word loop fully
    unrolls."""
    inter = sa = sb = None
    for w in range(Wp):
        p = pay_ref[w:w + 1, :]                   # (1, BLOCK_N)
        q = q_ref[:, w:w + 1]                     # (BLOCK_M, 1)
        both = jax.lax.population_count(q & p).astype(jnp.int32)
        pa = jax.lax.population_count(q).astype(jnp.int32)
        pb = jax.lax.population_count(p).astype(jnp.int32)
        if inter is None:
            inter, sa, sb = both, pa, pb
        else:
            inter, sa, sb = inter + both, sa + pa, sb + pb
    inter = inter.astype(jnp.float32)             # (BLOCK_M, BLOCK_N)
    sa = sa.astype(jnp.float32)                   # (BLOCK_M, 1)
    sb = sb.astype(jnp.float32)                   # (1, BLOCK_N)
    if metric == "jaccard":
        den = sa + sb - inter
    elif metric == "cosine":
        den = jnp.sqrt(sa * sb)
    else:                                         # containment (A = query)
        den = jnp.broadcast_to(sa, inter.shape)
    score = jnp.where(den > 0, inter / den, jnp.float32(0.0))
    out_ref[...] = jnp.where(surv_ref[...] != 0, score, jnp.float32(-1.0))


@functools.partial(jax.jit,
                   static_argnames=("metric", "block_m", "block_n",
                                    "interpret"))
def exact_rerank_pallas(pay_vert: jnp.ndarray, q_vert: jnp.ndarray,
                        surv: jnp.ndarray, *, metric: str,
                        block_m: int = DEFAULT_BLOCK_M,
                        block_n: int = DEFAULT_BLOCK_N,
                        interpret: bool = False) -> jnp.ndarray:
    """Exact re-rank scan: (Wp, n) payload bitmaps x (Wp, m) query
    bitmaps x (m, n) survivor mask -> (m, n) float32 exact scores.

    Same query-tiled (m/block_m, n/block_n) grid discipline as
    ``hamming_distances_pallas`` — one launch scores every survivor of
    the whole arena, reading the payload store once per query tile.
    Scores are exact Jaccard / cosine / containment over the uint32
    set bitmaps (see ``kernels.ref.exact_rerank_ref`` for semantics);
    non-survivor lanes emit the -1.0 sentinel.  Raises ValueError when
    the payload width's blocks exceed ``RERANK_VMEM_BUDGET``.
    """
    Wp, n = pay_vert.shape
    m = q_vert.shape[-1]
    assert metric in ("jaccard", "cosine", "containment"), metric
    assert n % block_n == 0, (n, block_n)
    assert m % block_m == 0, (m, block_m)
    assert surv.shape == (m, n), (surv.shape, m, n)
    # double-buffered blocks: the (Wp, block_n) payload block, the
    # (block_m, Wp) query tile (lanes padded to 128), the survivor-mask
    # and score tiles
    lanes = -(-Wp // 128) * 128
    need = 2 * 4 * (Wp * block_n + block_m * lanes + 2 * block_m * block_n)
    if need > RERANK_VMEM_BUDGET:
        raise ValueError(
            f"exact re-rank payload width Wp={Wp} words needs {need} bytes "
            f"of VMEM blocks at block_m={block_m}, block_n={block_n}; the "
            f"budget is {RERANK_VMEM_BUDGET} (use a smaller vocabulary or "
            f"block_n)")
    kernel = functools.partial(_rerank_kernel, Wp=Wp, metric=metric)
    return pl.pallas_call(
        kernel,
        grid=(m // block_m, n // block_n),
        in_specs=_scan_specs(pay_vert.shape, Wp, block_m, block_n)
        + [_tile_spec(block_m, block_n)],
        out_specs=_tile_spec(block_m, block_n),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        interpret=interpret,
    )(pay_vert.astype(jnp.uint32), q_vert.astype(jnp.uint32).T,
      surv.astype(jnp.int32))
