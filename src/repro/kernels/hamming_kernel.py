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
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BLOCK_N = 2048
DEFAULT_BLOCK_M = 8

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


def _verify_packed_kernel(db_ref, q_ref, base_ref, dist_ref, *, b: int,
                          S: int):
    """Packed-suffix twin of ``_verify_kernel``: the per-column payload
    is one uint32 word (the b bit planes of the S-symbol suffix below
    the segment's ℓ_s collapse depth) instead of (b, W) full-length
    words — the prefix part of the distance arrives through the base
    plane (DESIGN.md §7)."""
    dist = _packed_tile_distances(db_ref[...], q_ref[...], b=b, S=S)
    dist_ref[...] = jnp.minimum(dist + base_ref[...], BIG)


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


def _gather_base(base_plane: jnp.ndarray, base_idx: jnp.ndarray,
                 live: jnp.ndarray) -> jnp.ndarray:
    """(m, T) per-root base plane -> the dense (m, n) per-column base
    plane the verify kernels stream: an XLA gather through the
    segment-offset lane, BIG on dead lanes (DESIGN.md §6).  One 1-D
    gather per query row (``lax.map``): a single (m, T)[:, idx] gather
    lays its slices out as (n, m) and pads m to 128 lanes on TPU — a
    temporary of 512 bytes per column."""
    live = live != 0
    return jax.lax.map(
        lambda row: jnp.where(live, row[base_idx], BIG),
        base_plane.astype(jnp.int32))


@functools.partial(jax.jit,
                   static_argnames=("b", "S", "tau", "block_m", "block_n",
                                    "interpret"))
def sparse_verify_arena_packed_pallas(db_words: jnp.ndarray,
                                      q_words: jnp.ndarray,
                                      base_plane: jnp.ndarray,
                                      base_idx: jnp.ndarray,
                                      live: jnp.ndarray, *, b: int, S: int,
                                      tau: int,
                                      block_m: int = DEFAULT_BLOCK_M,
                                      block_n: int = DEFAULT_BLOCK_N,
                                      interpret: bool = False):
    """Arena verify over **single-word packed suffix columns**
    (DESIGN.md §7; requires b·S <= 32).

    db_words:   (n,) uint32 — one packed suffix word per column;
    q_words:    (m,) uint32 — the query suffixes in the same packing;
    base_plane: (m, T) int32 — concatenated per-(segment, root) *prefix*
                distances (BIG = pruned), slot 0 the delta's trivial 0;
    base_idx:   (n,) int32 segment-offset lane; live: (n,) int32.

    Same (m/block_m, n/block_n) query-tiled grid and return contract as
    ``sparse_verify_arena_pallas`` — only the column payload shrinks,
    from b·W words to one."""
    n = db_words.shape[-1]
    m = q_words.shape[-1]
    assert base_plane.shape[0] == m, (base_plane.shape, m)
    assert base_idx.shape == (n,), (base_idx.shape, n)
    assert live.shape == (n,), (live.shape, n)
    kernel = functools.partial(_verify_packed_kernel, b=b, S=S)
    return _verify_call(
        kernel, db_words.astype(jnp.uint32).reshape(1, n),
        q_words.astype(jnp.uint32).reshape(m, 1),
        _gather_base(base_plane, base_idx, live), tau=tau, block_m=block_m,
        block_n=block_n, interpret=interpret)


@functools.partial(jax.jit,
                   static_argnames=("tau", "block_m", "block_n", "interpret"))
def sparse_verify_arena_pallas(paths_vert: jnp.ndarray, q_vert: jnp.ndarray,
                               base_plane: jnp.ndarray,
                               base_idx: jnp.ndarray, live: jnp.ndarray,
                               *, tau: int,
                               block_m: int = DEFAULT_BLOCK_M,
                               block_n: int = DEFAULT_BLOCK_N,
                               interpret: bool = False):
    """Fused multi-segment verify over a **column arena** (DESIGN.md §6).

    paths_vert: (b, W, n) uint32 — concatenated verify columns of every
                segment plus the delta buffer (one column per physical
                row, full-length vertical packing);
    q_vert:     (b, W, m) uint32 query planes;
    base_plane: (m, T) int32 — the concatenated per-(segment, ℓ_s-root)
                base-distance plane (slot semantics are the caller's:
                the segmented index stores 0 = reached / BIG = pruned,
                with slot 0 the delta buffer's trivial base);
    base_idx:   (n,) int32 — per-column index into ``base_plane``'s T
                axis (segment columns point at segment_root_offset +
                their ℓ_s root; delta columns at the trivial slot);
    live:       (n,) int32 — per-column liveness lane (0 = tombstoned).

    Returns ((m, n) int32 survival masks, (m, n) int32 totals clamped to
    BIG).  One launch sweeps every segment and the delta buffer: the
    per-column base is gathered through the segment-offset lane by XLA
    into a dense (m, n) plane (Mosaic has no lane gather), which the
    ``sparse_verify_batch_pallas`` kernel body then streams."""
    b, W, n = paths_vert.shape
    m = q_vert.shape[-1]
    assert base_plane.shape[0] == m, (base_plane.shape, m)
    assert base_idx.shape == (n,), (base_idx.shape, n)
    assert live.shape == (n,), (live.shape, n)
    kernel = functools.partial(_verify_kernel, b=b, W=W)
    return _verify_call(kernel, paths_vert, _query_rows(q_vert),
                        _gather_base(base_plane, base_idx, live), tau=tau,
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
