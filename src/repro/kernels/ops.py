"""Public jit'd wrappers around the Pallas kernels.

Responsibilities: layout conversion ((n, b, W) <-> (b, W, n)), padding to
block multiples (both the lane/database axis and the query axis of the
query-tiled kernels), backend selection (compiled Pallas on TPU,
interpret mode on CPU so correctness tests execute the *same kernel
body*), and fallback to the pure-jnp oracle for shapes where a kernel
launch is not worth it.
"""

from __future__ import annotations

import functools
import threading

import jax
import jax.numpy as jnp

from . import ref
from .hamming_kernel import (BIG, DEFAULT_BLOCK_M, DEFAULT_BLOCK_N,
                             exact_rerank_pallas, hamming_distances_pallas,
                             plan_windows,
                             sparse_verify_arena_packed_pallas,
                             sparse_verify_arena_pallas,
                             sparse_verify_batch_pallas, sparse_verify_pallas)


def _interpret() -> bool:
    """Pallas mode for the current backend: compiled on TPU, interpret
    mode on CPU (the test path — same kernel body, executed by the
    interpreter).  Any other backend raises rather than serving answers
    from a path that hides the missing device."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(f"Pallas kernels run compiled on TPU or in interpret "
                       f"mode on CPU; the {backend!r} backend is neither")


# Process-wide kernel-build ledger (DESIGN.md §11): one bump per wrapper
# entry, keyed by wrapper name, with a ``:ref`` suffix when the call fell
# back to the pure-jnp oracle.  Wrappers run at *trace* time, so under
# jit these count kernel bodies staged into compiled programs (a cached
# program replays without re-entering the wrapper) — the companion to
# ``segments.dispatch_stats()``, which counts program launches.
_KSTATS_LOCK = threading.Lock()
_KERNEL_STATS: dict = {}


def _count(name: str, use_kernel: bool) -> None:
    key = name if use_kernel else name + ":ref"
    with _KSTATS_LOCK:
        _KERNEL_STATS[key] = _KERNEL_STATS.get(key, 0) + 1


def kernel_stats() -> dict:
    """Per-wrapper trace-time call counts (``<name>`` kernel path,
    ``<name>:ref`` oracle fallback)."""
    with _KSTATS_LOCK:
        return dict(_KERNEL_STATS)


def reset_kernel_stats() -> None:
    with _KSTATS_LOCK:
        _KERNEL_STATS.clear()


def to_lane_major(planes: jnp.ndarray) -> jnp.ndarray:
    """(n, b, W) sketch-major -> (b, W, n) lane-major (kernel layout)."""
    return jnp.transpose(planes, (1, 2, 0))


def _pad_lanes(x: jnp.ndarray, block_n: int) -> jnp.ndarray:
    n = x.shape[-1]
    pad = (-n) % block_n
    if pad:
        x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])
    return x


def hamming_distances(db_vert: jnp.ndarray, q_vert: jnp.ndarray,
                      *, block_m: int = DEFAULT_BLOCK_M,
                      block_n: int = DEFAULT_BLOCK_N,
                      use_kernel: bool | None = None) -> jnp.ndarray:
    """(b, W, n) x (b, W, m) -> (m, n) int32.  Pads n and m to block
    multiples, launches the query-tiled kernel, and slices the pads back
    off (pad sketches/queries are all-zero words -> garbage rows/columns,
    dropped here)."""
    n = db_vert.shape[-1]
    m = q_vert.shape[-1]
    if use_kernel is None:
        use_kernel = n >= block_n  # tiny scans: oracle is cheaper than launch
    _count("hamming_distances", use_kernel)
    if not use_kernel:
        return ref.hamming_distances_ref(db_vert, q_vert)
    block_m = min(block_m, m)  # never compute more pad-query rows than m
    db_p = _pad_lanes(db_vert, block_n)
    q_p = _pad_lanes(q_vert, block_m)
    out = hamming_distances_pallas(db_p, q_p, block_m=block_m,
                                   block_n=block_n, interpret=_interpret())
    return out[:m, :n]


def sparse_verify(paths_vert: jnp.ndarray, q_vert: jnp.ndarray,
                  base_dist: jnp.ndarray, *, tau: int,
                  live: jnp.ndarray | None = None,
                  block_n: int = DEFAULT_BLOCK_N,
                  use_kernel: bool | None = None):
    """Fused single-query verify: ((n,) int32 mask of leaves with
    prefix+suffix dist <= tau, (n,) int32 exact total distances —
    BIG-clamped when pruned).

    ``live`` is an optional (n,) bool tombstone mask (dynamic segmented
    index, DESIGN.md §4): dead lanes get a BIG base distance before the
    kernel launch, so tombstoned leaves are pruned by the verify exactly
    like subtries the traversal never reached — pruning == masking, no
    extra kernel pass."""
    if live is not None:
        base_dist = jnp.where(live, base_dist, jnp.int32(BIG))
    n = paths_vert.shape[-1]
    if use_kernel is None:
        use_kernel = n >= block_n
    _count("sparse_verify", use_kernel)
    if not use_kernel:
        mask, dist = ref.sparse_verify_ref(paths_vert, q_vert, base_dist, tau)
        return mask.astype(jnp.int32), dist
    paths_p = _pad_lanes(paths_vert, block_n)
    # pad base distances with +inf-like so pad lanes never survive
    pad = paths_p.shape[-1] - n
    base_p = jnp.pad(base_dist.astype(jnp.int32), (0, pad), constant_values=jnp.int32(BIG))
    mask, dist = sparse_verify_pallas(paths_p, q_vert, base_p, tau=tau,
                                      block_n=block_n, interpret=_interpret())
    return mask[:n], dist[:n]


def sparse_verify_batch(paths_vert: jnp.ndarray, q_vert: jnp.ndarray,
                        base_dist: jnp.ndarray, *, tau: int,
                        live: jnp.ndarray | None = None,
                        block_m: int = DEFAULT_BLOCK_M,
                        block_n: int = DEFAULT_BLOCK_N,
                        use_kernel: bool | None = None):
    """Fused query-tiled verify over a whole batch.

    paths_vert: (b, W, n) collapsed suffix paths (shared database);
    q_vert:     (b, W, m) query suffixes;
    base_dist:  (m, n) per-query prefix distances (BIG = pruned subtrie);
    live:       optional (n,) bool tombstone mask shared by every query —
                dead lanes get a BIG base distance before the kernel
                launch (tombstoned leaves are pruned exactly like
                unreached subtries; DESIGN.md §4);
    returns ((m, n) int32 masks, (m, n) int32 exact totals, BIG-clamped).

    Pads n to a ``block_n`` multiple with BIG base distances (pad lanes
    can never survive) and m to a ``block_m`` multiple with all-zero
    queries (pad rows sliced off), then launches the (m/block_m,
    n/block_n)-grid kernel: the database is streamed ⌈m/block_m⌉ times
    instead of m."""
    if live is not None:
        base_dist = jnp.where(live[None, :], base_dist, jnp.int32(BIG))
    n = paths_vert.shape[-1]
    m = q_vert.shape[-1]
    if use_kernel is None:
        use_kernel = n >= block_n
    _count("sparse_verify_batch", use_kernel)
    if not use_kernel:
        mask, dist = ref.sparse_verify_batch_ref(paths_vert, q_vert,
                                                 base_dist, tau)
        return mask.astype(jnp.int32), dist
    block_m = min(block_m, m)  # never compute more pad-query rows than m
    paths_p = _pad_lanes(paths_vert, block_n)
    q_p = _pad_lanes(q_vert, block_m)
    pad_n = paths_p.shape[-1] - n
    pad_m = q_p.shape[-1] - m
    base_p = jnp.pad(base_dist.astype(jnp.int32),
                     ((0, pad_m), (0, pad_n)),
                     constant_values=jnp.int32(BIG))
    mask, dist = sparse_verify_batch_pallas(paths_p, q_p, base_p, tau=tau,
                                            block_m=block_m, block_n=block_n,
                                            interpret=_interpret())
    return mask[:m, :n], dist[:m, :n]


def _arena_args(base_plane, base_idx, live, windows, *, n_pad: int,
                m_pad: int):
    """Pad the arena lanes to ``n_pad`` columns — dead, with the lane's
    last root, so it still rises in steps of 0 or 1 — and the base plane
    to ``m_pad`` all-BIG rows; check that a window plan is there."""
    if windows is None:
        raise ValueError("the windowed arena verify needs the lane's "
                         "window plan (ops.plan_windows)")
    n, m = base_idx.shape[-1], base_plane.shape[0]
    idx_p = jnp.pad(base_idx.astype(jnp.int32), (0, n_pad - n), mode="edge")
    live_p = jnp.pad(live.astype(jnp.int32), (0, n_pad - n))  # pads dead
    base_p = jnp.pad(base_plane.astype(jnp.int32), ((0, m_pad - m), (0, 0)),
                     constant_values=jnp.int32(BIG))
    return base_p, idx_p, live_p, (windows[0], windows[1])


def arena_uses_kernel(n: int, block_n: int = DEFAULT_BLOCK_N) -> bool:
    """Whether an arena verify over ``n`` columns runs the windowed
    kernel; narrower ones run the ``ref`` oracle."""
    return n >= block_n


def sparse_verify_arena(paths_vert: jnp.ndarray, q_vert: jnp.ndarray,
                        base_plane: jnp.ndarray, base_idx: jnp.ndarray,
                        live: jnp.ndarray, *, tau: int, windows=None,
                        block_m: int = DEFAULT_BLOCK_M,
                        block_n: int = DEFAULT_BLOCK_N,
                        use_kernel: bool | None = None):
    """Fused multi-segment verify over a column arena (DESIGN.md §6).

    paths_vert: (b, W, n) concatenated verify columns (all segments,
                plus the delta buffer in the full-length layout; one
                column per physical row);
    q_vert:     (b, W, m) query planes;
    base_plane: (m, T) per-(segment, root) base distances — T = total
                ℓ_s roots across segments (+ the delta's slot), ≪ n;
    base_idx:   (n,) int32 per-column index into the T axis (the
                segment-offset lane), rising in steps of 0 or 1;
    live:       (n,) bool per-column liveness;
    windows:    ``plan_windows(base_idx, block_n)`` (its start and
                steps), needed on the kernel path;
    returns ((m, n) int32 masks, (m, n) int32 totals, BIG-clamped).

    One launch sweeps every segment: pads n to a ``block_n`` multiple
    with dead lanes (live=False -> BIG, can never survive) and m to a
    ``block_m`` multiple with all-zero queries (rows sliced off)."""
    n = paths_vert.shape[-1]
    m = q_vert.shape[-1]
    if use_kernel is None:
        use_kernel = arena_uses_kernel(n, block_n)
    _count("sparse_verify_arena", use_kernel)
    if not use_kernel:
        mask, dist = ref.sparse_verify_arena_ref(paths_vert, q_vert,
                                                 base_plane, base_idx,
                                                 live, tau)
        return mask.astype(jnp.int32), dist
    block_m = min(block_m, m)  # never compute more pad-query rows than m
    paths_p = _pad_lanes(paths_vert, block_n)
    q_p = _pad_lanes(q_vert, block_m)
    base_p, idx_p, live_p, win = _arena_args(
        base_plane, base_idx, live, windows, n_pad=paths_p.shape[-1],
        m_pad=q_p.shape[-1])
    mask, dist = sparse_verify_arena_pallas(
        paths_p, q_p, base_p, idx_p, live_p, win, tau=tau, block_m=block_m,
        block_n=block_n, interpret=_interpret())
    return mask[:m, :n], dist[:m, :n]


def sparse_verify_arena_packed(db_words: jnp.ndarray, q_words: jnp.ndarray,
                               base_plane: jnp.ndarray,
                               base_idx: jnp.ndarray, live: jnp.ndarray,
                               *, b: int, S: int, tau: int, windows=None,
                               block_m: int = DEFAULT_BLOCK_M,
                               block_n: int = DEFAULT_BLOCK_N,
                               use_kernel: bool | None = None):
    """Arena verify over single-word packed suffix columns
    (DESIGN.md §7; requires b·S <= 32).

    db_words:   (n,) uint32 — one packed suffix word per column (the b
                bit planes of the S symbols below the segment's ℓ_s);
    q_words:    (m,) uint32 query suffixes in the same packing;
    base_plane: (m, T) per-(segment, root) *prefix* distances (BIG =
                pruned; the traversal's exact distance, not 0/BIG —
                total = prefix + suffix is the full-length Hamming
                distance bit for bit);
    base_idx:   (n,) int32 segment-offset lane, rising in steps of 0 or
                1;  live: (n,) bool;  windows: as ``sparse_verify_arena``;
    returns ((m, n) int32 masks, (m, n) int32 totals, BIG-clamped).

    Same padding discipline as ``sparse_verify_arena``: n pads with dead
    lanes, m with all-zero queries."""
    n = db_words.shape[-1]
    m = q_words.shape[-1]
    if use_kernel is None:
        use_kernel = arena_uses_kernel(n, block_n)
    _count("sparse_verify_arena_packed", use_kernel)
    if not use_kernel:
        mask, dist = ref.sparse_verify_arena_packed_ref(
            db_words, q_words, base_plane, base_idx, live, b, S, tau)
        return mask.astype(jnp.int32), dist
    block_m = min(block_m, m)  # never compute more pad-query rows than m
    db_p = _pad_lanes(db_words.astype(jnp.uint32), block_n)
    q_p = _pad_lanes(q_words.astype(jnp.uint32), block_m)
    base_p, idx_p, live_p, win = _arena_args(
        base_plane, base_idx, live, windows, n_pad=db_p.shape[-1],
        m_pad=q_p.shape[-1])
    mask, dist = sparse_verify_arena_packed_pallas(
        db_p, q_p, base_p, idx_p, live_p, win, b=b, S=S, tau=tau,
        block_m=block_m, block_n=block_n, interpret=_interpret())
    return mask[:m, :n], dist[:m, :n]


def exact_rerank(pay_vert: jnp.ndarray, q_vert: jnp.ndarray,
                 surv: jnp.ndarray, *, metric: str,
                 block_m: int = DEFAULT_BLOCK_M,
                 block_n: int = DEFAULT_BLOCK_N,
                 use_kernel: bool | None = None) -> jnp.ndarray:
    """Exact re-rank pass over the survivor plane (DESIGN.md §10).

    pay_vert: (Wp, n) uint32 column-major payload bitmaps (the payload
              column store's concatenated arena); q_vert: (Wp, m) uint32
              query bitmaps; surv: (m, n) survivor mask (nonzero ==
              lane survived the trie sweep at the final τ rung);
    returns (m, n) float32 exact Jaccard / cosine / containment scores,
    -1.0 on non-survivor lanes.

    Pads n to a ``block_n`` multiple with all-zero payloads and surv=0
    (pad lanes score the -1.0 sentinel, sliced back off) and m to a
    ``block_m`` multiple with all-zero queries (rows sliced off)."""
    n = pay_vert.shape[-1]
    m = q_vert.shape[-1]
    if use_kernel is None:
        use_kernel = n >= block_n  # tiny scans: oracle is cheaper than launch
    _count("exact_rerank", use_kernel)
    if not use_kernel:
        return ref.exact_rerank_ref(pay_vert, q_vert, surv, metric)
    block_m = min(block_m, m)  # never compute more pad-query rows than m
    pay_p = _pad_lanes(pay_vert.astype(jnp.uint32), block_n)
    q_p = _pad_lanes(q_vert.astype(jnp.uint32), block_m)
    pad_n = pay_p.shape[-1] - n
    pad_m = q_p.shape[-1] - m
    surv_p = jnp.pad(surv.astype(jnp.int32), ((0, pad_m), (0, pad_n)))
    out = exact_rerank_pallas(pay_p, q_p, surv_p, metric=metric,
                              block_m=block_m, block_n=block_n,
                              interpret=_interpret())
    return out[:m, :n]
