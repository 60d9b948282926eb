"""One-dispatch segment arena (DESIGN.md §6): the fused query path must
be bit-identical to the per-segment reference fan-out (and therefore to
a static rebuild over survivors) across random lifecycle interleavings,
every backend, and every batch shape — while issuing exactly ONE device
dispatch per ladder rung regardless of segment count.  Plus the arena
verify kernel's exactness against its oracle, incremental arena
maintenance, monotonic segment serials, and the bucketed delta scan."""

import numpy as np
import jax.numpy as jnp
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # clean env: deterministic fallback shim
    from _hypothesis_compat import given, settings, st

from repro.core import (SegmentedIndex, ShardedSegmentedIndex, bucket_m,
                        build_bst, dispatch_stats, reset_dispatch_stats,
                        searcher_cache_info, topk_batch)
from repro.core.bst import BIG
from repro.kernels import ops, ref

BIG_I = int(BIG)
_B = 2


def reference_columns(idx, qs, tau):
    """The per-segment fan-out, regardless of the index's arena flag."""
    return idx._search_columns(np.asarray(qs, np.uint8), tau)


def assert_columns_equal(idx, qs, tau):
    dist_r, ids_r, _ = reference_columns(idx, qs, tau)
    dist_f, ids_f, _ = idx._fused_columns(np.asarray(qs, np.uint8), tau)
    np.testing.assert_array_equal(ids_r, ids_f)
    np.testing.assert_array_equal(dist_r, dist_f)


def assert_topk_equal(idx, qs, k, tau0=None):
    got = idx.topk_batch(qs, k, tau0=tau0)
    flag = idx.use_arena
    idx.use_arena = False
    try:
        want = idx.topk_batch(qs, k, tau0=tau0)
    finally:
        idx.use_arena = flag
    np.testing.assert_array_equal(np.asarray(got.ids), np.asarray(want.ids))
    np.testing.assert_array_equal(np.asarray(got.dists),
                                  np.asarray(want.dists))
    assert got.tau == want.tau


# ---------------------------------------------------------------------------
# kernel exactness
# ---------------------------------------------------------------------------

def monotone_lane(rng, n, T, rise=0.7):
    """An arena base_idx lane as sealed trie-ordered rows give it: rises
    in steps of 0 or 1, from a random first root, within [0, T)."""
    steps = rng.random(n) < rise
    steps[0] = False
    lane = np.cumsum(steps)
    lane += rng.integers(0, max(1, T - int(lane[-1])))
    return np.minimum(lane, T - 1).astype(np.int32)


def assert_arena_kernels_match(paths, q, base, idx, live, *, block_n,
                               block_m, tau=20, S=10):
    """Both arena kernels — full-length vertical columns and packed
    suffix words — against their oracles, bit for bit."""
    b = paths.shape[0]
    windows = ops.plan_windows(np.asarray(idx), block_n)
    mk, dk = ops.sparse_verify_arena(
        paths, q, base, idx, live, tau=tau, windows=windows,
        block_n=block_n, block_m=block_m, use_kernel=True)
    mo, do = ref.sparse_verify_arena_ref(paths, q, base, idx, live, tau)
    np.testing.assert_array_equal(np.asarray(mk),
                                  np.asarray(mo).astype(np.int32))
    np.testing.assert_array_equal(np.asarray(dk), np.asarray(do))

    words, q_words = paths[0, 0], q[0, 0]
    mk, dk = ops.sparse_verify_arena_packed(
        words, q_words, base, idx, live, b=b, S=S, tau=tau // 3,
        windows=windows, block_n=block_n, block_m=block_m, use_kernel=True)
    mo, do = ref.sparse_verify_arena_packed_ref(words, q_words, base, idx,
                                                live, b, S, tau // 3)
    np.testing.assert_array_equal(np.asarray(mk),
                                  np.asarray(mo).astype(np.int32))
    np.testing.assert_array_equal(np.asarray(dk), np.asarray(do))


def random_arena(rng, n, m, T, *, b=3, W=2, big_share=0.3, live_share=0.8):
    paths = jnp.asarray(rng.integers(0, 2 ** 32, (b, W, n), np.uint64)
                        .astype(np.uint32))
    q = jnp.asarray(rng.integers(0, 2 ** 32, (b, W, m), np.uint64)
                    .astype(np.uint32))
    base = np.where(rng.random((m, T)) < big_share, BIG_I,
                    rng.integers(0, 5, (m, T))).astype(np.int32)
    live = rng.random(n) < live_share
    return paths, q, base, live


@pytest.mark.parametrize("n,m,T,block_n,block_m", [
    (300, 5, 170, 128, 8),     # pad on both axes
    (256, 1, 300, 128, 8),     # m=1 degenerate tile, aligned n
    (130, 9, 200, 128, 4),     # tile-misaligned both ways, T > n block
    (300, 8, 270, 128, 8),     # the scheduler's buckets at the default
    (300, 16, 270, 128, 8),    #   query tile, pad lanes
    (390, 64, 330, 128, 24),   # pad rows: 64 queries in 24-row tiles
])
def test_arena_kernel_matches_oracle(n, m, T, block_n, block_m):
    """Both arena kernels — full-length vertical columns and packed
    suffix words — against their oracles, bit for bit, on a lane that
    rises in steps of 0 or 1 (sealed rows in trie order)."""
    rng = np.random.default_rng(n + m)
    paths, q, base, live = random_arena(rng, n, m, T)
    idx = monotone_lane(rng, n, T)
    assert_arena_kernels_match(paths, q, jnp.asarray(base), jnp.asarray(idx),
                               jnp.asarray(live), block_n=block_n,
                               block_m=block_m)


def segment_lane(rng, sizes, *, delta=0):
    """The lane a stack of sealed segments (each in trie order, roots
    laid out in column order) gives, plus ``delta`` columns at the
    delta's slot after them: (lane, T)."""
    lanes, root0 = [], 0
    for size in sizes:
        lane = monotone_lane(rng, size, size)
        lane -= lane[0]
        lanes.append(root0 + lane)
        root0 += int(lane[-1]) + 1
    lanes.append(np.full(delta, root0, np.int32))
    return np.concatenate(lanes).astype(np.int32), root0 + 1


@pytest.mark.parametrize("m", [1, 2, 3, 4, 8])
@pytest.mark.parametrize("case", [
    "segments",        # windows across segment boundaries, n ragged
    "delta_slot",      # sealed columns then the delta's slot
    "all_big",         # every root pruned: the windows read only BIG
    "dead",            # most lanes tombstoned
    "flat",            # one root for long runs: windows of one tile
])
def test_windowed_verify_matches_oracle(case, m):
    """The windowed base expansion at the shapes the index gives it:
    multi-tile blocks (block_n = 512, four 128-lane sub-blocks) over
    lanes that cross segment boundaries and the delta slot, pruned
    windows and dead lanes, for every batch bucket up to 8 — bit for
    bit against the oracles' plain gather."""
    rng = np.random.default_rng(len(case) * 10 + m)
    block_n = 512
    if case == "segments":
        idx, T = segment_lane(rng, [700, 130, 1001])
    elif case == "delta_slot":
        idx, T = segment_lane(rng, [900, 400], delta=333)
    elif case == "flat":
        idx = monotone_lane(rng, 1500, 1500, rise=0.002)
        T = int(idx[-1]) + 1
    else:
        idx, T = segment_lane(rng, [1100, 500])
    paths, q, base, live = random_arena(
        rng, len(idx), m, T, big_share=1.0 if case == "all_big" else 0.3,
        live_share=0.1 if case == "dead" else 0.8)
    assert_arena_kernels_match(paths, q, jnp.asarray(base), jnp.asarray(idx),
                               jnp.asarray(live), block_n=block_n,
                               block_m=8, tau=12)


@pytest.mark.parametrize("lane", [
    [0, 2, 3],         # a step of 2
    [3, 2, 2],         # a step down
    [-1, 0, 1],        # a negative root
])
def test_window_plan_refuses_a_lane_that_breaks_the_invariant(lane):
    """No silent fallback: a lane that does not rise in steps of 0 or 1
    has no window plan, and the kernel path has nothing else."""
    with pytest.raises(ValueError, match="steps of 0 or 1"):
        ops.plan_windows(np.asarray(lane), 128)
    b, n, m = 2, 128, 1
    with pytest.raises(ValueError, match="window plan"):
        ops.sparse_verify_arena_packed(
            jnp.zeros((n,), jnp.uint32), jnp.zeros((m,), jnp.uint32),
            jnp.zeros((m, 4), jnp.int32), jnp.zeros((n,), jnp.int32),
            jnp.ones((n,), bool), b=b, S=4, tau=1, block_n=n,
            use_kernel=True)


def test_window_plan_starts_and_steps():
    """The plan's start is each block's first 128-lane tile, its steps
    the tile rises between sub-blocks, its roots what the blocks span."""
    lane = np.concatenate([np.zeros(200, np.int64),
                           np.arange(1, 200 + 1),       # 200 lanes, +1
                           np.full(112, 200)])          # 512 lanes
    plan = ops.plan_windows(lane, 256)
    # blocks: lanes 0-255 (roots 0..56), 256-511 (roots 57..200)
    np.testing.assert_array_equal(plan.start, [0, 0])
    # sub-block starts: lane 128 -> root 0 (tile 0); lane 384 -> root
    # 185 (tile 1), one tile up from lane 256's root 57 (tile 0)
    np.testing.assert_array_equal(plan.steps, [0, 1])
    assert plan.roots == 57 + (200 - 57 + 1)


def test_arena_kernel_dead_and_pruned_lanes_clamp_to_big():
    b, W, n, m = 2, 1, 256, 2
    paths = jnp.zeros((b, W, n), jnp.uint32)
    q = jnp.zeros((b, W, m), jnp.uint32)
    base = jnp.asarray([[0, BIG_I]] * m, jnp.int32)       # slot 1 pruned
    lane = np.asarray(([0] * 128) + ([1] * 128), np.int32)
    live = jnp.asarray(([True] * 64) + ([False] * 192))
    mask, dist = ops.sparse_verify_arena(
        paths, q, base, jnp.asarray(lane), live, tau=3,
        windows=ops.plan_windows(lane, 128), block_n=128, use_kernel=True)
    mask, dist = np.asarray(mask), np.asarray(dist)
    assert mask[:, :64].all()                  # live + reached, dist 0
    assert (dist[:, :64] == 0).all()
    assert not mask[:, 64:].any()              # dead or pruned
    assert (dist[:, 64:] == BIG_I).all()


# ---------------------------------------------------------------------------
# the headline property: fused == per-segment reference == static rebuild
# ---------------------------------------------------------------------------

@settings(max_examples=6, deadline=None)
@given(st.integers(0, 2 ** 16))
def test_fused_bit_identical_across_lifecycle_property(seed):
    """Random insert→delete→merge→compact interleavings: the fused arena
    path returns the same column planes, ids, and top-k as the reference
    fan-out AND a fresh static build over the survivors."""
    rng = np.random.default_rng(seed)
    L = int(rng.integers(6, 13))
    n = int(rng.integers(60, 300))
    k = int(rng.integers(1, 10))
    db = rng.integers(0, 1 << _B, size=(n, L), dtype=np.uint8)
    idx = SegmentedIndex(L, _B, delta_cap=int(rng.integers(16, 96)))
    surv = np.zeros(n, bool)
    inserted = 0
    while inserted < n:
        step = int(rng.integers(1, 48))
        ids = idx.insert(db[inserted:inserted + step])
        surv[ids] = True
        inserted += step
        if rng.random() < 0.4 and surv.any():
            victims = np.flatnonzero(surv)
            victims = victims[rng.random(victims.size) < 0.25]
            idx.delete(victims)
            surv[victims] = False
        if rng.random() < 0.3:
            idx.merge()
        if rng.random() < 0.2:
            idx.compact()
        # query mid-stream: sealed segments + live delta buffer together
        if rng.random() < 0.5:
            qs = db[rng.integers(0, n, 2)]
            assert_columns_equal(idx, qs, int(rng.integers(0, L // 2 + 1)))
    if not surv.any():
        return
    qs = np.concatenate([db[rng.integers(0, n, 2)],
                         rng.integers(0, 1 << _B, size=(1, L),
                                      dtype=np.uint8)])
    assert_columns_equal(idx, qs, 2)
    assert_topk_equal(idx, qs, k)
    # and against the static oracle over survivors
    surv_ids = np.flatnonzero(surv)
    static = topk_batch(build_bst(db[surv], _B), qs, k)
    mapped = np.where(np.asarray(static.ids) >= 0,
                      surv_ids[np.maximum(np.asarray(static.ids), 0)], -1)
    got = idx.topk_batch(qs, k)
    np.testing.assert_array_equal(np.asarray(got.dists),
                                  np.asarray(static.dists))
    np.testing.assert_array_equal(np.asarray(got.ids), mapped)


@pytest.mark.parametrize("backend,kw", [
    ("bst", {}), ("multi", {"mi_blocks": 2}), ("sharded", {"n_shards": 2}),
])
@pytest.mark.parametrize("m", [1, 3, 8])
def test_fused_matches_reference_all_backends_and_batch_shapes(backend, kw,
                                                               m):
    rng = np.random.default_rng(hash((backend, m)) % 2 ** 31)
    L = 12
    db = rng.integers(0, 1 << _B, size=(260, L), dtype=np.uint8)
    idx = SegmentedIndex(L, _B, delta_cap=10 ** 9, backend=backend,
                         auto_merge=False, **kw)
    for lo in range(0, 240, 80):
        idx.insert(db[lo:lo + 80])
        idx.flush()
    ids = np.arange(240)
    idx.delete(ids[rng.choice(240, 40, replace=False)])
    idx.insert(db[240:])             # live delta buffer rides along
    assert len(idx.segments) == 3
    qs = np.concatenate([db[rng.integers(0, 260, max(m - 1, 1))][:m - 1],
                         rng.integers(0, 1 << _B, size=(1, L),
                                      dtype=np.uint8)])
    assert qs.shape[0] == m
    assert_columns_equal(idx, qs, 3)
    assert_topk_equal(idx, qs, 6)


def test_sharded_segmented_index_uses_arena_and_matches():
    rng = np.random.default_rng(77)
    L = 10
    db = rng.integers(0, 1 << _B, size=(300, L), dtype=np.uint8)
    sh = ShardedSegmentedIndex(L, _B, n_shards=3, delta_cap=40)
    sh_ref = ShardedSegmentedIndex(L, _B, n_shards=3, delta_cap=40,
                                   use_arena=False)
    ids = sh.insert(db)
    sh_ref.insert(db)
    dels = ids[rng.choice(300, 50, replace=False)]
    sh.delete(dels)
    sh_ref.delete(dels)
    qs = db[[3, 99]]
    got, want = sh.topk_batch(qs, 5), sh_ref.topk_batch(qs, 5)
    np.testing.assert_array_equal(np.asarray(got.ids), np.asarray(want.ids))
    np.testing.assert_array_equal(np.asarray(got.dists),
                                  np.asarray(want.dists))
    res_a = sh.search_batch(qs, 2)
    res_r = sh_ref.search_batch(qs, 2)
    np.testing.assert_array_equal(res_a.mask, res_r.mask)
    np.testing.assert_array_equal(res_a.dist, res_r.dist)


# ---------------------------------------------------------------------------
# dispatch accounting: ONE launch per rung, independent of segment count
# ---------------------------------------------------------------------------

def sixteen_segment_index(with_delta=True):
    rng = np.random.default_rng(5)
    L = 12
    db = rng.integers(0, 1 << _B, size=(520, L), dtype=np.uint8)
    idx = SegmentedIndex(L, _B, delta_cap=10 ** 9, auto_merge=False)
    for lo in range(0, 512, 32):
        idx.insert(db[lo:lo + 32])
        idx.flush()
    if with_delta:
        idx.insert(db[512:])
    assert len(idx.segments) == 16
    return idx, db


def ladder_rungs(tau0, tau_final, L):
    """Replay the deterministic τ schedule: rungs executed from tau0
    until the ladder stopped at tau_final."""
    t, c = tau0, 1
    while t < tau_final:
        t = min(L, max(t + 1, 2 * t))
        c += 1
    return c


def test_dispatch_spy_one_launch_per_rung_at_16_segments():
    idx, db = sixteen_segment_index()
    qs = db[[3, 77, 200]]
    # single-rung top-k (tau0=L can never escalate)
    reset_dispatch_stats()
    idx.topk_batch(qs, 5, tau0=idx.L)
    spy = dispatch_stats()
    # 520 sealed columns: every verify runs on the oracle, no windows
    assert spy == {"total": 1, "fused": 1, "fanout": 0, "rerank": 0,
                   "windowed_cols": 0, "windows": 0,
                   "window_roots": 0}, spy
    # multi-rung top-k: exactly one launch per rung
    reset_dispatch_stats()
    res = idx.topk_batch(qs, 5, tau0=0)
    spy = dispatch_stats()
    rungs = ladder_rungs(0, res.tau, idx.L)
    assert rungs > 1
    assert spy["total"] == spy["fused"] == rungs, (spy, rungs)
    # range search: one launch, and the column contract carries it
    reset_dispatch_stats()
    res = idx.search_columns_batch(qs, 3)
    assert dispatch_stats()["total"] == 1
    assert res.dist.shape == (3, 520) and res.ids.shape == (520,)
    # the reference fan-out pays one launch per segment + delta instead
    idx.use_arena = False
    reset_dispatch_stats()
    idx.topk_batch(qs, 5, tau0=idx.L)
    spy = dispatch_stats()
    assert spy["total"] >= 17 and spy["fused"] == 0, spy


def test_dispatch_spy_flat_in_segment_count_for_search():
    rng = np.random.default_rng(6)
    L = 10
    db = rng.integers(0, 1 << _B, size=(256, L), dtype=np.uint8)
    for n_seg in (1, 4, 16):
        idx = SegmentedIndex(L, _B, delta_cap=10 ** 9, auto_merge=False)
        chunk = 256 // n_seg
        for lo in range(0, 256, chunk):
            idx.insert(db[lo:lo + chunk])
            idx.flush()
        assert len(idx.segments) == n_seg
        reset_dispatch_stats()
        idx.search_columns_batch(db[:2], 2)
        assert dispatch_stats()["total"] == 1, n_seg


# ---------------------------------------------------------------------------
# arena maintenance: incremental updates, not per-query re-uploads
# ---------------------------------------------------------------------------

def test_arena_appends_on_flush_and_rebuilds_on_merge():
    rng = np.random.default_rng(7)
    db = rng.integers(0, 4, size=(120, 8), dtype=np.uint8)
    idx = SegmentedIndex(8, 2, delta_cap=10 ** 9, auto_merge=False)
    idx.insert(db[:40])
    idx.flush()
    idx.topk_batch(db[:2], 3)            # builds the column store
    ar = idx._arena
    assert ar.n_cols == 40
    idx.insert(db[40:80])
    idx.flush()                          # append path: same store object
    idx.topk_batch(db[:2], 3)
    assert idx._arena is ar
    assert ar.n_cols == 80
    assert len(ar.serials) == 2
    idx.merge()                          # non-append change: full rebuild
    idx.topk_batch(db[:2], 3)
    assert idx._arena.n_cols == 80
    assert len(idx._arena.serials) == 1


def test_full_layout_arena_appends_on_flush_too():
    """The full-length reference layout keeps the PR-5 incremental
    maintenance: flush appends to the same ``_ColumnArena`` arrays."""
    rng = np.random.default_rng(7)
    db = rng.integers(0, 4, size=(80, 8), dtype=np.uint8)
    idx = SegmentedIndex(8, 2, delta_cap=10 ** 9, auto_merge=False,
                         layout="full")
    idx.insert(db[:40])
    idx.flush()
    idx.topk_batch(db[:2], 3)
    ar = idx._arena
    assert ar.cols.shape[-1] == ar.n_cols == 40
    idx.insert(db[40:])
    idx.flush()
    idx.topk_batch(db[:2], 3)
    assert idx._arena is ar and ar.cols.shape[-1] == 80


def test_delete_flips_device_liveness_lane_in_place():
    rng = np.random.default_rng(8)
    db = rng.integers(0, 4, size=(60, 8), dtype=np.uint8)
    idx = SegmentedIndex(8, 2, delta_cap=10 ** 9, auto_merge=False)
    ids = idx.insert(db)
    idx.flush()
    res = idx.search(db[17], 0)
    assert res.mask[ids[17]]
    ar = idx._arena
    idx.delete(ids[17])                  # no rebuild: same arena arrays
    assert idx._arena is ar
    assert not idx.search(db[17], 0).mask[ids[17]]
    col = int(np.flatnonzero(ar.col_ids == ids[17])[0])   # rows in trie order
    assert not bool(np.asarray(ar.live)[col])
    assert int(np.asarray(ar.live).sum()) == 59


def test_segment_serials_are_unique_and_survive_merge_away():
    rng = np.random.default_rng(9)
    db = rng.integers(0, 4, size=(90, 8), dtype=np.uint8)
    idx = SegmentedIndex(8, 2, delta_cap=10 ** 9, backend="sharded",
                         n_shards=2, auto_merge=False)
    for lo in range(0, 90, 30):
        idx.insert(db[lo:lo + 30])
        idx.flush()
    serials = [seg.serial for seg in idx.segments]
    assert len(set(serials)) == len(serials) == 3
    idx.topk_batch(db[:2], 3)            # populate per-serial caches
    idx.merge()
    idx.merge()
    assert [seg.serial for seg in idx.segments] != serials
    # a post-merge query must hit the NEW segments' searchers, never a
    # stale cache entry for a merged-away index
    assert_topk_equal(idx, db[[5, 41]], 4)


# ---------------------------------------------------------------------------
# bucketed delta scan + compile-cache steady state
# ---------------------------------------------------------------------------

def test_delta_planes_bucket_to_power_of_two():
    rng = np.random.default_rng(10)
    idx = SegmentedIndex(8, 2, delta_cap=10 ** 9)
    for total in (1, 2, 3, 5, 9):
        idx.insert(rng.integers(0, 4, size=(total - len(idx._delta_ids), 8),
                                dtype=np.uint8))
        assert idx._delta_planes().shape[-1] == bucket_m(total)


def test_streaming_inserts_within_bucket_do_not_retrace():
    rng = np.random.default_rng(11)
    db = rng.integers(0, 4, size=(80, 8), dtype=np.uint8)
    idx = SegmentedIndex(8, 2, delta_cap=10 ** 9, auto_merge=False)
    idx.insert(db[:40])
    idx.flush()
    idx.insert(db[40:45])                 # delta bucket 8
    q = db[:2]
    idx.topk_batch(q, 3, tau0=2)          # warm (bucket nd=5 -> 8)
    warm = searcher_cache_info()
    for row in range(45, 48):             # 6, 7, 8 rows: same bucket
        idx.insert(db[row:row + 1])
        idx.topk_batch(q, 3, tau0=2)
    info = searcher_cache_info()
    assert info["traces"] == warm["traces"], (warm, info)
    assert info["misses"] == warm["misses"], (warm, info)


# ---------------------------------------------------------------------------
# column-compressed primary contract
# ---------------------------------------------------------------------------

def test_column_contract_is_primary_and_dense_plane_wraps_it():
    rng = np.random.default_rng(12)
    db = rng.integers(0, 4, size=(100, 10), dtype=np.uint8)
    idx = SegmentedIndex(10, 2, delta_cap=40, auto_merge=False)
    ids = idx.insert(db)
    idx.delete(ids[:30])
    idx.compact()                         # physical rows shrink to 70+delta
    qs = db[[40, 90]]
    cols = idx.search_columns_batch(qs, 3)
    R = cols.dist.shape[1]
    assert R == idx.n_live                # churn cost tracks live corpus
    assert R < idx.n_ids                  # ... not ids-ever-assigned
    np.testing.assert_array_equal(np.sort(cols.ids),
                                  np.arange(30, 100))
    dense = idx.search_batch(qs, 3)       # opt-in dense plane
    assert dense.dist.shape == (2, idx.n_ids)
    plane = np.full((2, idx.n_ids), BIG_I, np.int32)
    plane[:, cols.ids] = cols.dist
    np.testing.assert_array_equal(dense.dist, plane)
    np.testing.assert_array_equal(dense.mask, plane <= 3)
