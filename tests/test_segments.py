"""Dynamic segmented index: streaming insert/delete/merge/compact must be
indistinguishable from a fresh static build over the surviving sketches —
bit-identical top-k (dists AND ids, after the monotone global-id mapping)
and range results — plus lifecycle mechanics (tombstones, size-tiered
merges, space accounting) and every backend (bst / multi / sharded)."""

import os

import numpy as np
import jax.numpy as jnp
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # clean env: deterministic fallback shim
    from _hypothesis_compat import given, settings, st

from repro.core import (SegmentedIndex, ShardedSegmentedIndex, bucket_m,
                        build_bst, tombstone_bits, topk_batch)
from repro.core.bst import BIG
from repro.core.hamming import hamming_pairwise_naive

BIG_I = int(BIG)
_B = 2  # alphabet bits shared by the fixed-shape tests


def brute(qs, db):
    return np.asarray(hamming_pairwise_naive(jnp.asarray(qs),
                                             jnp.asarray(db)))


def check_roundtrip(idx, db, surv, qs, k):
    """Segmented results == static build over survivors: static row r
    corresponds to the r-th surviving global id (insertion order is
    monotone in global id, so (distance, id) tie order matches)."""
    surv_ids = np.flatnonzero(surv)
    ref = topk_batch(build_bst(db[surv], idx.b), qs, k)
    mapped = np.where(np.asarray(ref.ids) >= 0,
                      surv_ids[np.maximum(np.asarray(ref.ids), 0)], -1)
    got = idx.topk_batch(qs, k)
    np.testing.assert_array_equal(np.asarray(got.dists),
                                  np.asarray(ref.dists))
    np.testing.assert_array_equal(np.asarray(got.ids), mapped)


@settings(max_examples=8, deadline=None)
@given(st.integers(0, 2 ** 16))
def test_insert_delete_merge_compact_roundtrip_property(seed):
    """The headline property: a random interleaving of
    insert→delete→merge→compact round-trips to bit-identical top-k
    results vs a fresh static build on the surviving sketches."""
    rng = np.random.default_rng(seed)
    L = int(rng.integers(6, 14))
    n = int(rng.integers(50, 500))
    k = int(rng.integers(1, 12))
    db = rng.integers(0, 1 << _B, size=(n, L), dtype=np.uint8)
    idx = SegmentedIndex(L, _B, delta_cap=int(rng.integers(16, 128)))
    surv = np.zeros(n, bool)
    inserted = 0
    while inserted < n:
        step = int(rng.integers(1, 64))
        ids = idx.insert(db[inserted:inserted + step])
        surv[ids] = True
        inserted += step
        if rng.random() < 0.5 and surv.any():
            victims = np.flatnonzero(surv)
            victims = victims[rng.random(victims.size) < 0.2]
            assert idx.delete(victims) == victims.size
            surv[victims] = False
    idx.flush()
    idx.merge()
    idx.compact()
    if not surv.any():
        assert idx.n_live == 0
        return
    qs = np.concatenate([db[rng.integers(0, n, 2)],
                         rng.integers(0, 1 << _B, size=(1, L),
                                      dtype=np.uint8)])
    check_roundtrip(idx, db, surv, qs, k)


@pytest.mark.parametrize("backend,kw", [
    ("bst", {}), ("multi", {"mi_blocks": 2}), ("sharded", {"n_shards": 2}),
])
def test_backends_roundtrip(backend, kw):
    rng = np.random.default_rng(7)
    L = 16
    db = rng.integers(0, 1 << _B, size=(300, L), dtype=np.uint8)
    idx = SegmentedIndex(L, _B, delta_cap=90, backend=backend, **kw)
    ids = idx.insert(db)
    dels = ids[rng.choice(300, 50, replace=False)]
    idx.delete(dels)
    idx.flush()
    surv = np.ones(300, bool)
    surv[dels] = False
    qs = np.stack([db[0], db[123],
                   rng.integers(0, 1 << _B, L).astype(np.uint8)])
    check_roundtrip(idx, db, surv, qs, 9)


def test_range_search_matches_bruteforce_mid_stream():
    """Queries mid-stream (sealed segments + a live delta buffer) return
    the exact τ-ball over live ids, with exact distances."""
    rng = np.random.default_rng(8)
    L, tau = 12, 3
    db = rng.integers(0, 1 << _B, size=(400, L), dtype=np.uint8)
    idx = SegmentedIndex(L, _B, delta_cap=128)
    ids = idx.insert(db[:350])
    idx.delete(ids[::5])
    idx.insert(db[350:])           # stays in the delta buffer
    assert len(idx._delta_ids) > 0
    surv = np.ones(400, bool)
    surv[ids[::5]] = False
    qs = db[[1, 51, 201]]
    res = idx.search_batch(qs, tau)
    assert res.overflow == 0
    d = brute(qs, db)
    want = (d <= tau) & surv[None, :]
    np.testing.assert_array_equal(res.mask, want)
    np.testing.assert_array_equal(res.dist[want], d[want])
    assert (res.dist[~want] == BIG_I).all()


def test_deleted_ids_never_return():
    rng = np.random.default_rng(9)
    db = rng.integers(0, 4, size=(120, 10), dtype=np.uint8)
    idx = SegmentedIndex(10, 2, delta_cap=60)
    ids = idx.insert(db)
    # delete the exact-match target: it must vanish from results
    assert idx.delete(ids[17]) == 1
    res = idx.search(db[17], 0)
    assert not res.mask[ids[17]]
    # deleting again (or an unknown id) is a no-op
    assert idx.delete(ids[17]) == 0
    assert idx.delete(np.int64(10 ** 9)) == 0
    # duplicate ids in one call count once
    assert idx.delete(np.array([ids[20], ids[20], ids[20]])) == 1


def test_size_tiered_merge_policy_bounds_segment_count():
    rng = np.random.default_rng(10)
    db = rng.integers(0, 4, size=(1024, 8), dtype=np.uint8)
    idx = SegmentedIndex(8, 2, delta_cap=64, auto_merge=True)
    for lo in range(0, 1024, 64):
        idx.insert(db[lo:lo + 64])
    # size-tiered invariant: at most one segment per ⌊log2 n⌋ tier
    tiers = [max(seg.n, 1).bit_length() for seg in idx.segments]
    assert len(tiers) == len(set(tiers))
    assert idx.counters["merges"] > 0
    assert idx.n_live == 1024


def test_compact_reclaims_tombstones_and_preserves_results():
    rng = np.random.default_rng(11)
    db = rng.integers(0, 4, size=(200, 10), dtype=np.uint8)
    idx = SegmentedIndex(10, 2, delta_cap=64)
    ids = idx.insert(db)
    idx.flush()
    idx.delete(ids[:80])
    before = idx.space_bits()
    assert idx.compact() >= 1
    assert idx.space_bits() < before
    assert sum(seg.n for seg in idx.segments) == 120
    surv = np.zeros(200, bool)
    surv[80:] = True
    check_roundtrip(idx, db, surv, db[[90, 150]], 5)


def test_fully_deleted_segment_is_dropped_and_empty_index_answers():
    rng = np.random.default_rng(12)
    db = rng.integers(0, 4, size=(50, 8), dtype=np.uint8)
    idx = SegmentedIndex(8, 2, delta_cap=10)
    ids = idx.insert(db)
    idx.delete(ids)
    assert idx.n_live == 0
    res = idx.topk_batch(db[:2], 3)
    assert (np.asarray(res.ids) == -1).all()
    assert (np.asarray(res.dists) == BIG_I).all()
    idx.compact()
    assert len(idx.segments) == 0


def test_sharded_segmented_index_roundtrip():
    rng = np.random.default_rng(13)
    L = 12
    db = rng.integers(0, 4, size=(500, L), dtype=np.uint8)
    sh = ShardedSegmentedIndex(L, 2, n_shards=3, delta_cap=40)
    ids = sh.insert(db)
    dels = ids[rng.choice(500, 70, replace=False)]
    assert sh.delete(dels) == 70
    sh.flush()
    sh.merge()
    surv = np.ones(500, bool)
    surv[dels] = False
    qs = np.stack([db[5], rng.integers(0, 4, L).astype(np.uint8)])
    surv_ids = np.flatnonzero(surv)
    ref = topk_batch(build_bst(db[surv], 2), qs, 7)
    mapped = np.where(np.asarray(ref.ids) >= 0,
                      surv_ids[np.maximum(np.asarray(ref.ids), 0)], -1)
    got = sh.topk_batch(qs, 7)
    np.testing.assert_array_equal(np.asarray(got.dists),
                                  np.asarray(ref.dists))
    np.testing.assert_array_equal(np.asarray(got.ids), mapped)
    # range plane agrees with brute force too
    res = sh.search_batch(qs, 3)
    d = brute(qs, db)
    want = (d <= 3) & surv[None, :]
    np.testing.assert_array_equal(res.mask, want)


def test_with_live_searcher_matches_postfilter_and_does_not_rejit():
    """The traced-liveness searcher (get_searcher with_live=True) equals
    post-filtering the plain searcher, and flipping tombstones reuses
    the same compiled fn (liveness is data, not a trace constant)."""
    from repro.core import clear_searcher_cache, get_searcher, \
        searcher_cache_info
    rng = np.random.default_rng(14)
    db = rng.integers(0, 4, size=(250, 12), dtype=np.uint8)
    idx = build_bst(db, 2)
    qs = jnp.asarray(db[:4])
    live = np.ones(250, bool)
    live[rng.choice(250, 100, replace=False)] = False
    clear_searcher_cache()
    fn = get_searcher(idx, 3, batch=True, with_live=True)
    res = fn(qs, jnp.asarray(live))
    plain = get_searcher(idx, 3, batch=True)(qs)
    np.testing.assert_array_equal(
        np.asarray(res.mask), np.asarray(plain.mask) & live[None, :])
    want_d = np.where(np.asarray(plain.mask) & live[None, :],
                      np.asarray(plain.dist), BIG_I)
    np.testing.assert_array_equal(np.asarray(res.dist), want_d)
    misses = searcher_cache_info()["misses"]
    live2 = ~live
    fn2 = get_searcher(idx, 3, batch=True, with_live=True)
    fn2(qs, jnp.asarray(live2))
    assert searcher_cache_info()["misses"] == misses  # no re-jit on delete


def test_tombstone_space_accounting():
    assert tombstone_bits(1) == 32 + 64
    assert tombstone_bits(64) == 64 + 96
    rng = np.random.default_rng(15)
    db = rng.integers(0, 4, size=(100, 8), dtype=np.uint8)
    idx = SegmentedIndex(8, 2, delta_cap=1000)
    idx.insert(db)
    # delta-only: bucket-padded verify planes + one tombstone bitmap
    # (bucket_m(100) == 128 rows of b*W uint32 planes actually allocated)
    assert idx.space_bits() == bucket_m(100) * 2 * 1 * 32 + tombstone_bits(100)
    idx.flush()
    seg = idx.segments[0]
    # sealed: succinct index + tombstones + the 9 B/row arena lanes
    # (base_idx int32 + gids int32 + live bool) the fused path allocates
    assert idx.space_bits() == (seg.index.model_bits() + tombstone_bits(seg.n)
                                + seg.n * (4 + 4 + 1) * 8)
    led = idx.space_ledger()
    assert set(led) == {"model_bits", "device_bytes", "host_bytes"}
    assert led["model_bits"] == idx.space_bits()
    assert led["host_bytes"] >= int(seg.packed.nbytes)


def test_stable_ids_survive_merge_and_compact():
    rng = np.random.default_rng(16)
    db = rng.integers(0, 4, size=(160, 10), dtype=np.uint8)
    idx = SegmentedIndex(10, 2, delta_cap=40, auto_merge=False)
    ids = idx.insert(db)
    idx.flush()
    idx.delete(ids[10:20])
    while idx.merge():
        pass
    idx.compact()
    # the exact-match query still reports its original global id
    res = idx.topk(db[42], 1)
    assert int(res.ids[0]) == int(ids[42])
    assert int(res.dists[0]) == 0


# ---------------------------------------------------------------------------
# sealed rows in trie order (the windowed verify's invariant)
# ---------------------------------------------------------------------------

def assert_trie_order(idx):
    """Every sealed segment stores its rows in its trie's leaf order,
    ties by id — sketches lexicographically sorted, so each row's ℓ_s
    root rises in steps of 0 or 1 from 0 — and the column store's group
    lanes keep rising in steps of 0 or 1 across segments."""
    for seg in idx.segments:
        keys = (seg.ids,) + tuple(seg.sketches[:, c]
                                  for c in range(idx.L - 1, -1, -1))
        np.testing.assert_array_equal(np.lexsort(keys), np.arange(seg.n))
        lane = np.asarray(seg.index.tail.leaf_root)[
            np.asarray(seg.index.id_leaf)]
        assert lane[0] == 0 and set(np.diff(lane)) <= {0, 1}
    for group in idx._refresh_store().plan():
        assert set(np.diff(np.asarray(group.base_idx))) <= {0, 1}


def assert_answers_brute(idx, db, alive, qs, k=5, tau=2):
    """Top-k and range search equal a brute force over the live rows
    (ties by id)."""
    d = brute(qs, db)
    dl = np.where(alive[None, :], d, BIG_I)
    got = idx.topk_batch(qs, k)
    for r in range(len(qs)):
        want = np.lexsort((np.arange(len(db)), dl[r]))[:k]
        np.testing.assert_array_equal(np.asarray(got.ids)[r], want)
        np.testing.assert_array_equal(np.asarray(got.dists)[r], dl[r][want])
    res = idx.search_batch(qs, tau)
    np.testing.assert_array_equal(res.mask, dl <= tau)
    np.testing.assert_array_equal(res.dist, np.where(dl <= tau, dl, BIG_I))


@pytest.mark.parametrize("stage", ["flush", "merge", "compact", "delete",
                                   "recover"])
def test_sealed_rows_in_trie_order_through_lifecycle(tmp_path, stage):
    """Flush, merge (of non-adjacent segments, whose ids interleave),
    compact, delete and checkpoint + WAL replay all leave every sealed
    segment in trie order; a delete by id tombstones exactly the rows
    of those ids; answers stay equal to the brute force."""
    from repro.store import CollectionStore
    rng = np.random.default_rng(31)
    L = 8
    db = rng.integers(0, 4, size=(330, L), dtype=np.uint8)
    db[200:210] = db[5]                   # equal rows across segments
    db[40:44] = db[300]
    store = CollectionStore(str(tmp_path / "c"))
    idx = store.attach(SegmentedIndex(L, _B, delta_cap=100,
                                      auto_merge=False))
    ids = np.concatenate([idx.insert(db[lo:lo + 100])
                          for lo in range(0, 330, 100)])
    assert len(idx.segments) == 3         # + 30 delta rows
    alive = np.ones(len(db), bool)
    if stage != "flush":
        idx.merge(2, 0)                   # ids 200-299, then 0-99
    if stage in ("compact", "delete", "recover"):
        gone = rng.choice(len(db), 40, replace=False)
        assert idx.delete(ids[gone]) == 40
        alive[gone] = False
        for seg in idx.segments:          # the rows of those ids, no other
            np.testing.assert_array_equal(seg.live, alive[seg.ids])
    if stage == "compact":
        assert idx.compact() == len(idx.segments)
        assert all(seg.live.all() for seg in idx.segments)
    if stage == "recover":
        store.wal.sync()
        idx = CollectionStore(str(tmp_path / "c")).recover(
            SegmentedIndex(L, _B, delta_cap=100, auto_merge=False))
        for seg in idx.segments:
            np.testing.assert_array_equal(seg.live, alive[seg.ids])
    assert_trie_order(idx)
    assert_answers_brute(idx, db, alive, db[[5, 40, 123, 300, 329]])


def test_recover_rewrites_an_id_ordered_checkpoint_whole(tmp_path):
    """Segments checkpointed with their rows in id order (as stores wrote
    them before rows were sealed in trie order) recover in trie order
    under a fresh serial, so the next checkpoint writes them whole
    instead of patching ``live.npy`` beside id-ordered arrays: deletes
    made after a recovery survive the next one, and no deleted row
    comes back."""
    from repro.store import CollectionStore
    rng = np.random.default_rng(7)
    L = 8
    db = rng.integers(0, 4, size=(200, L), dtype=np.uint8)
    root = str(tmp_path / "c")

    def fresh():
        return SegmentedIndex(L, _B, delta_cap=100, auto_merge=False)

    store = CollectionStore(root)
    idx = store.attach(fresh())
    ids = np.concatenate([idx.insert(db[lo:lo + 100]) for lo in (0, 100)])
    alive = np.ones(len(db), bool)
    gone = rng.choice(len(db), 30, replace=False)
    idx.delete(ids[gone])
    alive[gone] = False
    store.checkpoint(0)
    for seg in idx.segments:
        d = os.path.join(root, f"seg_{seg.serial:012d}")
        with np.load(os.path.join(d, "arrays.npz")) as arr:
            arrays = {k: arr[k] for k in arr.files}
        by_id = np.argsort(arrays["ids"])
        assert not np.array_equal(by_id, np.arange(len(by_id)))
        np.savez(os.path.join(d, "arrays.npz"),
                 **{k: v[by_id] for k, v in arrays.items()})
        np.save(os.path.join(d, "live.npy"),
                np.load(os.path.join(d, "live.npy"))[by_id])
    store.close()
    for _ in range(2):
        store = CollectionStore(root)
        idx = store.recover(fresh())
        for seg in idx.segments:
            np.testing.assert_array_equal(seg.live, alive[seg.ids])
        gone = rng.choice(np.flatnonzero(alive), 10, replace=False)
        assert idx.delete(ids[gone]) == 10
        alive[gone] = False
        store.checkpoint(0)
        store.close()
    store = CollectionStore(root)
    idx = store.recover(fresh())
    for seg in idx.segments:
        np.testing.assert_array_equal(seg.live, alive[seg.ids])
    assert_trie_order(idx)
    assert_answers_brute(idx, db, alive, db[[3, 77, 150, 199]])
