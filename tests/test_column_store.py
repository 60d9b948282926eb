"""Tiered suffix column store (DESIGN.md §7).

The layout layer must be *bit-identical* to the full-length arena and
the per-segment reference across the whole lifecycle (the suffix
columns drop exactly the bits the traversal's prefix distance already
carries); the placement layer must answer from the cold tier at the
same one-fused-dispatch-per-rung cost as the hot tier; and the
accounting must show the suffix layout's bytes-per-row win (>= 2x on
the review geometry L=16, b=2)."""

import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # clean env: deterministic fallback shim
    from _hypothesis_compat import given, settings, st

from repro.core import (SegmentedIndex, ShardedSegmentedIndex,
                        dispatch_stats, geometry_for, reset_dispatch_stats,
                        reset_tier_stats, tier_stats)
from repro.core.column_store import TIER_COLD, TIER_HOT
from repro.core.hamming import n_words, pack_suffix_words, pack_vertical, \
    unpack_vertical

_KW = dict(delta_cap=50, auto_merge=False)


def _popcount32(x):
    return np.unpackbits(
        np.asarray(x, np.uint32).view(np.uint8)).astype(np.int64) \
        .reshape(np.shape(x) + (32,)).sum(axis=-1)


# -- layout primitives ---------------------------------------------------

def test_pack_unpack_vertical_roundtrip():
    rng = np.random.default_rng(0)
    for b, L, n in ((1, 5, 7), (2, 16, 33), (3, 40, 11)):
        sk = rng.integers(0, 2 ** b, size=(n, L), dtype=np.uint8)
        np.testing.assert_array_equal(
            unpack_vertical(pack_vertical(sk, b), b, L), sk)


def test_pack_suffix_words_distance_identity():
    """popcount(OR over the b in-word bit fields of the XOR) is the
    Hamming distance — the single-word analogue of the plane identity."""
    rng = np.random.default_rng(1)
    b, S = 2, 16
    a = rng.integers(0, 4, size=(50, S), dtype=np.uint8)
    c = rng.integers(0, 4, size=(50, S), dtype=np.uint8)
    x = pack_suffix_words(a, b) ^ pack_suffix_words(c, b)
    field = np.uint32((1 << S) - 1)
    acc = (x & field) | ((x >> np.uint32(S)) & field)
    np.testing.assert_array_equal(_popcount32(acc), (a != c).sum(axis=1))
    with pytest.raises(ValueError):
        pack_suffix_words(np.zeros((1, 20), np.uint8), 2)   # 2*20 > 32


def test_geometry_for_picks_packed_vs_plane():
    assert geometry_for(16, 2, 4) == (12, True, 1)     # b*S = 24 <= 32
    g = geometry_for(64, 8, 0)                         # b*S = 512
    assert not g.packed and g.row_words == 8 * n_words(64)


# -- lifecycle bit-identity ----------------------------------------------

def _snapshots(idx, db, qs, k):
    """Query after every lifecycle stage: flush -> delete -> merge-to-one
    -> compact.  Chunked inserts leave a multi-segment stack so the
    merge stage actually merges."""
    out = []
    chunk = max(1, len(db) // 4)
    ids = np.concatenate([idx.insert(db[lo:lo + chunk])
                          for lo in range(0, len(db), chunk)])
    idx.flush()
    out.append(idx.topk_batch(qs, k))
    idx.delete(ids[15:45])
    out.append(idx.topk_batch(qs, k))
    while idx.merge():
        pass
    out.append(idx.topk_batch(qs, k))
    idx.compact()
    out.append(idx.topk_batch(qs, k))
    return [(np.asarray(r.ids), np.asarray(r.dists)) for r in out]


def test_lifecycle_bit_identity_suffix_full_reference():
    rng = np.random.default_rng(11)
    db = rng.integers(0, 4, size=(160, 16), dtype=np.uint8)
    qs = db[:5]
    got = {layout: _snapshots(SegmentedIndex(16, 2, layout=layout, **_KW),
                              db, qs, 5)
           for layout in ("suffix", "full")}
    ref = _snapshots(SegmentedIndex(16, 2, use_arena=False, **_KW),
                     db, qs, 5)
    for stage, (r_ref_ids, r_ref_d) in enumerate(ref):
        for layout in ("suffix", "full"):
            ids, d = got[layout][stage]
            np.testing.assert_array_equal(ids, r_ref_ids)
            np.testing.assert_array_equal(d, r_ref_d)


@settings(max_examples=3, deadline=None)
@given(st.integers(min_value=0, max_value=10 ** 6))
def test_lifecycle_bit_identity_property(seed):
    """Random corpus + queries: suffix == full == reference after a full
    insert -> delete -> merge -> compact pass (ids AND dists)."""
    rng = np.random.default_rng(seed)
    db = rng.integers(0, 4, size=(96, 8), dtype=np.uint8)
    qs = rng.integers(0, 4, size=(3, 8), dtype=np.uint8)
    kw = dict(delta_cap=30, auto_merge=False)
    runs = [_snapshots(SegmentedIndex(8, 2, layout="suffix", **kw),
                       db, qs, 4),
            _snapshots(SegmentedIndex(8, 2, layout="full", **kw),
                       db, qs, 4),
            _snapshots(SegmentedIndex(8, 2, use_arena=False, **kw),
                       db, qs, 4)]
    for stage in range(len(runs[0])):
        for run in runs[1:]:
            np.testing.assert_array_equal(run[stage][0], runs[0][stage][0])
            np.testing.assert_array_equal(run[stage][1], runs[0][stage][1])


def test_plane_fallback_geometry_bit_identical():
    """L=24, b=2: segments collapse shallow enough that b*S > 32, so the
    store takes the plane-packed fallback path — still bit-identical to
    the full-length arena."""
    rng = np.random.default_rng(12)
    db = rng.integers(0, 4, size=(120, 24), dtype=np.uint8)
    kw = dict(delta_cap=60, auto_merge=False)
    s = SegmentedIndex(24, 2, layout="suffix", **kw)
    f = SegmentedIndex(24, 2, layout="full", **kw)
    for idx in (s, f):
        idx.insert(db)
        idx.flush()
    rs, rf = s.topk_batch(db[:4], 6), f.topk_batch(db[:4], 6)
    np.testing.assert_array_equal(np.asarray(rs.ids), np.asarray(rf.ids))
    np.testing.assert_array_equal(np.asarray(rs.dists), np.asarray(rf.dists))
    assert any(not blk.geom.packed for blk in s._refresh_store().blocks)


def test_layout_validated():
    with pytest.raises(ValueError):
        SegmentedIndex(8, 2, layout="columnar")


# -- placement: cold tier ------------------------------------------------

def test_cold_tier_bit_identical_one_fused_dispatch_per_rung():
    """hot_bytes=0 forces every sealed block cold: answers must match the
    hot store bit for bit, at the SAME number of fused launches (staging
    is a transfer, not a program launch) and zero per-segment fan-out."""
    rng = np.random.default_rng(13)
    db = rng.integers(0, 4, size=(120, 16), dtype=np.uint8)
    qs = rng.integers(0, 4, size=(4, 16), dtype=np.uint8)
    kw = dict(delta_cap=10 ** 9, auto_merge=False)
    hot = SegmentedIndex(16, 2, layout="suffix", **kw)
    cold = SegmentedIndex(16, 2, layout="suffix", hot_bytes=0, **kw)
    full = SegmentedIndex(16, 2, layout="full", **kw)
    for idx in (hot, cold, full):
        for lo in range(0, 120, 40):            # 3 sealed segments
            idx.insert(db[lo:lo + 40])
            idx.flush()
    reset_dispatch_stats()
    rh = hot.topk_batch(qs, 5)
    d_hot = dispatch_stats()
    reset_tier_stats()
    reset_dispatch_stats()
    rc = cold.topk_batch(qs, 5)
    d_cold = dispatch_stats()
    rf = full.topk_batch(qs, 5)
    np.testing.assert_array_equal(np.asarray(rc.ids), np.asarray(rh.ids))
    np.testing.assert_array_equal(np.asarray(rc.dists), np.asarray(rh.dists))
    np.testing.assert_array_equal(np.asarray(rc.ids), np.asarray(rf.ids))
    assert d_cold["fanout"] == 0 and d_cold["total"] == d_cold["fused"]
    assert d_cold["fused"] == d_hot["fused"]    # cold adds no launches
    ts = tier_stats()
    assert ts["demotions"] == 3                 # 3 sealed blocks, all cold
    assert ts["prefetches"] >= 3 and ts["staged_bytes"] > 0
    tier = cold.stats()["tier"]
    assert tier["hot_blocks"] == 0 and tier["cold_blocks"] == 3
    assert tier["hot_bytes"] == 0 and tier["cold_bytes"] > 0


def test_lru_demotion_and_promotion_under_budget():
    rng = np.random.default_rng(14)
    db = rng.integers(0, 4, size=(120, 16), dtype=np.uint8)
    qs = db[:3]
    idx = SegmentedIndex(16, 2, layout="suffix", delta_cap=10 ** 9,
                         auto_merge=False)
    for lo in range(0, 120, 40):                # 3 sealed segments
        idx.insert(db[lo:lo + 40])
        idx.flush()
    r0 = idx.topk_batch(qs, 4)
    store = idx._refresh_store()
    assert store.tier_summary()["hot_blocks"] == 3
    blk_bytes = store.blocks[0].col_bytes       # 40 rows * 1 word = 160 B
    assert blk_bytes == 40 * 4
    reset_tier_stats()
    store.hot_bytes = 2 * blk_bytes
    store._enforce_budget()                     # LRU: oldest block demotes
    assert store.tier_summary() == {
        "hot_blocks": 2, "cold_blocks": 1,
        "hot_bytes": 2 * blk_bytes, "cold_bytes": blk_bytes}
    assert store.blocks[0].tier == TIER_COLD
    assert tier_stats()["demotions"] == 1
    gen0 = store.gen
    r1 = idx.topk_batch(qs, 4)                  # mixed hot/cold answer
    np.testing.assert_array_equal(np.asarray(r1.ids), np.asarray(r0.ids))
    np.testing.assert_array_equal(np.asarray(r1.dists),
                                  np.asarray(r0.dists))
    store.hot_bytes = 10 ** 9                   # budget grew: promote back
    store._enforce_budget()
    assert store.tier_summary()["cold_blocks"] == 0
    assert store.blocks[0].tier == TIER_HOT
    assert tier_stats()["promotions"] == 1 and store.gen > gen0
    r2 = idx.topk_batch(qs, 4)
    np.testing.assert_array_equal(np.asarray(r2.dists),
                                  np.asarray(r0.dists))


def test_sharded_stacks_split_hot_budget():
    rng = np.random.default_rng(15)
    db = rng.integers(0, 4, size=(120, 16), dtype=np.uint8)
    qs = db[:3]
    cold = ShardedSegmentedIndex(16, 2, 2, delta_cap=30, auto_merge=False,
                                 hot_bytes=0)
    ref = ShardedSegmentedIndex(16, 2, 2, delta_cap=30, auto_merge=False,
                                layout="full")
    for idx in (cold, ref):
        idx.insert(db)
        idx.flush()
    rc, rr = cold.topk_batch(qs, 5), ref.topk_batch(qs, 5)
    np.testing.assert_array_equal(np.asarray(rc.ids), np.asarray(rr.ids))
    np.testing.assert_array_equal(np.asarray(rc.dists), np.asarray(rr.dists))
    st_ = cold.stats()
    assert st_["host_bytes"] > 0 and "device_bytes" in st_


# -- accounting ----------------------------------------------------------

def test_suffix_layout_at_least_halves_device_column_bytes():
    """The acceptance ratio on the review geometry (L=16, b=2): the
    full-length layout spends 2 uint32 words per row, the packed suffix
    exactly one -> suffix column bytes <= half, integer-exact."""
    rng = np.random.default_rng(16)
    db = rng.integers(0, 4, size=(160, 16), dtype=np.uint8)
    s = SegmentedIndex(16, 2, layout="suffix", **_KW)
    f = SegmentedIndex(16, 2, layout="full", **_KW)
    for idx in (s, f):
        idx.insert(db)
        idx.flush()
        idx.topk_batch(db[:2], 3)               # builds the store/arena
    sfx = s._refresh_store().col_bytes()
    ful = f._refresh_arena().col_bytes()
    assert sfx > 0 and ful >= 2 * sfx
    st_s, st_f = s.stats(), f.stats()
    # one consistent ledger: same model bits either way (the model is the
    # succinct index + lanes, not the layout), device bytes strictly less
    assert st_s["space_bits"] == st_f["space_bits"]
    assert st_s["device_bytes"] < st_f["device_bytes"]
    # forced cold: column payload leaves the device entirely
    c = SegmentedIndex(16, 2, layout="suffix", hot_bytes=0, **_KW)
    c.insert(db)
    c.flush()
    c.topk_batch(db[:2], 3)
    store = c._refresh_store()
    assert store.tier_summary()["hot_bytes"] == 0
    assert store.host_bytes() == store.col_bytes() == sfx


# -- windowed verify counters ---------------------------------------------

@pytest.mark.parametrize("layout,m", [("suffix", 3), ("suffix", 9),
                                      ("full", 3)])
def test_windowed_verify_counters_report_sealed_columns(layout, m):
    """``dispatch_stats()`` tallies, per fused launch, the columns whose
    base came through the in-kernel window expansion, the windows (one
    per column block and query tile) and the roots they span — so the
    mean roots per window reads off the counters."""
    from repro.core import bucket_m
    rng = np.random.default_rng(40)
    db = rng.integers(0, 4, size=(3000, 12), dtype=np.uint8)
    idx = SegmentedIndex(12, 2, delta_cap=10 ** 9, auto_merge=False,
                         layout=layout)
    ids = idx.insert(db[:2500])
    idx.flush()
    idx.insert(db[2500:])                         # 500 delta rows
    idx.delete(ids[::9])
    t_root = int(idx.segments[0].index.tail.t_root)
    qs = db[rng.choice(3000, m)]
    reset_dispatch_stats()
    res = idx.topk_batch(qs, 4, tau0=12)          # one rung
    st = dispatch_stats()
    # the suffix layout verifies the sealed columns only; the full one
    # the delta's (bucketed) columns too, at its trailing slot
    cols = 2500 if layout == "suffix" else 2500 + bucket_m(500)
    tiles = bucket_m(m) // min(8, bucket_m(m))
    assert st["fused"] == 1 and st["windowed_cols"] == cols
    assert st["windows"] == -(-cols // 2048) * tiles
    extra = 0 if layout == "suffix" else 1        # the delta's slot
    assert (t_root + extra) * tiles <= st["window_roots"] \
        <= (t_root + extra + 1) * tiles           # one root per boundary
    assert 1 <= st["window_roots"] / st["windows"] <= 2048
    alive = np.ones(3000, bool)
    alive[ids[::9]] = False
    d = (db[None, :, :] != qs[:, None, :]).sum(-1)
    d = np.where(alive[None, :], d, 1 << 20)
    for r in range(m):
        want = np.lexsort((np.arange(3000), d[r]))[:4]
        np.testing.assert_array_equal(np.asarray(res.ids)[r], want)
        np.testing.assert_array_equal(np.asarray(res.dists)[r], d[r][want])
