"""Query-tiled batched verify kernel + natively batched traversal.

Kernel: interpret-mode bit-exactness of ``ops.sparse_verify_batch``
against the per-query oracle across tile-misaligned m and n, the m=1
degenerate tile, BIG clamping, and pad lanes; the grid really is
(⌈m/block_m⌉, ⌈n/block_n⌉) — the database is streamed once per query
TILE, not once per query.

Traversal: ``make_batch_searcher`` (the 2D-frontier batch trace) is
bit-identical to the per-query searcher, and ``topk_batch`` equals a
per-query ``topk`` loop.
"""

import numpy as np
import jax.numpy as jnp
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # clean env: deterministic fallback shim
    from _hypothesis_compat import given, settings, st

from repro.core import hamming as H
from repro.core.bst import BIG, build_bst, build_louds
from repro.core.search import (get_searcher, make_batch_searcher, topk,
                               topk_batch)
from repro.kernels import hamming_kernel, ops, ref
from repro.kernels.hamming_kernel import sparse_verify_batch_pallas


def make_db(rng, n, L, b):
    db = rng.integers(0, 1 << b, size=(n, L)).astype(np.uint8)
    planes = H.pack_vertical(db, b)          # (n, b, W)
    vert = np.transpose(planes, (1, 2, 0))   # (b, W, n)
    return db, jnp.asarray(vert)


# ---------------------------------------------------------------------------
# kernel bit-exactness vs the per-query oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,L,tau", [(2, 16, 2), (4, 32, 5), (8, 64, 3)])
@pytest.mark.parametrize("m,n,block_m,block_n", [
    (5, 390, 2, 128),    # neither m nor n a tile multiple
    (8, 384, 4, 128),    # both exact multiples
    (1, 200, 4, 128),    # m=1 degenerate tile (m < block_m)
    (3, 100, 8, 256),    # n < block_n entirely inside one padded block
    (1, 300, 8, 128),    # serving buckets at the default tile: pad lanes
    (8, 300, 8, 128),
    (16, 300, 8, 128),
    (64, 390, 24, 128),  # pad rows: 64 queries in 24-row tiles
])
def test_batch_verify_matches_per_query_oracle(b, L, tau, m, n, block_m,
                                               block_n):
    rng = np.random.default_rng(b * 100 + L + m + n)
    db, paths_vert = make_db(rng, n, L, b)
    qs, q_vert = make_db(rng, m, L, b)
    base = rng.integers(0, tau + 3, size=(m, n)).astype(np.int32)
    got, got_d = ops.sparse_verify_batch(paths_vert, q_vert,
                                         jnp.asarray(base), tau=tau,
                                         block_m=block_m, block_n=block_n,
                                         use_kernel=True)
    got, got_d = np.asarray(got), np.asarray(got_d)
    assert got.shape == got_d.shape == (m, n)
    for i in range(m):
        want, want_d = ref.sparse_verify_ref(paths_vert, q_vert[..., i],
                                             jnp.asarray(base[i]), tau)
        np.testing.assert_array_equal(got[i], np.asarray(want).astype(np.int32))
        np.testing.assert_array_equal(got_d[i], np.asarray(want_d))
    # distances are exact: base + per-query suffix Hamming distance
    suffix = (qs[:, None, :] != db[None, :, :]).sum(axis=2)
    np.testing.assert_array_equal(got_d, base + suffix)


def test_batch_verify_big_clamps_and_pad_lanes_never_survive():
    """BIG base distances (pruned subtries) clamp to exactly BIG, and the
    raw kernel's pad lanes (base = BIG beyond n) emit mask 0."""
    rng = np.random.default_rng(7)
    b, L, m, n, block_m, block_n = 2, 16, 4, 128, 2, 128
    _, paths_vert = make_db(rng, n, L, b)
    _, q_vert = make_db(rng, m, L, b)
    base = np.zeros((m, n), np.int32)
    base[1, :] = int(BIG)                  # query 1: everything pruned
    base[0, ::2] = int(BIG)                # query 0: alternate leaves pruned
    mask, dist = ops.sparse_verify_batch(paths_vert, q_vert,
                                         jnp.asarray(base), tau=L,
                                         block_m=block_m, block_n=block_n,
                                         use_kernel=True)
    mask, dist = np.asarray(mask), np.asarray(dist)
    pruned = base >= int(BIG)
    assert (mask[pruned] == 0).all()
    assert (dist[pruned] == int(BIG)).all()
    assert mask[1].sum() == 0
    # raw kernel with explicit pads: pad base lanes carry BIG -> mask 0
    pad_n = 2 * block_n
    paths_p = jnp.pad(paths_vert, ((0, 0), (0, 0), (0, pad_n - n)))
    base_p = jnp.pad(jnp.asarray(base), ((0, 0), (0, pad_n - n)),
                     constant_values=jnp.int32(BIG))
    pmask, pdist = sparse_verify_batch_pallas(paths_p, q_vert, base_p,
                                              tau=L, block_m=block_m,
                                              block_n=block_n, interpret=True)
    assert (np.asarray(pmask)[:, n:] == 0).all()
    assert (np.asarray(pdist)[:, n:] == int(BIG)).all()


def test_batch_verify_grid_streams_db_once_per_query_tile(monkeypatch):
    """The pallas grid is (⌈m/block_m⌉, ⌈n/block_n⌉): the HBM-traffic
    claim — the database block axis is walked once per query TILE."""
    captured = {}
    real_call = hamming_kernel.pl.pallas_call

    def spy(kernel, **kw):
        captured["grid"] = kw.get("grid")
        return real_call(kernel, **kw)

    monkeypatch.setattr(hamming_kernel.pl, "pallas_call", spy)
    rng = np.random.default_rng(3)
    b, L, m, n, block_m, block_n = 2, 16, 19, 1000, 4, 128
    _, paths_vert = make_db(rng, n, L, b)
    _, q_vert = make_db(rng, m, L, b)
    base = jnp.zeros((m, n), jnp.int32)
    ops.sparse_verify_batch(paths_vert, q_vert, base, tau=3,
                            block_m=block_m, block_n=block_n,
                            use_kernel=True)
    m_tiles = -(-m // block_m)
    n_tiles = -(-n // block_n)
    assert captured["grid"] == (m_tiles, n_tiles), captured


def test_hamming_distances_query_tiled_matches_oracle():
    rng = np.random.default_rng(9)
    b, L, m, n = 4, 32, 11, 700
    db, db_vert = make_db(rng, n, L, b)
    qs, q_vert = make_db(rng, m, L, b)
    got = np.asarray(ops.hamming_distances(db_vert, q_vert, block_m=4,
                                           block_n=128, use_kernel=True))
    brute = (qs[:, None, :] != db[None, :, :]).sum(axis=2)
    np.testing.assert_array_equal(got, brute)


@settings(max_examples=10, deadline=None)
@given(st.integers(1, 4), st.integers(2, 40), st.integers(1, 9),
       st.integers(1, 260), st.integers(0, 5), st.randoms())
def test_batch_verify_property(b, L, m, n, tau, rnd):
    rng = np.random.default_rng(rnd.randint(0, 2**31))
    db, paths_vert = make_db(rng, n, L, b)
    qs, q_vert = make_db(rng, m, L, b)
    base = rng.integers(0, 4, size=(m, n)).astype(np.int32)
    got, got_d = ops.sparse_verify_batch(paths_vert, q_vert,
                                         jnp.asarray(base), tau=tau,
                                         block_m=4, block_n=128,
                                         use_kernel=True)
    suffix = (qs[:, None, :] != db[None, :, :]).sum(axis=2)
    np.testing.assert_array_equal(np.asarray(got),
                                  ((base + suffix) <= tau).astype(np.int32))
    np.testing.assert_array_equal(np.asarray(got_d), base + suffix)


# ---------------------------------------------------------------------------
# natively batched traversal == per-query path
# ---------------------------------------------------------------------------

def random_db(rng, n, L, b, dup_frac=0.3):
    n_uniq = max(1, int(n * (1 - dup_frac)))
    base = rng.integers(0, 1 << b, size=(n_uniq, L)).astype(np.uint8)
    extra = base[rng.integers(0, n_uniq, size=n - n_uniq)]
    db = np.concatenate([base, extra], axis=0)
    rng.shuffle(db)
    return db


@pytest.mark.parametrize("builder", [build_bst, build_louds])
@pytest.mark.parametrize("tau", [0, 2, 4])
def test_batch_searcher_bit_identical_to_per_query(builder, tau):
    rng = np.random.default_rng(tau * 7 + 1)
    db = random_db(rng, 260, 14, 2)
    idx = builder(db, 2)
    qs = np.concatenate([db[:3], rng.integers(0, 4, size=(3, 14),
                                              dtype=np.uint8)])
    bres = make_batch_searcher(idx, tau, block_m=2)(jnp.asarray(qs))
    assert bres.overflow.shape == (len(qs),)
    for i in range(len(qs)):
        sres = get_searcher(idx, tau)(jnp.asarray(qs[i]))
        np.testing.assert_array_equal(np.asarray(bres.mask[i]),
                                      np.asarray(sres.mask))
        np.testing.assert_array_equal(np.asarray(bres.dist[i]),
                                      np.asarray(sres.dist))
        assert int(bres.overflow[i]) == int(sres.overflow)
        assert int(bres.traversed[i]) == int(sres.traversed)


def test_mi_search_batch_bit_identical_to_per_query():
    """The batched multi-index path (per-block 2D-frontier traces +
    per-query candidate compaction/verification) equals the single-query
    searcher and brute force."""
    from repro.core.multi_index import (build_multi_index, make_mi_searcher,
                                        mi_search_batch)
    rng = np.random.default_rng(19)
    db = random_db(rng, 280, 32, 2)
    mi = build_multi_index(db, 2, 2)
    tau = 4
    qs = np.stack([db[5], db[60],
                   rng.integers(0, 4, size=32).astype(np.uint8)])
    bres = mi_search_batch(mi, qs, tau)
    single = make_mi_searcher(mi, tau)
    for i in range(len(qs)):
        sres = single(jnp.asarray(qs[i]))
        np.testing.assert_array_equal(np.asarray(bres.mask[i]),
                                      np.asarray(sres.mask))
        np.testing.assert_array_equal(np.asarray(bres.dist[i]),
                                      np.asarray(sres.dist))
        assert int(bres.candidates[i]) == int(sres.candidates)
        d = (db != qs[i][None, :]).sum(axis=1)
        np.testing.assert_array_equal(np.asarray(bres.mask[i]), d <= tau)
        got_d = np.asarray(bres.dist[i])
        np.testing.assert_array_equal(got_d[d <= tau], d[d <= tau])
        assert (got_d[d > tau] == int(BIG)).all()


def test_sharded_scan_kernel_path_under_shard_vmap():
    """Shards large enough that the auto backend picks the pallas kernel
    (t_Lmax >= one block): the batch verify must vmap over the shard
    axis and still match brute force."""
    from repro.core.distributed_search import (build_sharded_bst, gather_ids,
                                               make_sharded_searcher)
    from repro.core.hamming import hamming_pairwise_naive
    rng = np.random.default_rng(21)
    n, L, b, tau, m = 6000, 12, 2, 1, 5
    db = rng.integers(0, 1 << b, size=(n, L)).astype(np.uint8)
    queries = np.concatenate(
        [db[:2], rng.integers(0, 1 << b, size=(m - 2, L), dtype=np.uint8)])
    index = build_sharded_bst(db, b, 2)
    assert index.paths_vert.shape[-1] >= hamming_kernel.DEFAULT_BLOCK_N
    masks, sdists, overflow = make_sharded_searcher(
        index, tau, cap_max=1 << 15, block_m=2)(jnp.asarray(queries))
    assert int(overflow) == 0
    got = gather_ids(index, np.asarray(masks))
    dists = np.asarray(hamming_pairwise_naive(jnp.asarray(queries),
                                              jnp.asarray(db)))
    for qi in range(m):
        want = np.flatnonzero(dists[qi] <= tau)
        np.testing.assert_array_equal(got[qi], want)
        dvec = np.asarray(sdists[qi])[index.shard_of, index.pos_of]
        np.testing.assert_array_equal(dvec[want], dists[qi][want])


@settings(max_examples=6, deadline=None)
@given(st.integers(1, 3), st.integers(2, 30), st.integers(1, 6),
       st.randoms())
def test_topk_batch_equals_per_query_topk_loop(b, k, m, rnd):
    rng = np.random.default_rng(rnd.randint(0, 2**31))
    L = {1: 20, 2: 14, 3: 10}[b]
    db = random_db(rng, 180, L, b)
    idx = build_bst(db, b)
    qs = np.stack([db[rng.integers(0, len(db))] if i % 2 == 0 else
                   rng.integers(0, 1 << b, size=L).astype(np.uint8)
                   for i in range(m)])
    bres = topk_batch(idx, qs, k)
    for i in range(m):
        # same final tau rung so the compiled searcher (and result) agree
        sres = topk(idx, qs[i], k, tau0=bres.tau)
        np.testing.assert_array_equal(np.asarray(bres.ids[i]),
                                      np.asarray(sres.ids))
        np.testing.assert_array_equal(np.asarray(bres.dists[i]),
                                      np.asarray(sres.dists))
