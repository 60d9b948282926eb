"""Two-stage exact re-rank (DESIGN.md §10).

Stage 2 must be *exact*: the fused re-rank kernel is bit-identical to
the interpretable oracle and to a host numpy brute force for every
metric, including pad rows, tile-misaligned lane counts, and fully
empty survivor tiles.  Threaded through the index it must stay exact
across the whole LSM lifecycle (insert -> delete -> merge -> compact)
on every backend, cost exactly ONE extra device launch per request
(never per segment), and its payload columns must show up in the space
ledger and the tier staging counters."""

import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # clean env: deterministic fallback shim
    from _hypothesis_compat import given, settings, st

from repro.core import (SegmentedIndex, ShardedSegmentedIndex,
                        dispatch_stats, reset_dispatch_stats,
                        reset_tier_stats, tier_stats)
from repro.core.hamming import pack_sets
from repro.core.segments import BIG_I
from repro.kernels import ops
from repro.kernels.ref import RERANK_METRICS, exact_rerank_ref

L, B = 12, 2
VOCAB = 96
WP = (VOCAB + 31) // 32


# -- host oracle ---------------------------------------------------------

def popcount_rows(x):
    x = np.ascontiguousarray(x, np.uint32)
    return np.unpackbits(x.view(np.uint8), axis=-1).sum(axis=-1)


def brute(metric, q_pay, pay, surv):
    """Row-major numpy oracle: q_pay (m, Wp), pay (n, Wp), surv (m, n)
    -> (m, n) float32 scores with the -1.0 non-survivor sentinel, using
    the kernel's exact f32 arithmetic."""
    inter = popcount_rows(
        q_pay[:, None, :] & pay[None, :, :]).astype(np.float32)
    sa = popcount_rows(q_pay).astype(np.float32)[:, None]
    sb = popcount_rows(pay).astype(np.float32)[None, :]
    if metric == "jaccard":
        den = sa + sb - inter
    elif metric == "cosine":
        den = np.sqrt(sa * sb).astype(np.float32)
    else:                               # containment: |A ∩ B| / |A|
        den = np.broadcast_to(sa, inter.shape)
    den_safe = np.where(den > 0, den, np.float32(1))
    sc = np.where(den > 0, (inter / den_safe).astype(np.float32),
                  np.float32(0))
    return np.where(surv, sc, np.float32(-1.0))


def brute_topk(metric, q_pay, pay, dist, ids, k):
    """Exact two-stage reference: score survivors (dist < BIG) of the
    stage-1 plane, order by (score desc, id asc), pad to k with the
    (-1, BIG_I, -1.0) sentinels."""
    surv = np.asarray(dist) < BIG_I
    sc = brute(metric, q_pay, pay, surv)
    out_i, out_d, out_s = [], [], []
    for r in range(sc.shape[0]):
        order = sorted(range(sc.shape[1]),
                       key=lambda j: (-sc[r, j], ids[j]))
        sel = [j for j in order if sc[r, j] >= 0][:k]
        pad = k - len(sel)
        out_i.append([ids[j] for j in sel] + [-1] * pad)
        out_d.append([dist[r, j] for j in sel] + [BIG_I] * pad)
        out_s.append([sc[r, j] for j in sel] + [np.float32(-1.0)] * pad)
    return (np.array(out_i, np.int64), np.array(out_d, np.int64),
            np.array(out_s, np.float32))


def make_rows(rng, n, vocab=VOCAB, max_tokens=20):
    sets = [rng.choice(vocab, size=int(rng.integers(1, max_tokens)),
                       replace=False) for _ in range(n)]
    pay = pack_sets(sets, vocab)
    sk = rng.integers(0, 1 << B, size=(n, L), dtype=np.uint8)
    return sk, pay


def check_rerank(idx, qs, qp, k, metric, want_rerank_launches=1):
    """One re-rank request vs the host two-stage oracle, with the
    dispatch spy asserting the one-extra-launch contract."""
    reset_dispatch_stats()
    res = idx.topk_batch(qs, k, rerank=metric, q_payloads=qp)
    ds = dispatch_stats()
    assert ds["rerank"] == want_rerank_launches, ds
    dist, col_ids, _ = idx._search_columns(qs, res.tau)
    bi, bd, bs = brute_topk(metric, qp, idx._payload_rows(),
                            np.asarray(dist), np.asarray(col_ids, np.int64),
                            k)
    np.testing.assert_array_equal(np.asarray(res.ids), bi)
    np.testing.assert_array_equal(np.asarray(res.dists), bd)
    np.testing.assert_array_equal(np.asarray(res.scores), bs)
    return res


# -- kernel vs oracle vs numpy ------------------------------------------

@pytest.mark.parametrize("metric", RERANK_METRICS)
@pytest.mark.parametrize("m,n", [(1, 70), (5, 64), (3, 130), (8, 200),
                                 (16, 130), (64, 200)])
def test_kernel_bit_exact_vs_oracle_and_numpy(metric, m, n):
    """Pad rows (m % block_m != 0), tile-misaligned n, m=1 — the pallas
    kernel, the jnp oracle, and the numpy brute force all agree bit for
    bit, -1.0 sentinels included."""
    rng = np.random.default_rng(m * 1000 + n)
    pay = rng.integers(0, 1 << 32, size=(n, WP), dtype=np.uint32)
    qp = rng.integers(0, 1 << 32, size=(m, WP), dtype=np.uint32)
    surv = (rng.random((m, n)) < 0.6).astype(np.int32)
    want = brute(metric, qp, pay, surv.astype(bool))
    got_ref = np.asarray(exact_rerank_ref(pay.T, qp.T, surv, metric))
    got_ker = np.asarray(ops.exact_rerank(
        pay.T, qp.T, surv, metric=metric, block_m=8, block_n=64,
        use_kernel=True))
    np.testing.assert_array_equal(got_ref, want)
    np.testing.assert_array_equal(got_ker, want)


@pytest.mark.parametrize("metric", RERANK_METRICS)
def test_kernel_empty_survivor_tiles_and_zero_sets(metric):
    """A whole survivor tile of zeros stays the -1.0 sentinel, and
    all-zero payload sets hit the zero-denominator -> 0.0 branch rather
    than NaN/inf."""
    rng = np.random.default_rng(9)
    m, n = 4, 192                          # 3 tiles of block_n=64
    pay = rng.integers(0, 1 << 32, size=(n, WP), dtype=np.uint32)
    pay[10] = 0                            # |B| = 0
    qp = rng.integers(0, 1 << 32, size=(m, WP), dtype=np.uint32)
    qp[2] = 0                              # |A| = 0 for one query row
    surv = np.ones((m, n), np.int32)
    surv[:, 64:128] = 0                    # middle tile fully dead
    surv[1] = 0                            # one query with zero survivors
    want = brute(metric, qp, pay, surv.astype(bool))
    got = np.asarray(ops.exact_rerank(
        pay.T, qp.T, surv, metric=metric, block_m=8, block_n=64,
        use_kernel=True))
    np.testing.assert_array_equal(got, want)
    assert (got[:, 64:128] == -1.0).all()
    assert (got[1] == -1.0).all()
    assert np.isfinite(got).all()


def test_small_scan_routes_to_oracle():
    """Below one lane tile the wrapper answers from the jnp oracle
    (use_kernel=None) — same bits either way."""
    rng = np.random.default_rng(3)
    pay = rng.integers(0, 1 << 32, size=(17, WP), dtype=np.uint32)
    qp = rng.integers(0, 1 << 32, size=(2, WP), dtype=np.uint32)
    surv = np.ones((2, 17), np.int32)
    auto = np.asarray(ops.exact_rerank(pay.T, qp.T, surv, metric="jaccard"))
    forced = np.asarray(ops.exact_rerank(pay.T, qp.T, surv,
                                         metric="jaccard", use_kernel=True))
    np.testing.assert_array_equal(auto, forced)


def test_payload_width_past_vmem_budget_rejected():
    """A 32k-token vocabulary (Wp = 1024 words) cannot double-buffer its
    payload blocks in VMEM at the default lane tile: the wrapper refuses
    it with a clear error instead of failing inside the TPU compiler."""
    wp, n, m = 1024, 2048, 8
    with pytest.raises(ValueError, match="VMEM"):
        ops.exact_rerank(np.zeros((wp, n), np.uint32),
                         np.zeros((wp, m), np.uint32),
                         np.ones((m, n), np.int32), metric="jaccard",
                         use_kernel=True)


def test_unknown_metric_rejected():
    z = np.zeros((WP, 4), np.uint32)
    with pytest.raises(ValueError):
        ops.exact_rerank(z, z[:, :1], np.ones((1, 4), np.int32),
                         metric="dice")


# -- lifecycle property: exact across the whole LSM lifecycle -----------

@settings(max_examples=2, deadline=None)
@given(st.randoms())
def test_rerank_exact_through_lifecycle_all_backends(rnd):
    """insert -> delete -> merge -> compact, then ``topk(rerank=...)``:
    bit-identical (ids, dists, scores, pads) to the host two-stage
    brute force on every backend/layout/arena combination, with exactly
    one re-rank launch per request regardless of segment count."""
    rng = np.random.default_rng(rnd.randint(0, 2 ** 31))
    combos = [("bst", "suffix", True), ("bst", "full", True),
              ("bst", "suffix", False), ("multi", "suffix", True),
              ("sharded", "suffix", True)]
    for backend, layout, use_arena in combos:
        idx = SegmentedIndex(L, B, delta_cap=25, backend=backend,
                             layout=layout, use_arena=use_arena,
                             payload_words=WP, auto_merge=False)
        sk, pay = make_rows(rng, 60)
        ids = idx.insert(sk, payloads=pay)
        idx.delete(ids[5:15])
        idx.merge()
        sk2, pay2 = make_rows(rng, 30)
        idx.insert(sk2, payloads=pay2)     # seals + leaves a live delta
        idx.delete(ids[40:44])
        idx.compact()
        assert len(idx.segments) >= 1
        qs = rng.integers(0, 1 << B, size=(3, L), dtype=np.uint8)
        qp = pack_sets([rng.choice(VOCAB, size=7, replace=False)
                        for _ in range(3)], VOCAB)
        for metric in RERANK_METRICS:
            check_rerank(idx, qs, qp, 8, metric)


def test_rerank_exact_on_sharded_index():
    rng = np.random.default_rng(17)
    sh = ShardedSegmentedIndex(L, B, n_shards=3, delta_cap=20,
                               payload_words=WP)
    sk, pay = make_rows(rng, 50)
    ids = sh.insert(sk, payloads=pay)
    sh.delete(ids[::7])
    sh.merge()
    qs = rng.integers(0, 1 << B, size=(2, L), dtype=np.uint8)
    qp = pack_sets([rng.choice(VOCAB, size=5, replace=False)
                    for _ in range(2)], VOCAB)
    for metric in RERANK_METRICS:
        check_rerank(sh, qs, qp, 6, metric)


def test_one_rerank_launch_even_with_many_segments():
    """The acceptance contract: +1 fused dispatch per request, not per
    segment.  Six sealed segments + a live delta still cost exactly one
    re-rank launch, and plain topk costs zero."""
    rng = np.random.default_rng(23)
    idx = SegmentedIndex(L, B, delta_cap=10, payload_words=WP,
                         auto_merge=False)
    for _ in range(6):
        sk, pay = make_rows(rng, 10)
        idx.insert(sk, payloads=pay)
    sk, pay = make_rows(rng, 4)            # live delta rows
    idx.insert(sk, payloads=pay)
    assert len(idx.segments) == 6 and idx.stats()["delta_rows"] == 4
    qs = rng.integers(0, 1 << B, size=(2, L), dtype=np.uint8)
    qp = pack_sets([rng.choice(VOCAB, size=6, replace=False)
                    for _ in range(2)], VOCAB)
    check_rerank(idx, qs, qp, 5, "jaccard", want_rerank_launches=1)
    reset_dispatch_stats()
    idx.topk_batch(qs, 5)
    assert dispatch_stats()["rerank"] == 0


def test_rerank_scores_improve_or_match_sketch_order():
    """Sanity on the knob itself: the query's own payload re-ranks its
    exact duplicate to the top with score 1.0 under every metric."""
    rng = np.random.default_rng(31)
    idx = SegmentedIndex(L, B, delta_cap=16, payload_words=WP)
    sk, pay = make_rows(rng, 40)
    ids = idx.insert(sk, payloads=pay)
    probe = 11
    for metric in RERANK_METRICS:
        res = idx.topk(sk[probe], 3, rerank=metric,
                       q_payloads=pay[probe])
        assert int(res.ids[0]) == int(ids[probe])
        assert float(res.scores[0]) == 1.0


# -- argument contract ---------------------------------------------------

def test_rerank_argument_contract():
    rng = np.random.default_rng(5)
    q = np.zeros((1, L), np.uint8)
    qp = np.zeros((1, WP), np.uint32)
    plain = SegmentedIndex(L, B)
    with pytest.raises(ValueError):        # no payload plane configured
        plain.topk_batch(q, 2, rerank="jaccard", q_payloads=qp)
    with pytest.raises(ValueError):        # payloads without rerank=
        plain.topk_batch(q, 2, q_payloads=qp)
    idx = SegmentedIndex(L, B, payload_words=WP)
    with pytest.raises(ValueError):        # rerank= without payloads
        idx.topk_batch(q, 2, rerank="jaccard")
    with pytest.raises(ValueError):        # unknown metric
        idx.topk_batch(q, 2, rerank="dice", q_payloads=qp)
    with pytest.raises(ValueError):        # wrong payload width
        idx.topk_batch(q, 2, rerank="jaccard",
                       q_payloads=np.zeros((1, WP + 1), np.uint32))
    with pytest.raises(ValueError):        # insert without payloads
        idx.insert(rng.integers(0, 1 << B, size=(3, L), dtype=np.uint8))
    with pytest.raises(ValueError):        # payloads on a plain index
        plain.insert(rng.integers(0, 1 << B, size=(3, L), dtype=np.uint8),
                     payloads=np.zeros((3, WP), np.uint32))


# -- space accounting ----------------------------------------------------

def test_payload_columns_in_space_ledger():
    """Configuring the payload plane grows the ledger by at least the
    payload bitmap bytes on both device (vertical columns / delta plane)
    and host (row-major recovery copies)."""
    rng = np.random.default_rng(41)
    sk, pay = make_rows(rng, 48)
    base = SegmentedIndex(L, B, delta_cap=16, auto_merge=False)
    base.insert(sk)
    with_pay = SegmentedIndex(L, B, delta_cap=16, payload_words=WP,
                              auto_merge=False)
    with_pay.insert(sk, payloads=pay)
    q = sk[:1]
    base.topk_batch(q, 2)                  # materialize the column store
    with_pay.topk_batch(q, 2)
    led0, led1 = base.space_ledger(), with_pay.space_ledger()
    sealed_pay_bytes = sum(s.payloads.nbytes for s in with_pay.segments)
    assert led1["host_bytes"] - led0["host_bytes"] >= sealed_pay_bytes
    assert led1["device_bytes"] - led0["device_bytes"] >= sealed_pay_bytes
    assert led1["model_bits"] == led0["model_bits"]  # succinct model unchanged


def test_cold_tier_rerank_counts_staged_payload_bytes():
    """Under a tiny hot budget the re-rank pass serves demoted blocks
    via the payload staging slab — visible as ``staged_payload_bytes``
    (plain topk on the same index stages only sketch columns)."""
    rng = np.random.default_rng(43)
    idx = SegmentedIndex(L, B, delta_cap=16, payload_words=WP,
                         auto_merge=False, hot_bytes=1)
    sk, pay = make_rows(rng, 48)
    idx.insert(sk, payloads=pay)
    assert idx._refresh_store().pay_bytes("cold") > 0
    qs = rng.integers(0, 1 << B, size=(2, L), dtype=np.uint8)
    qp = pack_sets([rng.choice(VOCAB, size=6, replace=False)
                    for _ in range(2)], VOCAB)
    reset_tier_stats()
    idx.topk_batch(qs, 4)
    assert tier_stats()["staged_payload_bytes"] == 0
    check_rerank(idx, qs, qp, 4, "jaccard")
    ts = tier_stats()
    assert ts["staged_payload_bytes"] > 0
    assert ts["staged_bytes"] >= ts["staged_payload_bytes"]
