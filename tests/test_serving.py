"""Serving runtime: the micro-batching scheduler must be semantically
invisible — for any interleaved request stream, per-request results
(masks, dists, ids) are bit-identical to executing each request alone,
in submission order, against the same index state — while coalescing
reads into power-of-two shape buckets (zero new searcher-cache misses
or jit traces after warmup), fencing reads on writes, and rejecting
overload explicitly."""

import threading

import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # clean env: deterministic fallback shim
    from _hypothesis_compat import given, settings, st

from repro.core import SegmentedIndex, clear_searcher_cache, \
    searcher_cache_info
from repro.serving import (CollectionConfig, OverloadError, Scheduler,
                           SchedulerConfig, bucket_table)

L, B, TAU, K = 10, 2, 2, 3


def make_stream(rnd, n_ops=18):
    """A deterministic interleaved request stream: bootstrap corpus
    insert, then mixed reads/writes.  Returns [(op, payload), ...]."""
    rng = np.random.default_rng(rnd.randint(0, 2**31))
    corpus = rng.integers(0, 1 << B, size=(24, L), dtype=np.uint8)
    stream = [("insert", corpus)]
    n_inserted = len(corpus)
    for _ in range(n_ops):
        r = rng.random()
        if r < 0.55:
            q = corpus[rng.integers(0, len(corpus))] if rng.random() < 0.7 \
                else rng.integers(0, 1 << B, size=L, dtype=np.uint8)
            stream.append(("search", q) if rng.random() < 0.5
                          else ("topk", q))
        elif r < 0.8:
            rows = rng.integers(0, 1 << B,
                                size=(int(rng.integers(1, 4)), L),
                                dtype=np.uint8)
            stream.append(("insert", rows))
            n_inserted += len(rows)
        else:
            stream.append(
                ("delete", rng.integers(0, n_inserted, size=2)))
    return stream


def run_sequential(stream):
    """The oracle: every request executed alone, in order, on a fresh
    index."""
    idx = SegmentedIndex(L, B, delta_cap=16)
    out = []
    for op, payload in stream:
        if op == "insert":
            out.append(idx.insert(payload))
        elif op == "delete":
            out.append(idx.delete(payload))
        elif op == "search":
            res = idx.search(payload, TAU)
            out.append((np.asarray(res.mask), np.asarray(res.dist)))
        else:
            nn = idx.topk(payload, K)
            out.append((np.asarray(nn.ids), np.asarray(nn.dists)))
    return out


def submit_stream(sched, stream):
    futs = []
    for op, payload in stream:
        if op == "insert":
            futs.append(sched.submit_insert("c", payload))
        elif op == "delete":
            futs.append(sched.submit_delete("c", payload))
        elif op == "search":
            futs.append(sched.submit_search("c", payload, TAU))
        else:
            futs.append(sched.submit_topk("c", payload, K))
    return futs


def check_results(stream, futs, want):
    for (op, _), fut, ref in zip(stream, futs, want):
        got = fut.result(timeout=300)
        if op == "insert":
            np.testing.assert_array_equal(got, ref)
        elif op == "delete":
            assert got == ref
        elif op == "search":
            np.testing.assert_array_equal(got.mask, ref[0])
            np.testing.assert_array_equal(got.dist, ref[1])
        else:  # topk: ids/dists exact; the tau rung is batch-shared
            np.testing.assert_array_equal(got.ids, ref[0])
            np.testing.assert_array_equal(got.dists, ref[1])


def make_sched(**kw):
    cfg = dict(max_batch=8, max_queue=10_000, max_wait_ms=1.0)
    cfg.update(kw)
    sched = Scheduler(config=SchedulerConfig(**cfg))
    sched.create_collection("c", CollectionConfig(L=L, b=B, delta_cap=16))
    return sched


# ---------------------------------------------------------------------------
# the core property: scheduling is semantically invisible
# ---------------------------------------------------------------------------

@settings(max_examples=3, deadline=None)
@given(st.randoms())
def test_interleaved_stream_bit_identical_to_sequential(rnd):
    stream = make_stream(rnd)
    want = run_sequential(stream)
    sched = make_sched()
    futs = submit_stream(sched, stream)     # whole stream queued at once
    sched.pump()                            # sync drive: deterministic
    check_results(stream, futs, want)


def test_incremental_pumping_matches_sequential():
    """Draining the queue in arbitrary chunks (pump between submits)
    must not change any result."""
    import random
    stream = make_stream(random.Random(7), n_ops=12)
    want = run_sequential(stream)
    sched = make_sched()
    futs = []
    for i, item in enumerate(stream):
        futs.extend(submit_stream(sched, [item]))
        if i % 3 == 0:
            sched.pump()
    sched.pump()
    check_results(stream, futs, want)


def test_threaded_mode_matches_sequential():
    """Same property with the worker thread + max-wait flush in play
    (single producer, so submission order is still deterministic)."""
    import random
    stream = make_stream(random.Random(11), n_ops=10)
    want = run_sequential(stream)
    sched = make_sched(max_wait_ms=5.0).start()
    futs = submit_stream(sched, stream)
    check_results(stream, futs, want)
    sched.stop()
    assert sched.queue_depth() == 0


# ---------------------------------------------------------------------------
# batching mechanics
# ---------------------------------------------------------------------------

def test_reads_coalesce_into_one_bucketed_dispatch():
    rng = np.random.default_rng(1)
    sched = make_sched()
    docs = rng.integers(0, 1 << B, size=(30, L), dtype=np.uint8)
    sched.submit_insert("c", docs)
    futs = [sched.submit_search("c", docs[i], TAU) for i in range(5)]
    sched.pump()
    snap = sched.stats()
    # 5 same-key reads -> ONE dispatch, padded 5 -> bucket 8
    assert snap["counters"]["batches_total:search"] == 1
    assert snap["batch_fill_ratio"] == pytest.approx(5 / 8)
    hits = [int(f.result().mask[i]) for i, f in enumerate(futs)]
    assert hits == [1] * 5                  # each query finds itself


def test_mixed_key_reads_split_into_separate_batches():
    rng = np.random.default_rng(2)
    sched = make_sched()
    docs = rng.integers(0, 1 << B, size=(20, L), dtype=np.uint8)
    sched.submit_insert("c", docs)
    f1 = [sched.submit_search("c", docs[i], 1) for i in range(2)]
    f2 = [sched.submit_search("c", docs[i], 2) for i in range(2)]
    f3 = [sched.submit_topk("c", docs[i], K) for i in range(2)]
    sched.pump()
    snap = sched.stats()
    assert snap["counters"]["batches_total:search"] == 2   # tau=1 and tau=2
    assert snap["counters"]["batches_total:topk"] == 1
    for i, f in enumerate(f1 + f2):
        assert int(f.result().mask[i % 2]) == 1
    for i, f in enumerate(f3):
        assert int(f.result().ids[0]) == i


def test_write_fences_reads():
    """A read submitted before a write must not observe it; a read after
    must."""
    sched = make_sched()
    base = np.zeros((4, L), np.uint8)
    sched.submit_insert("c", base)
    probe = np.full(L, 1, np.uint8)
    before = sched.submit_search("c", probe, 0)
    sched.submit_insert("c", probe[None])           # exact match lands
    after = sched.submit_search("c", probe, 0)
    sched.pump()
    assert before.result().mask.sum() == 0          # pre-insert state
    assert after.result().mask.sum() == 1
    assert after.result().mask.shape[0] == 5        # plane grew


def test_overload_rejection():
    sched = make_sched(max_queue=3)
    q = np.zeros(L, np.uint8)
    for _ in range(3):
        sched.submit_search("c", q, TAU)
    with pytest.raises(OverloadError):
        sched.submit_search("c", q, TAU)
    assert sched.stats()["counters"]["rejected_total"] == 1
    assert sched.queue_depth("c") == 3
    sched.pump()                                    # queued work drains
    assert sched.queue_depth("c") == 0


def test_collection_registry_errors():
    sched = make_sched()
    with pytest.raises(KeyError):
        sched.submit_search("nope", np.zeros(L, np.uint8), 1)
    with pytest.raises(ValueError):
        sched.create_collection("c", CollectionConfig(L=L, b=B))
    assert sched.registry.names() == ["c"]
    assert bucket_table(8) == [1, 2, 4, 8]


# ---------------------------------------------------------------------------
# steady state: varying-m traffic never re-jits (acceptance criterion)
# ---------------------------------------------------------------------------

def test_varying_batch_stream_zero_new_cache_misses():
    rng = np.random.default_rng(3)
    sched = make_sched()
    docs = rng.integers(0, 1 << B, size=(64, L), dtype=np.uint8)
    ids = sched.submit_insert("c", docs)
    sched.pump()
    ids = ids.result()
    idx = sched.registry.get("c").index
    idx.flush()                       # single sealed segment, empty delta

    def burst(sizes, offset):
        for g in sizes:
            futs = [sched.submit_search("c", docs[(offset + j) % 60], TAU)
                    for j in range(g)]
            futs += [sched.submit_topk("c", docs[(offset + j) % 60], 1,
                                       tau0=TAU) for j in range(g)]
            sched.pump()
            for f in futs:
                f.result(timeout=300)

    clear_searcher_cache()
    burst((1, 2, 4, 8), offset=0)               # warm every bucket
    sched.submit_delete("c", ids[60:62])        # tombstones are traced data
    sched.pump()
    warm = searcher_cache_info()
    burst((1, 3, 5, 2, 7, 8, 4, 6), offset=5)   # varying-m steady state
    sched.submit_delete("c", ids[62:64])
    sched.pump()
    burst((8, 1, 6, 3), offset=11)
    info = searcher_cache_info()
    assert info["misses"] == warm["misses"], (warm, info)
    assert info["traces"] == warm["traces"], (warm, info)
    assert info["hits"] > warm["hits"]


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------

def test_metrics_snapshot_and_text_dump():
    sched = make_sched()
    rng = np.random.default_rng(4)
    docs = rng.integers(0, 1 << B, size=(16, L), dtype=np.uint8)
    sched.submit_insert("c", docs)
    for i in range(3):
        sched.submit_topk("c", docs[i], K)
    sched.pump()
    snap = sched.stats()
    assert snap["counters"]["requests_total:topk"] == 3
    assert snap["latency"]["topk"]["count"] == 3
    assert snap["latency"]["topk"]["mean_ms"] > 0
    assert snap["queue_depth"]["c"] == 0
    assert snap["collections"]["c"]["n_live"] == 16
    assert snap["compile"]["compiles"] >= 0
    text = sched.render_stats()
    for needle in ('serving_requests_total{op="topk"} 3',
                   'serving_latency_seconds_count{op="topk"} 3',
                   'serving_latency_seconds_bucket{op="topk",le="+Inf"} 3',
                   'serving_queue_latency_seconds_count{op="topk"} 3',
                   'index_n_live{collection="c"} 16',
                   "serving_batch_fill_ratio",
                   "searcher_cache_traces",
                   "compiles_total", "compile_seconds_total"):
        assert needle in text, needle
    # the windowed percentile gauges are gone: the histograms carry them
    assert "_p99_ms" not in text and "_p50_ms" not in text


def test_overload_error_carries_context_and_per_op_counter():
    """A shed request's OverloadError names what was rejected, and the
    rejection counters split per op alongside the aggregate."""
    sched = make_sched(max_queue=2)
    q = np.zeros(L, np.uint8)
    sched.submit_search("c", q, TAU)
    sched.submit_search("c", q, TAU)
    with pytest.raises(OverloadError) as ei:
        sched.submit_topk("c", q, K)
    err = ei.value
    assert (err.collection, err.op, err.queue_depth) == ("c", "topk", 2)
    with pytest.raises(OverloadError):
        sched.submit_delete("c", np.asarray([0], np.int64))
    counters = sched.stats()["counters"]
    assert counters["rejected_total"] == 2
    assert counters["rejected_total:topk"] == 1
    assert counters["rejected_total:delete"] == 1
    assert 'serving_rejected_total{op="topk"} 1' in sched.render_stats()
    sched.pump()                                    # queued work drains


def test_executor_exception_fails_batch_but_worker_survives():
    """An exception inside batch execution must surface on the batch's
    futures and increment executor_errors_total — and the queue's only
    worker must keep serving afterwards."""
    rng = np.random.default_rng(6)
    sched = make_sched().start()
    docs = rng.integers(0, 1 << B, size=(8, L), dtype=np.uint8)
    sched.submit_insert("c", docs).result(timeout=300)
    bad = np.full((2, L), 1 << B, np.uint8)         # character out of Σ
    with pytest.raises(ValueError):
        sched.submit_insert("c", bad).result(timeout=300)
    # same worker, next request: still alive and correct
    nn = sched.submit_topk("c", docs[0], 1).result(timeout=300)
    assert int(nn.dists[0]) == 0
    snap = sched.stats()
    assert snap["counters"]["executor_errors_total"] == 1
    assert snap["collections"]["c"]["n_live"] == 8  # bad rows never landed
    sched.stop()


def test_metrics_and_dispatch_counters_survive_threaded_hammering():
    """The process-level dispatch counters and one ServingMetrics are
    bumped from every worker thread — concurrent increments (plus
    snapshots mid-flight) must lose nothing."""
    from repro.core.segments import _dispatch, dispatch_stats
    from repro.serving.metrics import ServingMetrics
    m = ServingMetrics()
    before = dispatch_stats()
    per_thread, n_threads = 400, 8

    def hammer(_):
        for i in range(per_thread):
            _dispatch("fused")
            m.inc("stress_total")
            m.record_latency("op", 1e-3)
            m.record_batch("op", 1, 2)
            if i % 100 == 0:
                m.snapshot()

    threads = [threading.Thread(target=hammer, args=(t,))
               for t in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    total = per_thread * n_threads
    after = dispatch_stats()
    assert after["total"] - before["total"] == total
    assert after["fused"] - before["fused"] == total
    snap = m.snapshot()
    assert snap["counters"]["stress_total"] == total
    assert snap["counters"]["batches_total:op"] == total
    assert snap["latency"]["op"]["count"] == total
    assert m.batch_fill_ratio() == pytest.approx(0.5)


# ---------------------------------------------------------------------------
# two-stage re-rank requests (DESIGN.md §10)
# ---------------------------------------------------------------------------

RVOCAB = 64
RWP = (RVOCAB + 31) // 32


def _rerank_fixture(seed, n_docs=30):
    from repro.core.hamming import pack_sets
    rng = np.random.default_rng(seed)
    sk = rng.integers(0, 1 << B, size=(n_docs, L), dtype=np.uint8)
    sets = [rng.choice(RVOCAB, size=int(rng.integers(2, 12)), replace=False)
            for _ in range(n_docs)]
    return rng, sk, pack_sets(sets, RVOCAB)


def make_rerank_sched(**kw):
    sched = make_sched(**kw)
    sched.create_collection(
        "r", CollectionConfig(L=L, b=B, delta_cap=16, payload_words=RWP))
    return sched


def test_mixed_rerank_and_plain_stream_bit_identical_to_sequential():
    """Interleaved ``rerank=``/plain topk traffic (plus writes) through
    the scheduler is bit-identical — ids, dists, AND exact scores — to
    executing each request alone, in order; plain responses carry no
    scores."""
    rng, sk, pays = _rerank_fixture(19)
    idx = SegmentedIndex(L, B, delta_cap=16, payload_words=RWP)
    sched = make_rerank_sched()
    # build the mixed stream: (op, args...) executed both ways
    stream = [("insert", sk[:20], pays[:20])]
    for i in range(12):
        if i % 4 == 3:
            stream.append(("insert", sk[20 + i // 4:21 + i // 4],
                           pays[20 + i // 4:21 + i // 4]))
        elif i % 3 == 0:
            stream.append(("topk", sk[i]))
        else:
            metric = "jaccard" if i % 2 else "cosine"
            stream.append(("rerank", sk[i], pays[i], metric))
    stream.append(("delete", np.arange(3, dtype=np.int64)))
    stream.append(("rerank", sk[5], pays[5], "containment"))
    want = []
    for op, *a in stream:
        if op == "insert":
            want.append(idx.insert(a[0], payloads=a[1]))
        elif op == "delete":
            want.append(idx.delete(a[0]))
        elif op == "topk":
            want.append(idx.topk(a[0], K))
        else:
            want.append(idx.topk(a[0], K, rerank=a[2], q_payloads=a[1]))
    futs = []
    for op, *a in stream:
        if op == "insert":
            futs.append(sched.submit_insert("r", a[0], payloads=a[1]))
        elif op == "delete":
            futs.append(sched.submit_delete("r", a[0]))
        elif op == "topk":
            futs.append(sched.submit_topk("r", a[0], K))
        else:
            futs.append(sched.submit_topk("r", a[0], K, rerank=a[2],
                                          q_payload=a[1]))
    sched.pump()
    for (op, *a), fut, ref in zip(stream, futs, want):
        got = fut.result(timeout=300)
        if op == "insert":
            np.testing.assert_array_equal(got, ref)
        elif op == "delete":
            assert got == ref
        else:
            np.testing.assert_array_equal(got.ids, np.asarray(ref.ids))
            np.testing.assert_array_equal(got.dists, np.asarray(ref.dists))
            if op == "topk":
                assert got.scores is None
            else:
                np.testing.assert_array_equal(got.scores,
                                              np.asarray(ref.scores))


def test_rerank_coalesces_only_within_same_metric_key():
    """The batch key is (op, k, τ0, metric): plain and per-metric
    re-rank requests at the same k split into separate dispatches, and
    same-key requests still coalesce (fill ratio counts all three)."""
    rng, sk, pays = _rerank_fixture(29)
    sched = make_rerank_sched()
    sched.submit_insert("r", sk, pays)
    sched.pump()
    futs = [sched.submit_topk("r", sk[i], K) for i in range(3)]
    futs += [sched.submit_topk("r", sk[i], K, rerank="jaccard",
                               q_payload=pays[i]) for i in range(2)]
    futs += [sched.submit_topk("r", sk[i], K, rerank="cosine",
                               q_payload=pays[i]) for i in range(2)]
    sched.pump()
    snap = sched.stats()
    # one batch per key: plain, jaccard, cosine — never merged
    assert snap["counters"]["batches_total:topk"] == 3
    # 3->4, 2->2, 2->2: the coalescing still packs within each key
    assert snap["batch_fill_ratio"] == pytest.approx(7 / 8)
    for i, f in enumerate(futs[:3]):
        assert int(f.result().ids[0]) == i and f.result().scores is None
    for i, f in enumerate(futs[3:5]):
        assert int(f.result().ids[0]) == i
        assert float(f.result().scores[0]) == 1.0
    for f in futs[5:]:
        assert f.result().scores is not None


def test_concurrent_submitters_all_complete():
    """Multiple producer threads against the threaded scheduler: every
    future completes with a sane result (ordering across producers is
    unspecified; completion and shape are not)."""
    rng = np.random.default_rng(5)
    sched = make_sched(max_queue=10_000).start()
    docs = rng.integers(0, 1 << B, size=(40, L), dtype=np.uint8)
    sched.submit_insert("c", docs).result(timeout=300)
    results, errs = [], []

    def client(seed):
        try:
            r = np.random.default_rng(seed)
            for _ in range(5):
                i = int(r.integers(0, len(docs)))
                nn = sched.submit_topk("c", docs[i], 1).result(timeout=300)
                results.append((i, int(nn.ids[0]), int(nn.dists[0])))
        except Exception as e:              # noqa: BLE001
            errs.append(e)

    threads = [threading.Thread(target=client, args=(s,)) for s in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    sched.stop()
    assert not errs
    assert len(results) == 20
    for i, nn_id, nn_dist in results:
        assert nn_dist == 0                 # the doc itself (or a dup twin)
        np.testing.assert_array_equal(docs[nn_id], docs[i])


def test_compile_cache_honours_env_else_one_checkout_dir(monkeypatch):
    """The serving entry points keep JAX's persistent compile cache where
    ``JAX_COMPILATION_CACHE_DIR`` says (setting nothing in code), else at
    one fixed directory inside the checkout — never a temporary name."""
    import pathlib

    import jax

    from repro.launch.compile_cache import use_compile_cache
    prev = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
        assert use_compile_cache() == "/elsewhere/cache"
        assert jax.config.jax_compilation_cache_dir == prev
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        first = use_compile_cache()
        assert use_compile_cache() == first
        assert jax.config.jax_compilation_cache_dir == first
        root = pathlib.Path(first).parent
        assert pathlib.Path(first).name == ".jax_cache"
        assert (root / "chip_smoke.py").exists() and (root / "src").is_dir()
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
