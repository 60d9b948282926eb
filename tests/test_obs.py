"""Observability (DESIGN.md §11): ``explain=True`` must be bit-identical
to the plain call on every backend; tracing disabled must add zero
device dispatches (the instrumentation points are shared no-ops); the
trace ring is bounded; the Chrome export loads and nests; the fused rung
program's named stages change HLO metadata only; the metrics exposition
round-trips through a strict Prometheus parser; and a fresh
``ServingMetrics`` never sees another instance's process-global traffic.
"""

import contextlib
import json
import os
import re
import sys
import threading
import time

import numpy as np
import pytest

import jax

from repro.core import segments
from repro.core.segments import (SegmentedIndex, ShardedSegmentedIndex,
                                 dispatch_stats)
from repro.core.hamming import pack_sets
from repro.obs import (QueryExplain, SlowQueryLog, Span, Tracer, attach,
                       chrome_trace, compile_stats, format_value,
                       parse_exposition, span, span_totals)
from repro.obs.prom import Histogram
from repro.obs.trace import _NULL, current
from repro.serving import (CollectionConfig, Scheduler, SchedulerConfig,
                           ServingMetrics)

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))
import trace_report  # noqa: E402

L, B = 12, 2
RNG = np.random.default_rng(7)
SKETCHES = RNG.integers(0, 1 << B, size=(180, L), dtype=np.uint8)
QUERY = SKETCHES[11]


def _filled(index):
    index.insert(SKETCHES)
    if hasattr(index, "flush"):
        index.flush()
    return index


# -- explain bit-identity ------------------------------------------------

@pytest.mark.parametrize("backend", ["bst", "multi"])
def test_explain_topk_bit_identical(backend):
    idx = _filled(SegmentedIndex(L=L, b=B, delta_cap=64, backend=backend))
    plain = idx.topk(QUERY, k=4)
    res, ex = idx.topk(QUERY, k=4, explain=True)
    np.testing.assert_array_equal(np.asarray(plain.ids), np.asarray(res.ids))
    np.testing.assert_array_equal(np.asarray(plain.dists),
                                  np.asarray(res.dists))
    assert plain.tau == res.tau and plain.overflow == res.overflow
    assert isinstance(ex, QueryExplain)
    assert ex.op == "topk" and ex.backend == backend
    assert ex.tau_final == res.tau and ex.k == 4
    assert ex.n_live == idx.n_live
    assert len(ex.rungs) >= 1 and ex.rungs[-1].tau == res.tau
    for rung in ex.rungs:
        assert rung.candidates >= 0
        assert len(rung.survivors) == len(rung.pruned) == 1
        # pruned + survivors partition the physical candidate columns
        assert rung.survivors[0] + rung.pruned[0] == rung.candidates
    assert ex.candidates_verified == sum(r.survivors[0] for r in ex.rungs)
    assert "rung tau=" in ex.summary()


def test_explain_sharded_bit_identical():
    idx = _filled(ShardedSegmentedIndex(L=L, b=B, delta_cap=64, n_shards=2))
    plain = idx.topk(QUERY, k=4)
    res, ex = idx.topk(QUERY, k=4, explain=True)
    np.testing.assert_array_equal(np.asarray(plain.ids), np.asarray(res.ids))
    np.testing.assert_array_equal(np.asarray(plain.dists),
                                  np.asarray(res.dists))
    assert ex.backend == "sharded-stacks"
    sres, sex = idx.search(QUERY, tau=3, explain=True)
    assert sex.op == "search" and sex.tau0 == 3


def test_explain_search_and_batch():
    idx = _filled(SegmentedIndex(L=L, b=B, delta_cap=64))
    plain = idx.search_batch(SKETCHES[:3], tau=3)
    res, ex = idx.search_batch(SKETCHES[:3], tau=3, explain=True)
    np.testing.assert_array_equal(np.asarray(plain.mask),
                                  np.asarray(res.mask))
    np.testing.assert_array_equal(np.asarray(plain.dist),
                                  np.asarray(res.dist))
    assert ex.n_queries == 3
    # per-query survivor counts match the dense mask row sums
    np.testing.assert_array_equal(
        np.asarray(ex.rungs[-1].survivors),
        np.asarray(plain.mask).sum(axis=1))


def test_explain_rerank_bit_identical():
    sets = [RNG.choice(64, size=9, replace=False) for _ in range(len(SKETCHES))]
    pays = pack_sets(sets, 64)
    idx = SegmentedIndex(L=L, b=B, delta_cap=64,
                         payload_words=pays.shape[1])
    idx.insert(SKETCHES, payloads=pays)
    idx.flush()
    plain = idx.topk(QUERY, k=4, rerank="jaccard", q_payloads=pays[11])
    res, ex = idx.topk(QUERY, k=4, rerank="jaccard", q_payloads=pays[11],
                       explain=True)
    np.testing.assert_array_equal(np.asarray(plain.ids), np.asarray(res.ids))
    np.testing.assert_array_equal(np.asarray(plain.scores),
                                  np.asarray(res.scores))
    assert ex.rerank == "jaccard"
    assert ex.rerank_survivors == ex.rungs[-1].survivors


def test_explain_frontier_widths_bst_only():
    idx = _filled(SegmentedIndex(L=L, b=B, delta_cap=64))
    _, ex = idx.topk(QUERY, k=4, explain=True)
    fr = ex.rungs[-1].frontier
    assert fr is not None and len(fr) == 1      # one query
    assert len(fr[0]) == L                      # one width per trie level
    assert fr[0][0] >= 1                        # root level is live
    _, ex_multi = _filled(SegmentedIndex(
        L=L, b=B, delta_cap=64, backend="multi")).topk(
            QUERY, k=4, explain=True)
    assert ex_multi.rungs[-1].frontier is None


# -- tracing: disabled is free, enabled nests ----------------------------

def test_span_disabled_is_shared_noop():
    assert current() is None
    assert span("anything", cat="x", a=1) is _NULL
    with span("nested"):        # no context attached: nothing recorded
        pass
    assert current() is None


def test_tracing_disabled_zero_extra_dispatches():
    idx = _filled(SegmentedIndex(L=L, b=B, delta_cap=64))
    idx.topk(QUERY, k=4)                        # warm the compiled program
    d0 = dispatch_stats()
    plain = idx.topk(QUERY, k=4)
    d_plain = {k: v - d0[k] for k, v in dispatch_stats().items()}

    root = Span("request")
    d1 = dispatch_stats()
    with attach(root):
        traced = idx.topk(QUERY, k=4)
    d_traced = {k: v - d1[k] for k, v in dispatch_stats().items()}
    # spans are host timers and profiler annotations: the device ledger
    # is identical
    assert d_traced == d_plain
    np.testing.assert_array_equal(np.asarray(plain.ids),
                                  np.asarray(traced.ids))
    for name in ("delta_planes", "store_refresh", "rung_dispatch",
                 "rung_program", "rung_launch", "rung_wait",
                 "topk_readback"):
        assert root.find(name) is not None, name


def _suffix_index():
    """A suffix-layout index with two sealed segments and delta rows."""
    idx = SegmentedIndex(L=16, b=B, delta_cap=256, layout="suffix",
                         auto_merge=False)
    rows = np.random.default_rng(3).integers(0, 1 << B, size=(650, 16),
                                             dtype=np.uint8)
    for lo in range(0, len(rows), 100):
        idx.insert(rows[lo:lo + 100])
    assert len(idx.segments) == 2 and idx.stats()["delta_rows"] == 50
    return idx, rows


def _walk(sp):
    yield sp
    for ch in sp.children:
        yield from _walk(ch)


def test_traced_suffix_index_bit_identical_and_dispatch_nests():
    idx, rows = _suffix_index()
    qs = rows[:3]
    plain = idx.topk_batch(qs, 5)
    root = Span("request")
    with attach(root):
        traced = idx.topk_batch(qs, 5)
    for a, b in ((plain.ids, traced.ids), (plain.dists, traced.dists)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert (plain.tau, plain.overflow) == (traced.tau, traced.overflow)
    rungs = [sp for sp in _walk(root) if sp.name == "rung_dispatch"]
    assert rungs
    tables = segments.fused_scope_tables()
    for sp in rungs:
        kids = [ch.name for ch in sp.children if ch.name != "compile"]
        assert kids == ["rung_program", "rung_launch", "rung_wait"]
        assert sp.args["program"] in tables
        for ch in sp.children:
            assert sp.ts <= ch.ts and ch.ts + ch.dur <= sp.ts + sp.dur


def test_trie_build_nests_under_seal_and_merge():
    before = span_totals().get("trie_build", (0, 0.0))
    idx = SegmentedIndex(L=L, b=B, delta_cap=64)
    root = Span("execute")
    with attach(root):
        idx.insert(SKETCHES[:64])
        idx.insert(SKETCHES[64:128])       # the second seal merges
    assert idx.counters == {**idx.counters, "flushes": 2, "merges": 1}
    seals = [sp for sp in _walk(root) if sp.name == "seal"]
    merges = [sp for sp in _walk(root) if sp.name == "merge"]
    assert [sp.args["rows"] for sp in seals] == [64, 64]
    assert [sp.args["rows"] for sp in merges] == [128]
    for sp in seals + merges:
        assert [ch.name for ch in sp.children
                if ch.name != "compile"] == ["trie_build", "pack_vertical"]
    builds = [sp for sp in _walk(root) if sp.name == "trie_build"]
    assert len(builds) == 3
    after = span_totals()["trie_build"]
    assert after[0] - before[0] == 3
    assert after[1] - before[1] == pytest.approx(
        sum(sp.dur for sp in builds))


def test_span_totals_exact_under_threads():
    """Scheduler workers close spans concurrently: the process-wide
    tally loses no update."""
    workers, per = 16, 400
    before = span_totals().get("tally_stress", (0, 0.0))[0]

    def work():
        with attach(Span("batch")):
            for _ in range(per):
                with span("tally_stress"):
                    pass
    prev = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=work) for _ in range(workers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(prev)
    assert not any(t.is_alive() for t in threads)
    assert span_totals()["tally_stress"][0] - before == workers * per


def test_cold_rung_adds_one_compile_span():
    idx = _filled(SegmentedIndex(L=L, b=B, delta_cap=64))
    c0 = compile_stats()
    t0 = time.perf_counter()
    root = Span("request")
    with attach(root):
        idx.topk(QUERY, k=4, tau0=3)          # first call: compiles
    c1 = compile_stats()
    compiles = [sp for sp in _walk(root) if sp.name == "compile"]
    assert c1["compiles"] - c0["compiles"] == len(compiles) >= 1
    assert c1["compile_s"] - c0["compile_s"] == pytest.approx(
        sum(sp.dur for sp in compiles))
    launches = [sp for sp in _walk(root) if sp.name == "rung_launch"]
    assert launches
    for launch in launches:
        assert [ch.name for ch in launch.children] == ["compile"]
    assert compile_stats(until=t0) == c0
    root = Span("request")
    with attach(root):
        idx.topk(QUERY, k=4, tau0=3)          # warm: nothing compiles
    assert not [sp for sp in _walk(root) if sp.name == "compile"]
    assert compile_stats()["compiles"] == c1["compiles"]


def _module_texts(monkeypatch, scoped: bool):
    """The optimized HLO of every fused program variant one top-k batch
    runs, as the scope table recorder sees it."""
    texts = []
    real = segments.hlo_scopes

    def spy(text):
        texts.append(text)
        return real(text)
    monkeypatch.setattr(segments, "hlo_scopes", spy)
    if not scoped:
        monkeypatch.setattr(jax, "named_scope",
                            lambda name: contextlib.nullcontext())
    idx, rows = _suffix_index()
    with attach(Span("request")):
        idx.topk_batch(rows[:2], 5, tau0=2)
    monkeypatch.undo()
    return texts


def _strip_metadata(text):
    text = re.sub(r', metadata=\{(?:[^{}"]|"[^"]*")*\}', "", text)
    return [ln for ln in text.splitlines()
            if ln.lstrip().startswith(("%", "ROOT", "ENTRY", "}"))]


@pytest.fixture
def no_persistent_compile_cache():
    """The persistent cache keys a program without its debug info, so a
    hit would hand the unscoped program the scoped one's metadata."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def test_rung_scopes_change_hlo_metadata_only(monkeypatch,
                                              no_persistent_compile_cache):
    scoped = _module_texts(monkeypatch, True)
    plain = _module_texts(monkeypatch, False)
    assert len(scoped) == len(plain) >= 1
    for a, b in zip(scoped, plain):
        assert _strip_metadata(a) == _strip_metadata(b)
        assert not set(segments.RUNG_SCOPES) & set(
            segments.hlo_scopes(b).values())
    for name in segments.RUNG_SCOPES:
        assert f"/{name}/" in scoped[0], name
    assert set(segments.hlo_scopes(scoped[0]).values()) == set(
        segments.RUNG_SCOPES)


def test_hlo_scopes_by_hand():
    text = "\n".join([
        "HloModule jit_run, entry_computation_layout={()->s32[]}",
        "",
        "%fused_computation.1 (param_0: s32[4]) -> s32[4] {",
        "  %param_0 = s32[4]{0} parameter(0)",
        '  ROOT %add.1 = s32[4]{0} add(%param_0, %param_0), '
        'metadata={op_name="jit(run)/rung.select/add" stack_frame_id=2}',
        "}",
        "",
        "ENTRY %main.3 (p: s32[4]) -> s32[4] {",
        "  %p = s32[4]{0} parameter(0)",
        '  %gather.2 = s32[4]{0} gather(%p, %p), '
        'metadata={op_name="jit(run)/rung.traverse/jit(_take)/gather"}',
        "  %pad.5 = s32[4]{0} pad(%gather.2, %p), padding=0_0",
        "  ROOT %fusion.7 = s32[4]{0} fusion(%pad.5), kind=kLoop, "
        "calls=%fused_computation.1",
        "}"])
    # fusion.7: its callee's root; pad.5 (made by the compiler): its user
    assert segments.hlo_scopes(text) == {"add.1": "rung.select",
                                         "gather.2": "rung.traverse",
                                         "pad.5": "rung.select",
                                         "fusion.7": "rung.select"}


def test_tracer_ring_bounded():
    tr = Tracer(capacity=4)
    for i in range(10):
        tr.add(Span(f"r{i}"))
    assert len(tr) == 4
    assert [s.name for s in tr.roots()] == ["r6", "r7", "r8", "r9"]
    tr.clear()
    assert len(tr) == 0


# -- scheduler span trees + Chrome export --------------------------------

def _traced_run(tmp_path):
    tracer = Tracer()
    sched = Scheduler(config=SchedulerConfig(slow_ms=0.0), tracer=tracer)
    sched.create_collection("c", CollectionConfig(L=L, b=B))
    sched.submit_insert("c", SKETCHES)
    futs = [sched.submit_topk("c", SKETCHES[i], k=3) for i in range(5)]
    futs.append(sched.submit_search("c", QUERY, 3))
    sched.pump()
    for f in futs:
        f.result()
    return tracer, sched


def test_scheduler_span_tree_and_chrome_json(tmp_path):
    tracer, sched = _traced_run(tmp_path)
    roots = tracer.roots()
    assert len(roots) == 7                      # 1 insert + 5 topk + 1 search
    read = next(r for r in roots if r.args["op"] == "topk")
    names = [c.name for c in read.children]
    assert names[0] == "queue_wait" and "batch" in names
    batch = read.find("batch")
    assert batch.find("execute") is not None
    assert batch.find("rung_dispatch") is not None
    # queue_wait + batch cover the request end-to-end exactly
    qw = read.find("queue_wait")
    assert abs((qw.dur + batch.dur) - read.dur) < 1e-6

    path = tracer.write_chrome(str(tmp_path / "trace.json"))
    events = json.load(open(path))
    xs = [e for e in events if e["ph"] == "X"]
    assert {e["name"] for e in xs} >= {"request", "queue_wait", "batch",
                                       "execute", "rung_dispatch"}
    # the shared batch span emits once despite 5 linking roots
    assert sum(e["name"] == "batch" and e["args"]["op"] == "topk"
               for e in xs) == 1
    # trace_report accepts it: nesting valid, >=1 complete request tree
    assert trace_report.check_nesting(events) >= 2
    trees = trace_report.request_trees(events)
    assert any(qw is not None and b is not None for _, qw, b in trees)
    assert trace_report.report(str(tmp_path), check=True) == 0

    # slow_ms=0.0: every request also landed in the slow-query log
    assert len(sched.slowlog) == 7
    entry = sched.slowlog.entries()[-1]
    assert entry["spans"]["name"] == "request" and entry["e2e_ms"] >= 0


def test_slowlog_ring_and_jsonl(tmp_path):
    p = str(tmp_path / "slow.jsonl")
    log = SlowQueryLog(capacity=2, path=p)
    for i in range(5):
        sp = Span(f"request")
        sp.dur = i / 1e3
        log.record(sp, op="topk")
    assert len(log) == 2 and log.dropped == 3
    lines = [json.loads(x) for x in open(p)]
    assert len(lines) == 5                      # the file keeps everything
    assert lines[-1]["op"] == "topk"


# -- Prometheus exposition ----------------------------------------------

def test_format_value_round_trips():
    for v in (0, 3, -17, 0.1, 0.30000000000000004, 1e-9, 2.5, 3.0,
              float("inf"), float("-inf")):
        s = format_value(v)
        assert float(s) == float(v) or (s in ("+Inf", "-Inf"))
    assert format_value(3.0) == "3"
    assert format_value(True) == "1"
    assert format_value(float("nan")) == "NaN"


def test_histogram_cumulative_monotone():
    h = Histogram(buckets=(0.001, 0.01, 0.1))
    for v in (0.0005, 0.005, 0.05, 0.5, 0.05):
        h.observe(v)
    cum = h.cumulative()
    assert cum[-1] == ("+Inf", 5)
    counts = [c for _, c in cum]
    assert counts == sorted(counts)
    lines = h.sample_lines("lat", 'op="topk"')
    assert lines[-1] == "lat_count{op=\"topk\"} 5"


def test_render_text_parses_as_prometheus():
    sched = Scheduler()
    sched.create_collection("c", CollectionConfig(L=L, b=B))
    sched.submit_insert("c", SKETCHES)
    futs = [sched.submit_topk("c", SKETCHES[i], k=3) for i in range(3)]
    sched.pump()
    for f in futs:
        f.result()
    text = sched.render_stats()
    parsed = parse_exposition(text)
    names = {s[0] for s in parsed["samples"]}
    assert "serving_latency_seconds_bucket" in names
    assert "serving_queue_latency_seconds_count" in names
    assert parsed["types"]["serving_latency_seconds"] == "histogram"
    assert ("serving_requests_total", {"op": "topk"}, 3.0) in \
        parsed["samples"]
    assert 'index_n_live{collection="c"}' in text


def test_parser_rejects_malformed():
    with pytest.raises(ValueError):
        parse_exposition("# TYPE x bogus\nx 1\n")
    with pytest.raises(ValueError):
        parse_exposition("# TYPE x counter\nx{op=} 1\n")
    with pytest.raises(ValueError):
        parse_exposition("# TYPE x counter\nx notanumber\n")
    with pytest.raises(ValueError):
        parse_exposition("orphan_sample 1\n")   # no TYPE line


# -- cross-instance metrics isolation (satellite a) ----------------------

def test_metrics_deltas_not_bled_across_instances():
    idx = _filled(SegmentedIndex(L=L, b=B, delta_cap=64))
    idx.topk(QUERY, k=4)                # traffic before the scheduler
    m = ServingMetrics()                # baselines at construction
    snap = m.snapshot()
    assert all(v == 0 for v in snap["device_dispatch"].values())
    assert snap["searcher_cache"]["hits"] == 0
    assert snap["searcher_cache"]["misses"] == 0
    assert snap["searcher_cache"]["traces"] == 0
    assert all(v == 0 for v in snap["tier"].values())
    idx.topk(SKETCHES[5], k=4)          # traffic after: the delta sees it
    snap2 = m.snapshot()
    assert snap2["device_dispatch"]["total"] >= 1
    m.rebaseline()
    assert all(v == 0
               for v in m.snapshot()["device_dispatch"].values())
