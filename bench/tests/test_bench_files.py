"""The benchmark's files, generator and trace reduction, on the CPU."""

from __future__ import annotations

import hashlib
import json
import pathlib
import shutil
import subprocess
import sys

import numpy as np
import pytest

from bench import corpus, roofline, spec, xplane
from bench.drive import Window

ROOT = pathlib.Path(__file__).resolve().parents[2]
DATA = pathlib.Path(__file__).resolve().parent / "data"


def test_every_file_named_in_benchmark_json_is_found():
    bench = spec.load_benchmark(ROOT)
    named = spec.files_named(ROOT)
    assert len(named) == (len(bench["configs"]) + len(bench["per_layer"])
                          + len(bench["workloads"])
                          + len({w["traffic"] for w in bench["workloads"]}))
    for key, path in named.items():
        assert path.is_file(), key
        if path.suffix == ".json":
            json.loads(path.read_text())
    for m in bench["per_layer"]:
        assert callable(spec.metric_reader(m["name"], ROOT))
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"], ROOT)
        assert cell.config["name"] == w["config"]
        assert cell.end_to_end and cell.per_layer
        assert cell.traffic["kind"] in ("topk", "ingest")


def _digest(root: pathlib.Path) -> dict:
    return {str(p.relative_to(root)): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted((root / "bench").rglob("*")) if p.is_file()}


def test_new_cell_config_traffic_and_metric_are_files_only(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    before = _digest(tmp_path)
    b = tmp_path / "bench"
    cfg = json.loads((b / "configs" / "review.json").read_text())
    cfg.update(name="tiny", n=4096)
    (b / "configs" / "tiny.json").write_text(json.dumps(cfg))
    (b / "traffic" / "topk3.sparse.json").write_text(json.dumps({
        "kind": "topk", "k": 3, "arrivals": "poisson", "arrival_order": 5,
        "perturbed_share": 0.25, "flips": 1}))
    (b / "workloads" / "tiny.topk3.sparse.json").write_text(
        json.dumps({"rate_per_s": 3.0}))
    (b / "metrics" / "answered_share.py").write_text(
        "def read(win):\n    return 100.0 * win.answered / win.count\n")
    bench = json.loads((tmp_path / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny", "source": "a test",
                             "file": "bench/configs/tiny.json",
                             "reduced": ["n"], "why": "a test"})
    bench["workloads"].append({"name": "tiny.topk3.sparse",
                               "config": "tiny", "traffic": "topk3.sparse",
                               "chips": 1, "why": "a test"})
    bench["per_layer"].append({"name": "answered_share", "unit": "%",
                               "better": "higher",
                               "source": "program_counter", "layer": "test",
                               "moves": "topk_mean_ms",
                               "workloads": ["tiny.topk3.sparse"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    after = _digest(tmp_path)
    assert {k: v for k, v in after.items() if k in before} == before
    cell = spec.load_cell("tiny.topk3.sparse", tmp_path)
    assert cell.config["n"] == 4096 and cell.traffic["k"] == 3
    assert [m["name"] for m in cell.per_layer][-1] == "answered_share"
    win = Window(count=4, answered=3, latency_s=np.zeros(4), answers=[],
                 errors=[], partial=0, late_s=np.zeros(4), t_win=0.0, t_last=1.0,
                 dispatch={}, traces=0, compiles=0)
    assert spec.metric_reader("answered_share", tmp_path)(win) == 75.0
    # the existing cells do not report the new metric
    assert "answered_share" not in [
        m["name"] for m in spec.load_cell("review.topk10.poisson",
                                          tmp_path).per_layer]
    # the new mix's own arrival order: the same gaps, other send times
    offsets = corpus.arrivals(cell.traffic, 30, 10.0)
    assert np.all(np.diff(offsets) > 0) and offsets[-1] < 10.0
    ours = corpus.arrivals(spec.load_cell("review.topk10.poisson",
                                          tmp_path).traffic, 30, 10.0)
    assert not np.array_equal(offsets, ours)
    np.testing.assert_allclose(np.sort(np.diff(np.append(offsets, 10.0))),
                               np.sort(np.diff(np.append(ours, 10.0))))


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 12345, 2**40 + 3])
def test_generator_is_fixed_by_seed(seed):
    db = corpus.make_corpus(3000, 16, 2, 1024, seed)
    assert np.array_equal(db, corpus.make_corpus(3000, 16, 2, 1024, seed))
    other = corpus.make_corpus(3000, 16, 2, 1024, seed + 1)
    assert not np.array_equal(db, other)
    q = corpus.make_queries(db, 2, 50, 0.5, 2, seed)
    assert np.array_equal(q, corpus.make_queries(db, 2, 50, 0.5, 2, seed))
    # every seed sends at the same times
    a = corpus.arrival_offsets(240, 30.0, 2019)
    assert np.array_equal(a, corpus.arrival_offsets(240, 30.0, 2019))
    assert a[0] == 0.0 and a[-1] < 30.0 and np.all(np.diff(a) > 0)
    gaps = np.diff(np.append(a, 30.0))
    # exponential quantiles: about 1 - 1/e of the gaps below the mean
    assert 0.6 < np.mean(gaps < 30.0 / 240) < 0.66


def test_every_seed_builds_the_same_trie_shapes():
    """Per-position relabeling and shuffles within a seal's block keep
    each segment's trie level sizes, so compiled programs are shared."""
    from repro.core.trie_builder import build_trie_levels
    shapes = []
    for seed in (1, 2, 2**35):
        db = corpus.make_corpus(5000, 16, 2, 2048, seed)
        shapes.append([build_trie_levels(db[lo:lo + 2048], 2).t
                       for lo in (0, 2048)]
                      + [build_trie_levels(db[:4096], 2).t])
    assert shapes[0] == shapes[1] == shapes[2]


def test_query_mix_has_near_neighbours():
    db = corpus.make_corpus(2000, 16, 2, 1024, 3)
    q = corpus.make_queries(db, 2, 40, 0.5, 2, 3)
    from bench.reference import numpy_dists, pack_rows
    packed = pack_rows(db, 2)
    nearest = [int(numpy_dists(packed, x, 16, 2).min()) for x in q]
    assert sum(d <= 2 for d in nearest) >= 20


def test_dispatch_bytes_by_hand():
    # review-like: one packed segment (ls=12 -> S=4 -> 1 word) and one
    # plane segment of a 32-character corpus (ls=10 -> S=22 -> 2 words)
    assert roofline.suffix_row_words(16, 2, 12) == 1
    assert roofline.suffix_row_words(32, 2, 10) == 2
    assert roofline.suffix_row_words(32, 2, 16) == 1
    got = roofline.dispatch_bytes(16, 2, [(1000, 12, 500)], 10)
    assert got == 1000 * (4 + 9) + 500 + 10 * (2 * 4 + 9)
    got = roofline.dispatch_bytes(32, 2, [(100, 10, 7), (50, 16, 3)], 0)
    assert got == 100 * (8 + 9) + 7 + 50 * (4 + 9) + 3
    assert roofline.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        roofline.peaks_for("cpu")


def test_trace_reduction_on_recorded_trace():
    """A CPU trace of two calls of one jitted function (four ops each),
    the first inside annotation bench.step_a, then 20 ms of sleep, the
    second inside bench.step_b.  Numbers below read off the events by
    hand."""
    tr = xplane.read_trace(str(DATA / "cpu_trace.xplane.pb"))
    assert [a[0] for a in tr.annotations] == [
        "bench.clock_sync", "bench.step_a", "bench.step_b"]
    (ops,) = tr.ops.values()
    iv = [(s, e) for _, _, s, e in ops]
    assert len(iv) == 8
    # 900076 + 59998 + 1575 + 579183 + 696430 + 59420 + 2314 + 262340
    assert xplane.busy_ns(iv) == 2561336
    lo, hi = 144415, 23726199               # clock_sync start .. step_b end
    window = hi - lo                         # 23581784 ns
    idle = 1 - xplane.busy_ns(xplane.clip(iv, lo, hi)) / window
    assert idle == pytest.approx(1 - 2561336 / 23581784, abs=1e-15)
    spans = [a for a in tr.annotations if a[0] != "bench.clock_sync"]
    top = xplane.idle_gaps_by_host(iv, lo, hi, spans, top=3)
    assert top == [["no batch open", 20546887 / 1e9],
                   ["bench.step_b", 255438 / 1e9],
                   ["bench.step_a", 211284 / 1e9]]
    ops_top = xplane.top_ops(ops, top=2)
    assert ops_top == [["jit__lambda/wrapped_cosine", (900076 + 696430) / 1e9],
                       ["jit__lambda/broadcast_add_fusion",
                        (579183 + 262340) / 1e9]]


def test_union_of_overlapping_intervals():
    iv = [(0, 10), (5, 20), (30, 40), (35, 36), (50, 50)]
    assert xplane.union(iv) == [(0, 20), (30, 40)]
    assert xplane.busy_ns(iv) == 30
    assert xplane.gaps(iv, 0, 60) == [(20, 30), (40, 60)]
    assert xplane.name_gap((20, 30), [("outer", 0, 100), ("inner", 22, 28)]) \
        == "inner"


def test_self_time_of_nested_ops_and_short_names():
    loop = "%while.5 = (s32[], s32[4,8]{1,0:T(4,128)}) while(...)"
    body = "%fusion.9 = s32[8]{0:T(1024)S(1)} fusion(s32[9]{0} %p), kind=kCustom"
    ops = [(loop, "", 0, 100), (body, "", 10, 40), (body, "", 50, 70),
           ("copy", "", 120, 130)]
    assert xplane.self_times(ops) == [(loop, 50), (body, 30), (body, 20),
                                      ("copy", 10)]
    assert xplane.short_name(body) == "fusion.9 s32[8]"
    assert xplane.top_ops(ops) == [["while.5 (tuple)", 50e-9],
                                   ["fusion.9 s32[8]", 50e-9],
                                   ["copy", 10e-9]]


def test_run_refuses_without_a_chip(capsys):
    from bench import run
    rc = run.main(["--workload", "review.topk10.poisson", "--seed", "1",
                   "--seconds", "1"], compile_cache=False)
    assert rc != 0
    assert capsys.readouterr().out == ""


def test_run_refuses_without_the_program(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "review.topk10.poisson", "--seed", "1", "--seconds", "1"],
        cwd=tmp_path, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert proc.stdout == ""
