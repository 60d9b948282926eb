"""The output check: the reference against the index, and whole runs of
the harness at a tiny size on the CPU (no chip), sound, with the control
in the program's place, and with the timed path broken."""

from __future__ import annotations

import json
import pathlib
import shutil

import numpy as np
import pytest

from bench import corpus
from bench.reference import DeviceReference, numpy_topk, pack_rows

ROOT = pathlib.Path(__file__).resolve().parents[2]


@pytest.mark.parametrize("L,geometry", [(16, "packed"), (32, "plane")])
def test_reference_equals_segmented_index(L, geometry):
    from repro.core.segments import SegmentedIndex
    b, n, k = 2, 3000, 10
    db = corpus.make_corpus(n, L, b, 1024, 11)
    idx = SegmentedIndex(L=L, b=b, delta_cap=1024, layout="suffix")
    for lo in range(0, n, 256):
        idx.insert(db[lo:lo + 256])
    assert len(idx.segments) >= 1 and idx.stats()["delta_rows"] > 0
    S = L - int(idx.segments[0].index.ls)
    assert (b * S <= 32) == (geometry == "packed")
    qs = corpus.make_queries(db, b, 12, 0.5, 2, 11)
    got = idx.topk_batch(qs, k)
    want_ids, want_d = DeviceReference(db, b).topk(qs, k)
    np.testing.assert_array_equal(np.asarray(got.ids), want_ids)
    np.testing.assert_array_equal(np.asarray(got.dists), want_d)
    packed = pack_rows(db, b)
    for i, q in enumerate(qs):
        ids, d = numpy_topk(packed, q, k, L, b)
        np.testing.assert_array_equal(ids, want_ids[i])
        np.testing.assert_array_equal(d, want_d[i])


def _tiny_root(tmp_path: pathlib.Path) -> pathlib.Path:
    """A copy of the benchmark with every configuration shrunk to a size
    the CPU's interpret mode runs in seconds."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in (tmp_path / "bench" / "configs").glob("*.json"):
        c = json.loads(p.read_text())
        c.update(n=3000, delta_cap=1024, insert_chunk=256)
        c["serving"]["max_batch"] = 4
        p.write_text(json.dumps(c))
    for p in (tmp_path / "bench" / "workloads").glob("*.json"):
        c = json.loads(p.read_text())
        if "rate_per_s" in c:
            c["rate_per_s"] = 4.0
            p.write_text(json.dumps(c))
    p = tmp_path / "bench" / "traffic" / "ingest.bulk.json"
    c = json.loads(p.read_text())
    c["chunk_rows"] = 256
    p.write_text(json.dumps(c))
    return tmp_path


def _run(root, capsys, *extra, trace=0, cell="review.topk10.poisson"):
    from bench import run
    rc = run.main(["--workload", cell, "--seed", str(2**31 + 77),
                   "--seconds", "1.5", "--trace", str(trace), *extra],
                  root=root, require_chip=False, compile_cache=False,
                  peaks={"hbm_bytes_per_s": 819e9})
    out, err = capsys.readouterr()
    assert rc == 0, err[-2000:]
    line = json.loads(out.strip().splitlines()[-1])
    assert list(line)[-1] == "checks"
    assert err.strip().splitlines()[-1].startswith("check ")
    return line


@pytest.mark.parametrize("trace", [0, 1])
def test_sound_run_is_correct(tmp_path, capsys, trace):
    line = _run(_tiny_root(tmp_path), capsys, trace=trace)
    assert line["correct"] is True
    assert line["attempted"] == 6 and line["failed"] == 0
    assert line["checks"]["wrong_answers"]["value"] == 0
    names = set(line["metrics"])
    if trace:
        assert {"sched_queue_wait_ms_p50", "rungs_per_topk",
                "device_idle_pct.topk"} <= names
        assert line["device"]["busy_s"] > 0
        assert line["breakdown"]["device_ops"]
    else:
        assert names == {"topk_mean_ms", "topk_p95_ms", "setup_s"}


def test_control_run_is_not_correct(tmp_path, capsys):
    line = _run(_tiny_root(tmp_path), capsys, "--control", "1")
    assert line["correct"] is False
    assert line["checks"]["wrong_answers"]["value"] > 0


def test_altered_answer_is_not_correct(tmp_path, capsys, monkeypatch):
    """The timed path broken where answers are produced: the index's
    top-k returns its first row with one id changed."""
    from repro.core import segments
    orig = segments.SegmentedIndex.topk_batch

    def altered(self, qs, k, *a, **kw):
        res = orig(self, qs, k, *a, **kw)
        ids = np.array(res.ids)
        ids[0, -1] = (ids[0, -1] + 1) % self.n_ids
        return res._replace(ids=ids)
    monkeypatch.setattr(segments.SegmentedIndex, "topk_batch", altered)
    line = _run(_tiny_root(tmp_path), capsys)
    assert line["correct"] is False
    assert line["checks"]["wrong_answers"]["value"] > 0


INGEST = "review.ingest.bulk"


@pytest.mark.parametrize("trace", [0, 1])
def test_sound_ingest_run_is_correct(tmp_path, capsys, trace):
    line = _run(_tiny_root(tmp_path), capsys, cell=INGEST, trace=trace)
    assert line["correct"] is True
    assert line["attempted"] == 2 * 1024 // 256 and line["failed"] == 0
    if trace:
        assert set(line["metrics"]) == {"device_idle_pct.ingest",
                                        "ingest_build_s_per_mrow"}
    else:
        assert set(line["metrics"]) == {"ingest_rows_s", "setup_s"}


def test_control_ingest_run_is_not_correct(tmp_path, capsys):
    line = _run(_tiny_root(tmp_path), capsys, "--control", "1", cell=INGEST)
    assert line["correct"] is False
    assert line["checks"]["wrong_answers"]["value"] > 0


def test_insert_acknowledged_but_not_applied_is_not_correct(
        tmp_path, capsys, monkeypatch):
    """The timed path broken: the third insert returns its ids and leaves
    the index's rows as they were."""
    from repro.core import segments
    orig = segments.SegmentedIndex.insert
    calls = []

    def lossy(self, sketches, payloads=None):
        calls.append(1)
        if len(calls) == 3:
            ids = np.arange(self.n_ids, self.n_ids + len(sketches))
            self.n_ids += len(sketches)
            return ids
        return orig(self, sketches, payloads)
    monkeypatch.setattr(segments.SegmentedIndex, "insert", lossy)
    line = _run(_tiny_root(tmp_path), capsys, cell=INGEST)
    assert line["correct"] is False
    assert line["checks"]["wrong_answers"]["value"] > 0
