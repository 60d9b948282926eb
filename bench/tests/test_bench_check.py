"""The output check: the reference against the index, and whole runs of
the harness at a tiny size on the CPU (no chip), sound, with the control
in the program's place, and with the timed path broken."""

from __future__ import annotations

import hashlib
import json
import pathlib
import re
import shutil
import subprocess
import sys
import textwrap
import time

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
    qs = corpus.make_queries(db, b, 12, 0.5, 2, 11, delta_cap=1024)
    got = idx.topk_batch(qs, k)
    want_ids, want_d = DeviceReference(db, b).topk(qs, k)
    np.testing.assert_array_equal(np.asarray(got.ids), want_ids)
    np.testing.assert_array_equal(np.asarray(got.dists), want_d)
    packed = pack_rows(db, b)
    for i, q in enumerate(qs):
        ids, d = numpy_topk(packed, q, k, L, b)
        np.testing.assert_array_equal(ids, want_ids[i])
        np.testing.assert_array_equal(d, want_d[i])


# a set-up limit that a tiny run on a busy CPU meets with room to spare
TINY_SETUP_LIMIT_S = 600


def _tiny_root(tmp_path: pathlib.Path,
               setup_limit_s: float = TINY_SETUP_LIMIT_S) -> pathlib.Path:
    """A copy of the benchmark with every configuration shrunk to a size
    the CPU's interpret mode runs in seconds, and every cell's set-up
    limit set to ``setup_limit_s`` (the cells' own limits are the
    chip's)."""
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
        c["setup_limit_s"] = setup_limit_s
        p.write_text(json.dumps(c))
    p = tmp_path / "bench" / "traffic" / "ingest.bulk.json"
    c = json.loads(p.read_text())
    c["chunk_rows"] = 256
    p.write_text(json.dumps(c))
    return tmp_path


SEED = 2**31 + 77


def _main(root, capsys, *extra, trace=0, cell="review.topk10.poisson"):
    """One run in this process, its set-up timed from this call; the
    result line and standard error."""
    from bench import run
    rc = run.main(["--workload", cell, "--seed", str(SEED),
                   "--seconds", "1.5", "--trace", str(trace), *extra],
                  root=root, require_chip=False, compile_cache=False,
                  peaks={"hbm_bytes_per_s": 819e9},
                  t_start=time.perf_counter())
    out, err = capsys.readouterr()
    assert rc == 0, err[-2000:]
    line = json.loads(out.strip().splitlines()[-1])
    assert list(line)[-1] == "checks"
    assert err.strip().splitlines()[-1].startswith("check ")
    return line, err


def _run(root, capsys, *extra, **kw):
    return _main(root, capsys, *extra, **kw)[0]


@pytest.mark.parametrize("trace", [0, 1])
def test_sound_run_is_correct(tmp_path, capsys, trace):
    """With the set-up limit armed, as in every run."""
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
        assert 0 < line["metrics"]["setup_s"]["value"] < TINY_SETUP_LIMIT_S


def test_setup_past_its_limit_ends_the_run(tmp_path):
    """A run whose warm-up blocks in native code (the first rung
    dispatch sleeps, as a long compile would) ends at the cell's
    ``setup_limit_s`` with exit code 4, one line on standard error that
    names the limit, and nothing on standard output.  The watchdog ends
    the process, so the run has one of its own."""
    limit = 15
    root = _tiny_root(tmp_path, setup_limit_s=limit)
    script = tmp_path / "slow_warm_up.py"
    script.write_text(textwrap.dedent(f"""\
        import pathlib, sys, time
        t_start = time.perf_counter()
        sys.path[:0] = [{str(ROOT / "src")!r}, {str(ROOT)!r}]
        from repro.core import segments
        from bench import run

        def blocked(self, *args, **kwargs):
            time.sleep(3600)
        segments.SegmentedIndex._fused_call = blocked
        sys.exit(run.main(
            ["--workload", "review.topk10.poisson", "--seed", "{SEED}",
             "--seconds", "1.5", "--trace", "0"],
            root=pathlib.Path({str(root)!r}),
            require_chip=False, compile_cache=False, t_start=t_start))
        """))
    t0 = time.perf_counter()
    proc = subprocess.run([sys.executable, str(script)], cwd=tmp_path,
                          capture_output=True, text=True, timeout=300)
    took = time.perf_counter() - t0
    assert proc.returncode == 4, proc.stderr[-2000:]
    assert proc.stdout == ""
    last = proc.stderr.strip().splitlines()[-1]
    m = re.fullmatch(
        r"bench: review\.topk10\.poisson: set-up passed its limit "
        r"\(setup_limit_s 15\) after ([0-9.]+) s, in stage "
        r"(start-up|corpus|load|warm-up bucket \d+) \(.*\); no result",
        last)
    assert m, last
    assert limit <= float(m.group(1)) < took < limit + 60


@pytest.mark.parametrize("case", ["disarmed", "stage", "detail_raises"])
def test_setup_limit_watchdog(tmp_path, case):
    """The watchdog alone, in a process of its own: disarmed before the
    limit it stays quiet and ``setup_s`` is the time to the disarm;
    past the limit it names the stage and its progress (or why that
    could not be read) and ends the process with code 4."""
    body = {
        "disarmed": "setup_s = limit.disarm(t_start + 0.125)\n"
                    "time.sleep(1.0)\n"
                    "print(setup_s, limit.disarm())",
        "stage": "limit.stage('warm-up bucket 2', lambda: '3 rungs')\n"
                 "time.sleep(30)",
        "detail_raises": "limit.stage('load', lambda: 1 / 0)\n"
                         "time.sleep(30)"}[case]
    script = tmp_path / "watch.py"
    script.write_text(
        f"import sys, time\nsys.path.insert(0, {str(ROOT)!r})\n"
        "from bench.setup_limit import SetupLimit\n"
        "t_start = time.perf_counter()\n"
        "limit = SetupLimit('a.cell', 0.5, t_start)\n" + body + "\n")
    proc = subprocess.run([sys.executable, str(script)], capture_output=True,
                          text=True, timeout=120)
    if case == "disarmed":
        assert proc.returncode == 0, proc.stderr
        first, again = map(float, proc.stdout.split())
        assert first == again == pytest.approx(0.125)
        return
    assert proc.returncode == 4 and proc.stdout == ""
    last = proc.stderr.strip().splitlines()[-1]
    assert last.startswith("bench: a.cell: set-up passed its limit "
                           "(setup_limit_s 0.5) after "), last
    want = {"stage": "in stage warm-up bucket 2 (",
            "detail_raises": "in stage load ("}[case]
    assert want in last
    assert last.endswith({"stage": "; 3 rungs); no result",
                          "detail_raises": "ZeroDivisionError('division by "
                          "zero')); no result"}[case])


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


def test_a_32_character_configuration_is_data_files_only(tmp_path, capsys):
    """An L = 32, b = 2 configuration and a cell of it, added as data
    files beside the tiny review ones and run through ``run.main``: the
    harness's own files stay as they were, the run is correct, and every
    segment takes the plane geometry, two words a suffix column."""
    root = _tiny_root(tmp_path)

    def digest():
        return {str(p.relative_to(root)): hashlib.sha256(
            p.read_bytes()).hexdigest()
            for p in sorted((root / "bench").rglob("*")) if p.is_file()}
    before = digest()
    b = root / "bench"
    cfg = json.loads((b / "configs" / "review.json").read_text())
    cfg.update(name="tiny32", L=32)
    cfg["published"] = dict(cfg["published"], L=32)
    (b / "configs" / "tiny32.json").write_text(json.dumps(cfg))
    (b / "workloads" / "tiny32.topk10.poisson.json").write_text(json.dumps(
        {"rate_per_s": 4.0, "setup_limit_s": TINY_SETUP_LIMIT_S}))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny32", "source": "a test",
                             "file": "bench/configs/tiny32.json",
                             "reduced": ["n"], "why": "a test"})
    bench["workloads"].append({"name": "tiny32.topk10.poisson",
                               "config": "tiny32",
                               "traffic": "topk10.poisson", "chips": 1,
                               "why": "a test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "review.topk10.poisson" in m.get("workloads", ()):
            m["workloads"].append("tiny32.topk10.poisson")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    assert {k: v for k, v in digest().items() if k in before} == before

    line, err = _main(root, capsys, cell="tiny32.topk10.poisson")
    assert line["correct"] is True
    assert line["attempted"] == 6 and line["failed"] == 0
    assert set(line["metrics"]) == {"topk_mean_ms", "topk_p95_ms",
                                    "setup_s"}
    assert "corpus: tiny32 n=3000 L=32 b=2" in err
    segs = re.findall(r"^segment \d+: .*$", err, re.M)
    assert segs
    for seg in segs:
        assert "plane geometry, 2 word(s) per column" in seg, seg
