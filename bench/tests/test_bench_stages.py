"""The readers of the program's own spans (``bench/stages.py``): scope
attribution and device idle time inside batches by hand, on synthetic
intervals and on a recorded CPU trace; the new metrics in a traced run
of the harness; and nothing but ``None`` from a program without the
spans, tables and tallies they read."""

from __future__ import annotations

import pathlib

import numpy as np
import pytest

from bench import spec, stages, xplane
from bench.drive import Window
from repro.obs import Span

DATA = pathlib.Path(__file__).resolve().parent / "data"

NEW_TOPK_METRICS = {"topk_traverse_ms_per_query", "topk_verify_ms_per_query",
                    "topk_select_ms_per_query", "rung_launch_ms_p50",
                    "topk_batch_host_gap_ms", "setup_trie_build_s",
                    "setup_compile_s"}


def test_scope_attribution_by_hand():
    """Two rung spans of two program variants, a loop holding its body's
    ops, an op with no scope, and ops outside every rung."""
    tables = {"p0": {"while.1": "rung.traverse", "fusion.2": "rung.traverse",
                     "fusion.3": "rung.select"},
              "p1": {"fusion.2": "rung.verify"}}
    rungs = [(100, 200, "p1"), (0, 100, "p0")]
    ops = [("%while.1 = (s32[]) while(...)", "", 10, 60),
           ("%fusion.2 = s32[8]{0} fusion(...)", "", 20, 30),  # in the loop
           ("%fusion.2 = s32[8]{0} fusion(...)", "", 40, 45),  # in the loop
           ("fusion.3", "", 70, 90),
           ("copy.9", "", 92, 96),                 # no scope in p0's table
           ("fusion.2", "", 120, 150),             # p1's table: verify
           ("fusion.3", "", 190, 230),             # middle 210: no rung
           ("fusion.3", "", 300, 310)]             # no rung
    got = stages.scope_self_ns(ops, rungs, tables)
    assert got == {"rung.traverse": (50 - 10 - 5) + 10 + 5,
                   "rung.select": 20, None: 4, "rung.verify": 30}
    assert stages.instruction(ops[0][0]) == "while.1"


def test_idle_inside_spans_by_hand():
    busy = [(0, 10), (5, 20), (30, 40), (55, 70)]
    # [0, 25): busy 20, idle 5; [35, 60): busy 5 + 5, idle 15; [80, 90): 10
    assert stages.idle_inside_ns(busy, [(0, 25), (35, 60), (80, 90)]) == 30
    assert stages.idle_inside_ns(busy, [(5, 5)]) == 0


# The scope table of the recorded program, as ``segments.hlo_scopes``
# read it from the compiled module when the trace was made.
RECORDED_TABLE = {"cos.0": "rung.traverse", "mul.1": "rung.traverse",
                  "mul.0": "rung.traverse", "slice.0": "rung.select",
                  "broadcast_multiply_fusion": "rung.traverse",
                  "sort.0": "rung.select", "wrapped_slice": "rung.select",
                  "constant.10": "rung.traverse"}


def test_stages_on_recorded_trace():
    """A CPU trace of three calls of one jitted function whose ops lie in
    ``rung.traverse`` (a fused multiply) and ``rung.select`` (a sort and a
    slice): under ``attach(Span("batch"))``, two calls each inside
    ``span("rung_dispatch")`` with 20 ms of sleep between them; then,
    after the batch, 10 ms of sleep and a third call.  The program's
    spans are ``obs.`` annotations in the trace, read with the
    annotation prefix ``read_trace`` already takes.  Numbers below read
    off the events by hand."""
    tr = xplane.read_trace(str(DATA / "cpu_rung_trace.xplane.pb"),
                           annotation_prefix=("bench.", "obs."))
    assert tr.annotations == [
        ("bench.clock_sync", 165523, 170802),
        ("obs.batch", 257213, 75038552),
        ("obs.rung_dispatch", 305223, 30607356),
        ("obs.rung_dispatch", 50898694, 74937009)]
    (ops,) = tr.ops.values()
    assert len(ops) == 9
    rungs = [(s, e, "p0") for name, s, e in tr.annotations
             if name == "obs.rung_dispatch"]
    got = stages.scope_self_ns(ops, rungs, {"p0": RECORDED_TABLE})
    # multiply fusion: 1010242 - 569638, 51622431 - 51227531; sort and
    # slice: 30378364 - 1225703 + 30386411 - 30382153, 73221717 -
    # 51728313 + 73231799 - 73225885; the third call is in no rung
    assert got == {"rung.traverse": 440604 + 394900,
                   "rung.select": 29152661 + 4258 + 21493404 + 5914}
    batch = [(s, e) for name, s, e in tr.annotations if name == "obs.batch"]
    busy = [(s, e) for _, _, s, e in ops]
    # 75038552 - 257213 less the six ops inside it
    assert stages.idle_inside_ns(busy, batch) == (
        74781339 - (440604 + 29152661 + 4258 + 394900 + 21493404 + 5914))


def _span(name, ts, dur, children=(), **args):
    sp = Span(name, ts=ts, dur=dur, args=args)
    sp.children = list(children)
    return sp


def _window(batch_spans, ops):
    win = Window(count=2, answered=2, latency_s=np.zeros(2), answers=[],
                 errors=[], partial=0, late_s=np.zeros(2), t_win=0.0,
                 t_last=1.0, dispatch={"fused": 2}, traces=0, compiles=0)
    win.batch_spans = batch_spans
    win.trace = xplane.Trace(ops=ops, modules={}, annotations=[])
    win.lo_ns, win.hi_ns = 0, 10**9
    win.pc_to_ns = lambda t: int(round(t * 1e9))
    return win


def _synthetic_window():
    """Two batches of one rung each, 1 s window, times in seconds."""
    rung_a = _span("rung_dispatch", 0.100, 0.100, [
        _span("rung_program", 0.100, 0.001),
        _span("rung_launch", 0.101, 0.004),
        _span("rung_wait", 0.105, 0.095)], program="p0")
    rung_b = _span("rung_dispatch", 0.600, 0.100, [
        _span("rung_launch", 0.601, 0.002,
              [_span("compile", 0.6015, 0.0005)])], program="p0")
    batches = [_span("batch", 0.090, 0.120,
                     [_span("execute", 0.095, 0.110, [rung_a])]),
               _span("batch", 0.590, 0.130,
                     [_span("execute", 0.595, 0.120, [rung_b])])]
    ms = 1_000_000
    ops = {"/device:0": [("fusion.2", "", 110 * ms, 150 * ms),
                         ("fusion.3", "", 150 * ms, 190 * ms),
                         ("fusion.3", "", 610 * ms, 690 * ms),
                         ("copy.1", "", 800 * ms, 801 * ms)]}
    return _window(batches, ops)


def test_window_readers_by_hand(monkeypatch):
    from repro.core import segments
    monkeypatch.setattr(segments, "fused_scope_tables", lambda: {
        "p0": {"fusion.2": "rung.traverse", "fusion.3": "rung.select"}})
    win = _synthetic_window()
    assert stages.stage_seconds(win) == pytest.approx(
        {"rung.traverse": 0.040, "rung.select": 0.120})
    assert stages.stage_ms_per_query(win, "rung.traverse") == \
        pytest.approx(20.0)
    assert stages.stage_ms_per_query(win, "rung.verify") == 0.0
    # batch 1: 120 ms, busy 80; batch 2: 130 ms, busy 80 -> 90 ms over 2
    assert stages.batch_host_gap_ms(win) == pytest.approx(45.0)
    assert spec.metric_reader("rung_launch_ms_p50")(win) == \
        pytest.approx(3.0)


def test_window_readers_on_a_program_without_the_spans(monkeypatch):
    """What an older program gives: no ``program`` label on its rung
    spans, no scope tables, no span or compile tallies — ``None``, and
    nothing raises."""
    import repro.obs
    from repro.core import segments
    win = _synthetic_window()
    for sp in stages._walk(win.batch_spans):
        sp.args.pop("program", None)
    assert stages.stage_seconds(win) is None
    win = _synthetic_window()             # tables without rung scopes
    monkeypatch.setattr(segments, "fused_scope_tables",
                        lambda: {"p0": {}})
    assert stages.stage_seconds(win) is None
    win = _synthetic_window()
    monkeypatch.delattr(segments, "fused_scope_tables")
    monkeypatch.delattr(repro.obs, "span_totals")
    monkeypatch.delattr(repro.obs, "compile_stats")
    assert stages.stage_ms_per_query(win, "rung.select") is None
    assert stages.setup_span_seconds(win) is None
    for name in ("setup_trie_build_s", "setup_compile_s",
                 "topk_traverse_ms_per_query"):
        assert spec.metric_reader(name)(win) is None
    for sp in stages._walk(win.batch_spans):
        sp.children = [ch for ch in sp.children if ch.name != "rung_launch"]
    assert spec.metric_reader("rung_launch_ms_p50")(win) is None


def test_traced_run_reports_the_new_metrics(tmp_path, capsys):
    from bench.tests.test_bench_check import _run, _tiny_root
    line = _run(_tiny_root(tmp_path), capsys, trace=1)
    assert line["correct"] is True
    metrics = line["metrics"]
    assert NEW_TOPK_METRICS <= set(metrics)
    for name in NEW_TOPK_METRICS:
        assert metrics[name]["value"] >= 0, name
    assert metrics["topk_traverse_ms_per_query"]["value"] > 0
    assert metrics["setup_trie_build_s"]["value"] > 0
