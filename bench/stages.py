"""What the program's own spans say about a traced window: device time
of the fused rung program by named stage, device idle time inside the
scheduler's batches, and the set-up's spans and compiles.

The rung program wraps its stages in ``jax.named_scope`` (``rung.*``,
``repro.core.segments.RUNG_SCOPES``).  A TPU trace names a device op by
its HLO instruction alone (no ``op_name``, no module stat), so the
program records, while a span is attached, which scope each instruction
of every compiled variant came from (``segments.fused_scope_tables()``)
and labels each ``rung_dispatch`` span with its variant
(``args["program"]``).  An op resolves through the ``rung_dispatch`` span
that holds its middle: that span's table, then its instruction name.

A program without these spans, tables or tallies (one older than them)
gives ``None`` from every function here, and nothing raises.

The functions on plain lists are tested apart from the window
(``bench/tests/test_bench_stages.py``).
"""

from __future__ import annotations

import bisect
from collections import defaultdict
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

from . import xplane
from .drive import log

Rung = Tuple[int, int, str]          # (start_ns, end_ns, program label)

SETUP_SPANS = ("trie_build", "pack_vertical", "seal", "merge",
               "store_refresh", "delta_planes", "rung_program",
               "rung_launch", "rung_wait", "compile")


def instruction(op_name: str) -> str:
    """The HLO instruction an op event stands for: a TPU event is named
    by the instruction's text (``%fusion.3 = s32[8]{0} fusion(...)``), a
    CPU one by the instruction's name."""
    return op_name.split(" = ", 1)[0].lstrip("%")


def rung_ops(ops: Sequence[Tuple[str, str, int, int]],
             rungs: Sequence[Rung], tables: Dict[str, Dict[str, str]]
             ) -> Iterator[Tuple[Optional[str], str, int]]:
    """(scope, op name, self ns) of each op of one device whose middle
    lies in a rung span, the scope read from that span's table (``None``
    for an op of a rung program that no scope holds).  ``rungs`` must
    not overlap."""
    rungs = sorted(rungs)
    starts = [r[0] for r in rungs]
    for (name, _, s, e), (_, own) in zip(ops, xplane.self_times(ops)):
        mid = (s + e) // 2
        i = bisect.bisect_right(starts, mid) - 1
        if i >= 0 and mid < rungs[i][1]:
            yield tables.get(rungs[i][2], {}).get(instruction(name)), \
                name, own


def scope_self_ns(ops, rungs, tables) -> Dict[Optional[str], int]:
    """Self time (ns) per scope of ``rung_ops``."""
    out: Dict[Optional[str], int] = defaultdict(int)
    for scope, _, own in rung_ops(ops, rungs, tables):
        out[scope] += own
    return dict(out)


def idle_inside_ns(busy: Sequence[xplane.Interval],
                   spans: Sequence[xplane.Interval]) -> int:
    """Device idle time summed over the host spans: each span's length
    less the busy time inside it."""
    cover = xplane.union(busy)
    return sum((e - s) - xplane.busy_ns(xplane.clip(cover, s, e))
               for s, e in spans if e > s)


# -- the window ----------------------------------------------------------

def _walk(spans) -> Iterator[object]:
    for sp in spans:
        yield sp
        yield from _walk(sp.children)


def _on_clock(win, sp) -> Tuple[int, int]:
    s = win.pc_to_ns(sp.ts)
    return s, s + int(sp.dur * 1e9)


def rung_spans(win) -> List[Rung]:
    """The window's ``rung_dispatch`` spans that name their program, on
    the trace clock."""
    return [(*_on_clock(win, sp), sp.args["program"])
            for sp in _walk(win.batch_spans)
            if sp.name == "rung_dispatch" and "program" in sp.args]


def stage_seconds(win) -> Optional[Dict[Optional[str], float]]:
    """Device self seconds of the rung programs by scope (``None``:
    unscoped ops), averaged over devices; logs the split, the heaviest
    ops with their scopes, and every unscoped op of 0.5 % or more, once."""
    if "rung_stages" in win.extra:
        return win.extra["rung_stages"]
    win.extra["rung_stages"] = None
    try:
        from repro.core.segments import RUNG_SCOPES, fused_scope_tables
    except ImportError:
        return None
    rungs = rung_spans(win)
    if win.trace is None or not win.trace.ops or not rungs:
        return None
    tables = fused_scope_tables()
    per_op: Dict[Tuple[Optional[str], str], float] = defaultdict(float)
    n_dev = len(win.trace.ops)
    for evs in win.trace.ops.values():
        ops = [op for op in evs if op[3] > win.lo_ns and op[2] < win.hi_ns]
        for scope, name, own in rung_ops(ops, rungs, tables):
            per_op[(scope, xplane.short_name(name))] += own / 1e9 / n_dev
    secs: Dict[Optional[str], float] = defaultdict(float)
    for (scope, _), t in per_op.items():
        secs[scope] += t
    total = sum(secs.values())
    scoped = total - secs.get(None, 0.0)
    if not scoped:
        # JAX's persistent compile cache keys a program without its debug
        # info, so an executable compiled from a program without scopes
        # comes back with that program's metadata: nothing to attribute
        log("rung program ops carry no rung.* scope: the executables came "
            "from a compile cache entry of a program without scopes")
        return None
    module_s = win.dispatch_device_s() or 0.0
    log("rung program device self time by scope: " + ", ".join(
        f"{sc} {secs.get(sc, 0.0):.6f} s" for sc in RUNG_SCOPES)
        + f", unscoped {secs.get(None, 0.0):.6f} s; scoped {scoped:.6f} s"
        f" of {module_s:.6f} s of rung program time"
        + (f" ({scoped / module_s * 100:.3f} %)" if module_s else ""))
    ranked = sorted(per_op.items(), key=lambda kv: -kv[1])
    log("rung program ops by device self time (scope, op, s, % of the "
        "rung programs' self time): " + "; ".join(
            f"{sc}, {op}, {t:.6f}, {t / total * 100:.3f}"
            for (sc, op), t in ranked[:12]))
    loose = [(op, t) for (sc, op), t in ranked
             if sc is None and t >= 0.005 * total]
    log(f"unscoped rung program ops at 0.5 % or more: {len(loose)}"
        + "".join(f"; {op} {t:.6f} s" for op, t in loose))
    win.extra["rung_stages"] = dict(secs)
    return win.extra["rung_stages"]


def stage_ms_per_query(win, scope: str) -> Optional[float]:
    secs = stage_seconds(win)
    if secs is None or not win.answered:
        return None
    return secs.get(scope, 0.0) / win.answered * 1e3


def batch_host_gap_ms(win) -> Optional[float]:
    """Device idle time inside the window's batch spans, per batch."""
    if win.trace is None or not win.trace.ops or not win.batch_spans:
        return None
    spans = [xplane.clip([_on_clock(win, sp)], win.lo_ns, win.hi_ns)
             for sp in win.batch_spans]
    spans = [iv[0] for iv in spans if iv]
    if not spans:
        return None
    per = [idle_inside_ns(busy, spans) for busy in win.device_intervals()]
    return sum(per) / len(per) / len(spans) / 1e6


def span_durations(win, name: str) -> List[float]:
    return [sp.dur for sp in _walk(win.batch_spans) if sp.name == name]


def setup_span_seconds(win) -> Optional[Dict[str, Tuple[int, float]]]:
    """``{name: (count, seconds)}`` of the spans closed before the window
    (process-wide tallies less the window's own spans); logs the split
    and the backend compiles up to the first request once."""
    if "setup_spans" in win.extra:
        return win.extra["setup_spans"]
    out = None
    try:
        from repro.obs import compile_stats, span_totals
    except ImportError:
        pass
    else:
        totals = span_totals()
        if totals:
            inside: Dict[str, List[float]] = defaultdict(lambda: [0, 0.0])
            for sp in _walk(win.batch_spans):
                inside[sp.name][0] += 1
                inside[sp.name][1] += sp.dur
            out = {k: (n - inside[k][0], s - inside[k][1])
                   for k, (n, s) in totals.items()}
            comp = compile_stats(until=win.t_win)
            log("set-up spans before the first request (count, s): "
                + ", ".join(f"{k} {out[k][0]} {out[k][1]:.3f}"
                            for k in SETUP_SPANS if k in out)
                + f"; backend compiles on every thread: "
                f"{comp['compiles']} in {comp['compile_s']:.3f} s")
    win.extra["setup_spans"] = out
    return out
