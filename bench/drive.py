"""One top-k cell on the served path: set-up, open-loop window, check.

Set-up (timed as ``setup_s``, from process start to the first request
of the window): the corpus from the seed, a ``Scheduler`` with one
collection, the corpus loaded through ``Scheduler.submit_insert`` in
chunks (seals and merges as the configuration states), and a warm-up of
the cell's own shape buckets with queries of the cell's own mix.

Window: the scheduler runs threaded; the generator sends each request
at its scheduled time whether or not earlier ones have been answered
(an open loop), and each request is timed from that scheduled time to
its answer.

Check: once every answer is in (or a minute past the close), the
program's state is dropped and every answer of the window is compared
with the plain reference (``reference.py``) over the same rows.
"""

from __future__ import annotations

import bisect
import dataclasses
import gc
import shutil
import sys
import tempfile
import threading
import time
from typing import Callable, Dict, List, Optional

import numpy as np

from . import corpus, roofline, spec, xplane

SYNC_MARK = "bench.clock_sync"
LATE_WAIT_S = 60.0


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


@dataclasses.dataclass
class Window:
    """One open-loop window; what the per-layer readers get
    (``bench/metrics/*.py``)."""
    count: int                          # requests scheduled
    answered: int                       # requests answered
    latency_s: np.ndarray               # per request; missing as the wait
    answers: list                       # per request: (ids, dists) or None
    errors: list                        # per request: error text or None
    partial: int                        # answers the program flags as
    #                                     possibly partial (overflow > 0)
    late_s: np.ndarray                  # generator lateness per request
    t_win: float                        # perf_counter of the first send
    t_last: float                       # perf_counter of the last answer
    dispatch: Dict[str, int]            # dispatch_stats() over the window
    traces: int                         # program traces inside the window
    compiles: int                       # backend compiles inside the window
    queue_wait_s: List[float] = dataclasses.field(default_factory=list)
    batch_spans: list = dataclasses.field(default_factory=list)
    trace: Optional[xplane.Trace] = None
    lo_ns: int = 0                      # traced window on the trace clock
    hi_ns: int = 0
    pc_to_ns: Callable[[float], int] = lambda t: 0
    bytes_per_dispatch: int = 0
    peaks: Optional[dict] = None
    extra: dict = dataclasses.field(default_factory=dict)

    def device_intervals(self) -> List[List[xplane.Interval]]:
        if self.trace is None:
            return []
        return [xplane.clip([(s, e) for _, _, s, e in evs], self.lo_ns,
                            self.hi_ns)
                for evs in self.trace.ops.values()]

    def busy_s(self) -> float:
        per = [xplane.busy_ns(iv) for iv in self.device_intervals()]
        return (sum(per) / len(per) / 1e9) if per else 0.0

    def window_s(self) -> float:
        return (self.hi_ns - self.lo_ns) / 1e9

    def host_spans(self, names=None) -> List[tuple]:
        """Batch-level host spans on the trace clock: (name, start, end)."""
        out = []

        def walk(sp):
            if names is None or sp.name in names:
                s = self.pc_to_ns(sp.ts)
                out.append((sp.name, s, s + int(sp.dur * 1e9)))
            for ch in sp.children:
                walk(ch)
        for sp in self.batch_spans:
            walk(sp)
        return out

    def dispatch_device_s(self) -> Optional[float]:
        """Device time of the programs that ran inside a ``rung_dispatch``
        span (the fused top-k rung programs), averaged over devices."""
        rungs = sorted(self.host_spans({"rung_dispatch"}),
                       key=lambda r: r[1])
        if self.trace is None or not rungs:
            return None
        starts = [r[1] for r in rungs]
        per = []
        for mods in self.trace.modules.values():
            tot = 0
            for _, s, e in mods:
                mid = (s + e) // 2
                i = bisect.bisect_right(starts, mid) - 1
                if i >= 0 and mid < rungs[i][2]:
                    tot += e - s
            per.append(tot)
        if not any(per):
            return None
        return sum(per) / len(per) / 1e9


def _leaves(tree) -> list:
    import jax
    return jax.tree_util.tree_leaves(tree)


def compile_events() -> List[float]:
    """A list that every backend compile of this process appends its
    seconds to."""
    from jax import monitoring
    out: List[float] = []
    monitoring.register_event_duration_secs_listener(
        lambda name, secs, **kw: out.append(secs)
        if name == "/jax/core/compile/backend_compile_duration" else None)
    return out


def served_collection(cfg: dict, trace: bool):
    """A ``Scheduler`` set as the configuration states, with one empty
    collection "c" (and a span tracer for a traced run)."""
    from repro.obs.trace import Tracer
    from repro.serving import CollectionConfig, Scheduler, SchedulerConfig
    serving = cfg["serving"]
    sched = Scheduler(
        config=SchedulerConfig(max_batch=int(serving["max_batch"]),
                               max_wait_ms=float(serving["max_wait_ms"]),
                               max_queue=int(serving["max_queue"])),
        tracer=Tracer(capacity=1 << 20) if trace else None)
    index = sched.create_collection("c", CollectionConfig(
        L=int(cfg["L"]), b=int(cfg["b"]), delta_cap=int(cfg["delta_cap"]),
        layout=cfg["layout"])).index
    return sched, index


class TopkCell:
    """The served top-k path of one cell, set up from a seed."""

    def __init__(self, cell: spec.Cell, seed: int, *, trace: bool = False):
        import jax

        self.cell, self.seed, self.traced = cell, seed, trace
        cfg, traffic = cell.config, cell.traffic
        self.n, self.L, self.b = int(cfg["n"]), int(cfg["L"]), int(cfg["b"])
        self.k = int(traffic["k"])
        self.dev = jax.devices()[0]
        self.compile_s = compile_events()

        t0 = time.perf_counter()
        self.db = corpus.make_corpus(self.n, self.L, self.b,
                                     int(cfg["delta_cap"]), seed)
        log(f"corpus: {cfg['name']} n={self.n} L={self.L} b={self.b} "
            f"seed={seed} in {time.perf_counter() - t0:.3f} s")
        self.max_batch = int(cfg["serving"]["max_batch"])
        self.sched, self.index = served_collection(cfg, trace)
        t0 = time.perf_counter()
        futs = [self.sched.submit_insert("c", self.db[lo:hi])
                for lo, hi in corpus.split_blocks(self.n,
                                                  int(cfg["insert_chunk"]))]
        self.sched.pump()
        ids = np.concatenate([f.result() for f in futs])
        if not np.array_equal(ids, np.arange(self.n)):
            raise AssertionError("insert returned unexpected global ids")
        self.segments = [(int(seg.n), int(seg.index.ls),
                          roofline.leaf_bytes(_leaves(seg.index.levels)))
                         for seg in self.index.segments]
        self.delta_rows = int(self.index.stats()["delta_rows"])
        c = self.index.counters
        log(f"load: {time.perf_counter() - t0:.3f} s, {c['flushes']} seals, "
            f"{c['merges']} merges, segments "
            f"{[s[0] for s in self.segments]} + {self.delta_rows} delta rows")
        for i, (rows, ls, level_bytes) in enumerate(self.segments):
            S = self.L - ls
            log(f"segment {i}: {rows} rows, ls={ls}, suffix {S} chars, "
                f"{'packed' if self.b * S <= 32 else 'plane'} geometry, "
                f"{roofline.suffix_row_words(self.L, self.b, ls)} word(s) "
                f"per column, trie levels {level_bytes} B")

    def queries(self, count: int, stream: int = 1) -> np.ndarray:
        t = self.cell.traffic
        return corpus.make_queries(self.db, self.b, count,
                                   float(t["perturbed_share"]),
                                   int(t["flips"]), self.seed, stream)

    def warm_up(self) -> None:
        """Every shape bucket the window can dispatch, one batch of the
        cell's own mix each (so the τ-ladder rungs they reach compile
        here)."""
        from repro.core.search import searcher_cache_info
        from repro.core.segments import dispatch_stats
        from repro.serving.batching import bucket_table
        t0, c0 = time.perf_counter(), len(self.compile_s)
        tr0 = searcher_cache_info()["traces"]
        seen = []
        for bi, bucket in enumerate(bucket_table(self.max_batch)):
            d0 = dispatch_stats()["fused"]
            futs = [self.sched.submit_topk("c", q, self.k) for q in
                    self.queries(bucket, stream=1000 + 16 * bi)]
            self.sched.pump()
            res = [f.result() for f in futs]
            seen.append((bucket, max(x.tau for x in res),
                         dispatch_stats()["fused"] - d0))
        log(f"warm-up batches (bucket, final tau, dispatches): {seen}")
        log(f"warm-up: {time.perf_counter() - t0:.3f} s, "
            f"{searcher_cache_info()['traces'] - tr0} traces, "
            f"{len(self.compile_s) - c0} compiles "
            f"({sum(self.compile_s[c0:]):.3f} s)")
        if self.sched.tracer is not None:
            self.sched.tracer.clear()

    def window(self, qs: np.ndarray, offsets: np.ndarray, seconds: float,
               *, before_first: Optional[Callable[[], None]] = None,
               peaks: Optional[dict] = None) -> Window:
        """Send ``qs[i]`` at ``offsets[i]`` seconds into the window, open
        loop, and wait for every answer (a minute past the close at
        most).  ``before_first`` runs just before the first send."""
        import jax
        from repro.core.search import searcher_cache_info
        from repro.core.segments import dispatch_stats

        count = len(qs)
        t_done = np.full(count, np.nan)
        t_sub = np.full(count, np.nan)
        answers: List[Optional[tuple]] = [None] * count
        errors: List[Optional[str]] = [None] * count
        overflow = np.zeros(count, np.int64)
        done = threading.Event()
        pending = [count]
        lock = threading.Lock()

        def settle():
            with lock:
                pending[0] -= 1
                if pending[0] == 0:
                    done.set()

        def finished(i, f):
            t = time.perf_counter()
            try:
                res = f.result()
                answers[i] = (np.asarray(res.ids, np.int64),
                              np.asarray(res.dists, np.int64))
                overflow[i] = int(res.overflow)
                t_done[i] = t
            except Exception as e:         # noqa: BLE001 - counted failed
                errors[i] = repr(e)
            settle()

        sched = self.sched
        if self.sched.tracer is not None:
            sched.tracer.clear()
        sched.start()
        disp0 = dispatch_stats()
        tr0, c0 = searcher_cache_info()["traces"], len(self.compile_s)
        trace_dir = sync_pc = None
        if self.traced:
            trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
            jax.profiler.start_trace(trace_dir)
            sync_pc = time.perf_counter()
            with jax.profiler.TraceAnnotation(SYNC_MARK):
                pass
        if before_first is not None:
            before_first()
        t_win = time.perf_counter()
        for i in range(count):
            target = t_win + offsets[i]
            while True:
                now = time.perf_counter()
                if now >= target:
                    break
                time.sleep(min(target - now, 0.05))
            t_sub[i] = time.perf_counter()
            try:
                f = sched.submit_topk("c", qs[i], self.k)
            except Exception as e:         # noqa: BLE001 - refused
                errors[i] = repr(e)
                settle()
            else:
                f.add_done_callback(lambda f, i=i: finished(i, f))
        t_close = t_win + seconds
        done.wait(timeout=max(0.0, t_close + LATE_WAIT_S
                              - time.perf_counter()))
        t_last = (float(np.nanmax(t_done)) if np.isfinite(t_done).any()
                  else time.perf_counter())
        if self.traced:
            jax.profiler.stop_trace()
        sched.stop()
        disp = {key: v - disp0.get(key, 0)
                for key, v in dispatch_stats().items()}
        scheduled = t_win + offsets
        win = Window(
            count=count, answered=int(sum(a is not None for a in answers)),
            latency_s=np.where(np.isnan(t_done), t_close + LATE_WAIT_S,
                               t_done) - scheduled,
            answers=answers, errors=errors,
            partial=int((overflow > 0).sum()), late_s=t_sub - scheduled,
            t_win=t_win, t_last=t_last, dispatch=disp,
            traces=searcher_cache_info()["traces"] - tr0,
            compiles=len(self.compile_s) - c0)
        if self.traced:
            read_trace_into(win, sched.tracer, trace_dir, sync_pc, peaks)
            win.bytes_per_dispatch = roofline.dispatch_bytes(
                self.L, self.b, self.segments, self.delta_rows)
        return win

    def peak_bytes(self) -> int:
        return int((self.dev.memory_stats() or {}).get("peak_bytes_in_use",
                                                       0))

    def free_program(self) -> None:
        """Drop the program's state, so the reference has the device."""
        from repro.core.segments import clear_fused_cache
        self.sched = self.index = None
        clear_fused_cache()
        gc.collect()


def read_trace_into(win: Window, tracer, trace_dir: str, sync_pc: float,
                    peaks: Optional[dict]) -> None:
    """Fill ``win`` from the profiler trace in ``trace_dir`` and the
    scheduler's span trees; the trace clock is tied to ``perf_counter``
    by the annotation made at ``sync_pc``."""
    seen = set()
    for root in tracer.roots():
        for ch in root.children:
            if ch.name == "queue_wait":
                win.queue_wait_s.append(ch.dur)
            elif ch.name == "batch" and id(ch) not in seen:
                seen.add(id(ch))
                win.batch_spans.append(ch)
    tr = xplane.read_trace(xplane.find_xplane(trace_dir))
    shutil.rmtree(trace_dir, ignore_errors=True)
    marks = [a for a in tr.annotations if a[0] == SYNC_MARK]
    if not marks:
        raise RuntimeError("the trace holds no clock-sync annotation")
    sync_ns = marks[0][1]

    def pc_to_ns(t: float) -> int:
        return int(sync_ns + (t - sync_pc) * 1e9)
    win.trace, win.pc_to_ns = tr, pc_to_ns
    win.lo_ns = pc_to_ns(win.t_win)
    win.hi_ns = pc_to_ns(max(win.t_last, win.t_win + 1e-3))
    win.peaks = peaks


def describe_window(win: Window, seconds: float, rate: float) -> None:
    log(f"generator: {win.count} requests over {seconds} s at {rate} "
        f"req/s; late by median {np.median(win.late_s) * 1e3:.3f} ms, p99 "
        f"{np.percentile(win.late_s, 99) * 1e3:.3f} ms, max "
        f"{win.late_s.max() * 1e3:.3f} ms")
    log(f"window: {win.traces} traces and {win.compiles} compiles inside "
        f"the window; dispatches {win.dispatch}; {win.partial} answers "
        f"flagged possibly partial (frontier overflow) by the program")
    lat = win.latency_s * 1e3
    log(f"latency over {win.count} requests: mean {lat.mean():.3f} ms, p50 "
        f"{np.percentile(lat, 50):.3f} ms, p95 {np.percentile(lat, 95):.3f}"
        f" ms, max {lat.max():.3f} ms; answered {win.answered}, last answer "
        f"{win.t_last - win.t_win - seconds:+.3f} s after the close")


def compare(db: np.ndarray, b: int, qs: np.ndarray, k: int, answers: list,
            errors: list, *, control: bool = False) -> Dict[str, dict]:
    """Every answer against the reference (or, with ``control``, the
    reference one step down in precision); the checks with limits."""
    from .reference import DeviceReference
    t0 = time.perf_counter()
    want_ids, want_d = DeviceReference(db, b, drop_low_bit=control).topk(qs, k)
    wrong, first = 0, None
    for i, a in enumerate(answers):
        if a is None:
            continue
        if not (np.array_equal(a[0], want_ids[i])
                and np.array_equal(a[1], want_d[i])):
            wrong += 1
            if first is None:
                first = (i, a, want_ids[i], want_d[i])
    log(f"reference: {len(qs)} answers compared in "
        f"{time.perf_counter() - t0:.3f} s"
        + (" (control: characters compared on their high bits)"
           if control else ""))
    if first is not None:
        i, a, wi, wd = first
        log(f"first differing answer: request {i}: ids {a[0].tolist()} "
            f"dists {a[1].tolist()}; reference ids {wi.tolist()} dists "
            f"{wd.tolist()}")
    err = next((e for e in errors if e is not None), None)
    if err is not None:
        log(f"first failed request: {err}")
    unanswered = sum(a is None for a in answers)
    return {"wrong_answers": {"value": wrong, "limit": 0},
            "unanswered": {"value": unanswered, "limit": 0}}


def run(cell: spec.Cell, seed: int, seconds: float, trace: bool,
        t_start: float, *, control: bool = False,
        peaks: Optional[dict] = None) -> dict:
    """One run of the cell, as ``run.py`` reports it."""
    tc = TopkCell(cell, seed, trace=trace)
    rate = float(cell.settings["rate_per_s"])
    count = max(1, int(round(rate * seconds)))
    qs = tc.queries(count)
    offsets = corpus.arrivals(cell.traffic, count, seconds)
    tc.warm_up()
    setup = {}
    win = tc.window(qs, offsets, seconds, peaks=peaks,
                    before_first=lambda: setup.setdefault(
                        "s", time.perf_counter() - t_start))
    describe_window(win, seconds, rate)
    peak = tc.peak_bytes()
    db, b, k = tc.db, tc.b, tc.k
    tc.free_program()
    checks = compare(db, b, qs, k, win.answers, win.errors, control=control)
    lat = win.latency_s * 1e3
    return {"attempted": count, "failed": count - win.answered,
            "e2e": {"topk_mean_ms": float(lat.mean()),
                    "topk_p95_ms": float(np.percentile(lat, 95)),
                    "setup_s": setup["s"]},
            "peak_bytes": peak, "window": win, "checks": checks}
