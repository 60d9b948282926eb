"""One bulk-ingest cell on the served path: set-up, window, check.

Set-up (``setup_s``, held to the cell's ``setup_limit_s``): the corpus
from the seed and a ``Scheduler`` with one empty collection.

Window: the client loads the first ``rows`` rows of the corpus through
``Scheduler.submit_insert`` in chunks of ``chunk_rows``, keeping
``in_flight`` chunks outstanding (a closed loop: a bulk loader that
sends the next chunk once the queue has room), and stops sending at the
close.  The rows are a whole number of ``delta_cap`` blocks, so the
window holds the same seals and merges in every run; ``ingest_rows_s``
is the rows acknowledged over the time from the first send to the last
acknowledgement.

Check: every acknowledgement must carry the chunk's own global ids, and
every acknowledged chunk must be visible to the next read: one row drawn
from the seed out of each chunk, and some random rows, are read back as
top-k queries through the scheduler, and each answer is compared with
the reference over the acknowledged rows.
"""

from __future__ import annotations

import collections
import time
from typing import List, Optional

import numpy as np

from . import corpus, spec
from .drive import (LATE_WAIT_S, SYNC_MARK, Window, compare, compile_events,
                    log, read_trace_into, served_collection)
from .setup_limit import SetupLimit


def run(cell: spec.Cell, seed: int, seconds: float, trace: bool,
        limit: SetupLimit, *, control: bool = False,
        peaks: Optional[dict] = None) -> dict:
    import jax

    cfg, traffic = cell.config, cell.traffic
    n, L, b = int(cfg["n"]), int(cfg["L"]), int(cfg["b"])
    delta_cap = int(cfg["delta_cap"])
    rows = min(n, int(traffic["delta_caps"]) * delta_cap)
    chunk, in_flight = int(traffic["chunk_rows"]), int(traffic["in_flight"])
    dev = jax.devices()[0]
    compile_s = compile_events()

    t0 = time.perf_counter()
    limit.stage("corpus")
    db = corpus.make_corpus(n, L, b, delta_cap, seed)
    log(f"corpus: {cfg['name']} n={n} L={L} b={b} seed={seed} in "
        f"{time.perf_counter() - t0:.3f} s; the window loads {rows} rows "
        f"in chunks of {chunk}, {in_flight} in flight")
    limit.stage("collection")
    sched, index = served_collection(cfg, trace)
    events: list = []
    tap = index.event_hook

    def hook(event, info):
        if event in ("flush", "merge"):
            events.append((time.perf_counter(), event, info))
        if tap is not None:
            tap(event, info)
    index.event_hook = hook

    blocks = corpus.split_blocks(rows, chunk)
    acks: List[Optional[np.ndarray]] = [None] * len(blocks)
    t_ack = np.full(len(blocks), np.nan)
    errors: List[Optional[str]] = [None] * len(blocks)

    def acked(i, f):
        t = time.perf_counter()
        try:
            acks[i] = np.asarray(f.result())
            t_ack[i] = t
        except Exception as e:             # noqa: BLE001 - counted failed
            errors[i] = repr(e)

    sched.start()
    c0 = len(compile_s)
    trace_dir = sync_pc = None
    if trace:
        import tempfile
        trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
        jax.profiler.start_trace(trace_dir)
        sync_pc = time.perf_counter()
        with jax.profiler.TraceAnnotation(SYNC_MARK):
            pass
    t_win = time.perf_counter()
    setup_s = limit.disarm(t_win)
    t_close = t_win + seconds
    outstanding: collections.deque = collections.deque()
    sent = 0
    for i, (lo, hi) in enumerate(blocks):
        while (len(outstanding) >= in_flight
               and time.perf_counter() < t_close):
            try:
                outstanding[0].exception(
                    timeout=t_close - time.perf_counter())
            except TimeoutError:
                break
            outstanding.popleft()
        if len(outstanding) >= in_flight or time.perf_counter() >= t_close:
            break
        f = sched.submit_insert("c", db[lo:hi])
        f.add_done_callback(lambda f, i=i: acked(i, f))
        outstanding.append(f)
        sent += 1
    for f in outstanding:
        try:
            f.exception(timeout=max(0.0, t_close + LATE_WAIT_S
                                    - time.perf_counter()))
        except TimeoutError:               # never acknowledged
            pass
    done = np.isfinite(t_ack)
    t_last = float(np.nanmax(t_ack)) if done.any() else time.perf_counter()
    if trace:
        jax.profiler.stop_trace()
    rows_acked = int(sum(hi - lo for (lo, hi), a in zip(blocks, acks)
                         if a is not None))
    window_compiles = len(compile_s) - c0
    peak = int((dev.memory_stats() or {}).get("peak_bytes_in_use", 0))
    span_s = t_last - t_win
    rate = rows_acked / span_s if span_s > 0 else 0.0
    st = index.stats()
    log(f"window: {sent} of {len(blocks)} chunks sent, "
        f"{int(done.sum())} acknowledged, {rows_acked} rows in "
        f"{span_s:.3f} s ({rate:.1f} rows/s), last acknowledgement "
        f"{t_last - t_close:+.3f} s after the close; "
        f"{sum(e[1] == 'flush' for e in events)} seals, "
        f"{sum(e[1] == 'merge' for e in events)} merges; segments "
        f"{[s[0] for s in st['segments']]} + {st['delta_rows']} delta rows; "
        f"{window_compiles} compiles inside the window "
        f"({sum(compile_s[c0:]):.3f} s)")
    lat = np.where(done, t_ack, t_close + LATE_WAIT_S)[:sent] - t_win
    win = Window(count=sent, answered=int(done.sum()),
                 latency_s=lat, answers=[], errors=errors, partial=0,
                 late_s=np.zeros(len(blocks)), t_win=t_win, t_last=t_last,
                 dispatch={}, traces=0, compiles=window_compiles)
    win.extra.update(rows_acked=rows_acked)
    if trace:
        read_trace_into(win, sched.tracer, trace_dir, sync_pc, peaks)
        sealing = [sp for sp in win.batch_spans
                   if any(sp.ts <= t <= sp.ts + sp.dur for t, _, _ in events)]
        win.extra["seal_merge_s"] = sum(sp.dur for sp in sealing)

    wrong_ids = sum(
        a is not None and not np.array_equal(a, np.arange(lo, hi))
        for (lo, hi), a in zip(blocks, acks))
    # read back: one row of every acknowledged chunk, and random rows
    rng = np.random.default_rng([seed & ((1 << 64) - 1), 7])
    picks = [int(rng.integers(lo, hi)) for (lo, hi), a in zip(blocks, acks)
             if a is not None]
    qs = np.concatenate([
        db[picks].reshape(-1, L),
        rng.integers(0, 1 << b, size=(int(traffic["readback_random"]), L),
                     dtype=np.uint8)])
    k = int(traffic["readback_k"])
    t0 = time.perf_counter()
    futs = [sched.submit_topk("c", q, k) for q in qs]
    answers, read_errors = [], []
    for f in futs:
        try:
            res = f.result(timeout=max(1.0, t0 + 600 - time.perf_counter()))
            answers.append((np.asarray(res.ids, np.int64),
                            np.asarray(res.dists, np.int64)))
            read_errors.append(None)
        except Exception as e:             # noqa: BLE001 - a failed read
            answers.append(None)
            read_errors.append(repr(e))
    sched.stop()
    log(f"read-back: {len(qs)} top-{k} queries ({len(picks)} acknowledged "
        f"rows, {len(qs) - len(picks)} random) in "
        f"{time.perf_counter() - t0:.3f} s")
    del sched, index
    from repro.core.segments import clear_fused_cache
    clear_fused_cache()
    checks = compare(db[:rows_acked], b, qs, k, answers, read_errors,
                     control=control)
    checks = {"wrong_ids": {"value": int(wrong_ids), "limit": 0},
              "unacknowledged": {"value": sent - int(done.sum()),
                                 "limit": 0},
              **checks}
    return {"attempted": sent, "failed": sent - int(done.sum()),
            "e2e": {"ingest_rows_s": rate, "setup_s": setup_s},
            "peak_bytes": peak, "window": win, "checks": checks}
