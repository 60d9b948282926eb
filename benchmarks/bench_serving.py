"""Closed-loop serving-runtime benchmark (DESIGN.md §5).

A threaded ``repro.serving.Scheduler`` fronts one collection preloaded
with a synthetic corpus; C closed-loop clients each submit one request,
wait for its future, and immediately submit the next — the classic
closed-loop load model, so offered load adapts to service rate and the
reported QPS is *sustained*, not offered.  The request mix is
read-heavy with interleaved writes (defaults: 70% topk, 20% search,
5% insert, 5% delete), exercising the read-coalescing + write-fencing
path the scheduler exists for.

Rows:
  * ``serving/<ds>/qps``        — sustained requests/sec over the run
  * ``serving/<ds>/topk_p50``   — end-to-end (queue + exec) ms
  * ``serving/<ds>/topk_p99``
  * ``serving/<ds>/search_p99``
  * ``serving/<ds>/topk_queue_p99`` / ``topk_exec_p99`` — the p99
                                  request *decomposed* from its span
                                  tree (DESIGN.md §11): time queued vs
                                  time in the batch's device dispatch —
                                  where the e2e p99 actually goes
  * ``serving/<ds>/fill``       — batch-fill ratio (coalesced queries /
                                  dispatched bucket rows)
  * ``serving/<ds>/sweep_seg{1,4,16}_p99`` — fixed-corpus segment-count
                                  sweep: end-to-end topk p99 through the
                                  scheduler at 1/4/16 sealed segments —
                                  flat under the fused arena
                                  (DESIGN.md §6; asserted non-smoke)
  * ``serving/<ds>/burst_goodput`` / ``burst_degraded_frac`` /
    ``burst_victim_p99_ratio`` — overload-control rows (DESIGN.md §12)
                                  from the chaos harness's 10× burst +
                                  slow-dispatch-fault scenario
                                  (``tools/overload_smoke.run_burst``):
                                  co-tenant within-deadline goodput,
                                  fraction of victim answers served
                                  degraded, and the victim's p99/p50 —
                                  deadline-bounded, never unbounded

Standalone: ``PYTHONPATH=src python -m benchmarks.bench_serving
[--smoke] [--clients C] [--ops N] [--out BENCH.json]``.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import threading
import time

import numpy as np

from repro.obs import Tracer
from repro.serving import (CollectionConfig, OverloadError, Scheduler,
                           SchedulerConfig)

from . import common
from .common import Csv, cap_n, make_dataset

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent
                       / "tools"))
import overload_smoke  # noqa: E402  (the chaos harness's burst scenario)

# op mix: (name, cumulative probability)
MIX = (("topk", 0.70), ("search", 0.90), ("insert", 0.95), ("delete", 1.0))


def _submit_with_retry(submit):
    """Closed-loop overload handling: back off and re-submit until the
    queue admits the request, so every client iteration completes exactly
    one op (the reported totals stay honest under overload)."""
    while True:
        try:
            return submit()
        except OverloadError:
            time.sleep(0.001)


def _client(sched: Scheduler, docs: np.ndarray, ids_pool: list,
            lock: threading.Lock, rng: np.random.Generator, ops: int,
            k: int, tau: int, errors: list) -> None:
    n = len(docs)
    for _ in range(ops):
        r = rng.random()
        try:
            if r < MIX[0][1]:
                doc = docs[rng.integers(0, n)]
                fut = _submit_with_retry(
                    lambda: sched.submit_topk("bench", doc, k))
            elif r < MIX[1][1]:
                doc = docs[rng.integers(0, n)]
                fut = _submit_with_retry(
                    lambda: sched.submit_search("bench", doc, tau))
            elif r < MIX[2][1]:
                rows = docs[rng.integers(0, n, size=4)]
                fut = _submit_with_retry(
                    lambda: sched.submit_insert("bench", rows))
            else:
                with lock:
                    victim = ids_pool[rng.integers(0, len(ids_pool))]
                fut = _submit_with_retry(
                    lambda: sched.submit_delete("bench", victim))
            res = fut.result(timeout=300)
            if r >= MIX[1][1] and r < MIX[2][1]:     # insert: bank new ids
                with lock:
                    ids_pool.extend(res.tolist())
        except Exception as e:                       # noqa: BLE001
            errors.append(e)
            return


def run(csv: Csv, datasets=("review",), clients: int = 8,
        ops_per_client: int = 40, k: int = 10, tau: int = 2) -> None:
    if common.SMOKE:
        clients, ops_per_client = 4, 6
    for name in datasets:
        cfg, db, _ = make_dataset(name, n=cap_n(1 << 14))
        n = len(db)
        tracer = Tracer(capacity=8192)      # span every request of the run
        sched = Scheduler(config=SchedulerConfig(
            max_batch=max(8, clients), max_queue=4 * clients + 64,
            max_wait_ms=1.0), tracer=tracer)
        sched.create_collection("bench", CollectionConfig(
            L=cfg.L, b=cfg.b, delta_cap=max(256, n // 4)))
        preload = sched.submit_insert("bench", db)
        sched.start()
        ids_pool = list(preload.result(timeout=600).tolist())
        # pre-jit every power-of-two shape bucket the mix can dispatch
        # before timing — first-request compiles never pollute the p99
        sched.warmup(ks=(k,), taus=(tau,))

        lock = threading.Lock()
        errors: list = []
        threads = [
            threading.Thread(target=_client, args=(
                sched, db, ids_pool, lock,
                np.random.default_rng(1000 + c), ops_per_client, k, tau,
                errors))
            for c in range(clients)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        dt = time.perf_counter() - t0
        sched.stop()
        if errors:
            raise errors[0]

        total = clients * ops_per_client
        snap = sched.stats()
        lat = snap["latency"]
        qps = total / dt
        csv.add(f"serving/{name}/qps", dt / total * 1e6,
                f"qps={qps:.0f};clients={clients};ops={total};"
                f"rejected={snap['counters'].get('rejected_total', 0)}")
        # per-op percentiles from the request spans (enqueue -> resolved)
        e2e: dict = {}
        for root in tracer.roots():
            e2e.setdefault(root.args.get("op"), []).append(root.dur)
        for op in ("topk", "search"):
            if op in e2e:
                p50, p99 = (float(np.percentile(e2e[op], q)) * 1e3
                            for q in (50, 99))
                csv.add(f"serving/{name}/{op}_p50", p50 * 1e3,
                        f"p50_ms={p50:.2f}")
                csv.add(f"serving/{name}/{op}_p99", p99 * 1e3,
                        f"p99_ms={p99:.2f}")
        fill = snap["batch_fill_ratio"]
        csv.add(f"serving/{name}/fill", 0.0,
                f"fill={fill:.3f};cache_traces="
                f"{snap['searcher_cache']['traces']}")

        # span-derived decomposition: where the topk p99 goes — queue
        # wait vs device execution (from each request's span tree, not
        # the aggregate windows)
        queue_s, exec_s = [], []
        for root in tracer.roots():
            if root.args.get("op") != "topk":
                continue
            wait = root.find("queue_wait")
            execute = root.find("execute")
            if wait is not None:
                queue_s.append(wait.dur)
            if execute is not None:
                exec_s.append(execute.dur)
        if queue_s and exec_s:
            qp99 = float(np.percentile(np.asarray(queue_s), 99)) * 1e3
            ep99 = float(np.percentile(np.asarray(exec_s), 99)) * 1e3
            csv.add(f"serving/{name}/topk_queue_p99", qp99 * 1e3,
                    f"p99_ms={qp99:.2f};spans={len(queue_s)}")
            csv.add(f"serving/{name}/topk_exec_p99", ep99 * 1e3,
                    f"p99_ms={ep99:.2f};spans={len(exec_s)}")
        if not common.SMOKE:
            # relational sanity: the runtime must actually coalesce —
            # with 8 closed-loop clients the mean read batch must beat 1
            batches = sum(v for kk, v in snap["counters"].items()
                          if kk.startswith("batches_total:"))
            reads = sum(lat[op]["count"] for op in ("topk", "search")
                        if op in lat)
            assert batches < reads, (batches, reads)

        # segment-count sweep: end-to-end read latency through the
        # scheduler must stay flat (not linear) in the collection's
        # sealed segment count — the fused arena's one-dispatch claim
        # observed from the client side
        n_sweep = min(n, cap_n(1 << 12))
        sweep_ops = 8 if common.SMOKE else 24
        sweep_p99 = {}
        for n_seg in (1, 4, 16):
            sw = Scheduler(config=SchedulerConfig(
                max_batch=8, max_queue=1024, max_wait_ms=1.0))
            sw.create_collection("sweep", CollectionConfig(
                L=cfg.L, b=cfg.b, delta_cap=n_sweep + 1, auto_merge=False))
            sidx = sw.registry.get("sweep").index
            chunk = n_sweep // n_seg
            for lo in range(0, n_seg * chunk, chunk):
                sidx.insert(db[lo:lo + chunk])
                sidx.flush()
            for i in range(2):       # warm bucket 1 — the dispatch shape
                f = sw.submit_topk("sweep", db[i], k)
                sw.pump()
                f.result(timeout=600)
            rng = np.random.default_rng(7)
            took = []
            for _ in range(sweep_ops):          # one dispatch per pump
                t1 = time.perf_counter()
                f = sw.submit_topk("sweep",
                                   db[rng.integers(0, n_sweep)], k)
                sw.pump()
                f.result(timeout=600)
                took.append(time.perf_counter() - t1)
            p50, p99 = (float(np.percentile(took, q)) * 1e3
                        for q in (50, 99))
            sweep_p99[n_seg] = p50
            csv.add(f"serving/{name}/sweep_seg{n_seg}_p99", p99 * 1e3,
                    f"segments={n_seg};p50_ms={p50:.2f};rows={n_sweep}")
        if not common.SMOKE:
            # flat, not linear, in n_segments (p50 — the p99 of a short
            # run is a single sample and may catch a ladder escalation)
            assert sweep_p99[16] < 6 * max(sweep_p99[1], 1e-3), sweep_p99

        # overload-control burst scenario (DESIGN.md §12): one tenant
        # fires a 10x open-loop burst under slow-dispatch faults; the
        # chaos harness measures co-tenant goodput, the degraded
        # fraction, and the victim's deadline-bounded tail
        burst_kw = dict(n_docs=1024, burst=120) if common.SMOKE else {}
        res = overload_smoke.run_burst(**burst_kw)
        csv.add(f"serving/{name}/burst_goodput", res["goodput"] * 1e6,
                f"goodput={res['goodput']:.3f};"
                f"cotenant_ops={res['cotenant_total']};"
                f"deadline_exceeded={res['deadline_exceeded']};"
                f"breaker_trips={res['breaker_trips']}")
        csv.add(f"serving/{name}/burst_degraded_frac",
                res["degraded_frac"] * 1e6,
                f"degraded_frac={res['degraded_frac']:.3f};"
                f"stages={','.join(res['degraded_stages']) or 'none'}")
        csv.add(f"serving/{name}/burst_victim_p99_ratio",
                res["victim_p99_ratio"] * 1e6,
                f"p99_over_p50={res['victim_p99_ratio']:.2f};"
                f"p50_ms={res['victim_p50_ms']:.1f};"
                f"p99_ms={res['victim_p99_ms']:.1f}")
        if not common.SMOKE:
            overload_smoke.check_burst(res)     # the CI-enforced SLO


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--clients", type=int, default=8)
    ap.add_argument("--ops", type=int, default=40,
                    help="requests per closed-loop client")
    ap.add_argument("--out", default=None,
                    help="also write machine-readable JSON rows here")
    args = ap.parse_args(argv)
    if args.smoke:
        from . import common
        common.set_smoke()
    csv = Csv()
    csv.header()
    run(csv, clients=args.clients, ops_per_client=args.ops)
    if args.out:
        with open(args.out, "w") as f:
            json.dump({"suite": "serving", "smoke": args.smoke,
                       "rows": csv.records}, f, indent=2)
        print(f"# wrote {args.out}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
