#!/usr/bin/env python3
"""Chip smoke: serve the paper's review corpus at its published size on
one TPU, through the entry points a user calls, and check every answer.

    python chip_smoke.py [--seed 0] [--n ROWS] [--cpu-rehearsal]

Generates ``PAPER_DATASETS["review"]`` from the seed (n = 12,886,488
sketches, L = 16, b = 2 — synthetic rows of the paper's shape), loads it
through ``Scheduler.submit_insert`` (a few seals and size-tiered merges
of the default ``layout="suffix"`` column store), warms every shape
bucket, and serves individual top-k (k = 10) and range (τ = 2) requests
that the scheduler coalesces into shared dispatches — once on the loaded
corpus, once after an insert/delete round that puts answers in the delta
buffer and tombstones sealed ones.  Every answer is compared with a NumPy
brute-force Hamming scan over the same live rows (top-k on (distance,
id) ascending, range on ids and distances).

Any mismatch or error exits non-zero, as does a run where JAX finds no
TPU (``--cpu-rehearsal`` lifts that check, for a small ``--n`` on the
CPU in interpret mode).  The timings printed are smoke readings, not a
benchmark.  The last line of standard output is the JSON contract line
``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

K = 10                 # neighbours per top-k request
TAU = 2                # range-search radius
MAX_BATCH = 16         # scheduler batch limit: (m, n) int32 planes at
#                        published n cost ~52 MB per query row
N_TOPK = 32            # top-k requests per round: two m = 16 batches
N_SEARCH = 4           # range requests per round: one m = 4 batch
N_INSERT = 1024        # rows inserted between the rounds
N_DELETE = 300         # sealed ids deleted between the rounds
BIG = 1 << 20          # the index's "no result" distance


def log(msg: str) -> None:
    print(msg, flush=True)


class BruteForce:
    """The reference: every row ever inserted packed into one uint64 word
    (b bits per character), and a liveness mask over global ids — plain
    NumPy, independent of ``repro.core``."""

    def __init__(self, rows: np.ndarray, b: int):
        self.b, self.L = b, rows.shape[1]
        assert self.L * b <= 64
        self.low = np.uint64(sum(1 << (b * j) for j in range(self.L)))
        self.words = self.pack(rows)
        self.live = np.ones(len(rows), bool)

    def pack(self, rows: np.ndarray) -> np.ndarray:
        words = np.zeros(len(rows), np.uint64)
        for j in range(self.L):
            words |= rows[:, j].astype(np.uint64) << np.uint64(self.b * j)
        return words

    def insert(self, rows: np.ndarray) -> None:
        self.words = np.concatenate([self.words, self.pack(rows)])
        self.live = np.concatenate([self.live, np.ones(len(rows), bool)])

    def delete(self, ids: np.ndarray) -> None:
        self.live[ids] = False

    def dists(self, q: np.ndarray) -> np.ndarray:
        """(n_ids,) int32 Hamming distance of every row to ``q``, BIG on
        deleted rows."""
        x = self.words ^ self.pack(q[None])[0]
        diff = x
        for i in range(1, self.b):
            diff = diff | (x >> np.uint64(i))
        d = np.bitwise_count(diff & self.low).astype(np.int32)
        return np.where(self.live, d, BIG)

    def topk(self, q: np.ndarray, k: int):
        d = self.dists(q)
        kk = min(k, int(self.live.sum()))
        kth = np.partition(d, kk - 1)[kk - 1]
        cand = np.flatnonzero(d <= kth)
        top = cand[np.lexsort((cand, d[cand]))][:kk]
        ids = np.full(k, -1, np.int64)
        ds = np.full(k, BIG, np.int64)
        ids[:kk], ds[:kk] = top, d[top]
        return ids, ds

    def search(self, q: np.ndarray, tau: int):
        d = self.dists(q)
        hit = np.flatnonzero(d <= tau)
        return hit, d[hit]


def check_round(sched, ref: BruteForce, qs_topk, qs_search, label: str):
    """Submit every request individually, drain the scheduler, compare
    with the brute force; returns (seconds, compiles) of the round."""
    from repro.core.search import searcher_cache_info
    traces0 = searcher_cache_info()["traces"]
    t0 = time.perf_counter()
    topk = [sched.submit_topk("review", q, K) for q in qs_topk]
    search = [sched.submit_search("review", q, TAU) for q in qs_search]
    sched.pump()
    topk = [f.result() for f in topk]
    search = [f.result() for f in search]
    dt = time.perf_counter() - t0
    for i, (q, res) in enumerate(zip(qs_topk, topk)):
        ids, ds = ref.topk(q, K)
        got_ids = np.asarray(res.ids, np.int64)
        got_ds = np.asarray(res.dists, np.int64)
        if not (np.array_equal(got_ids, ids) and np.array_equal(got_ds, ds)):
            raise AssertionError(
                f"{label}: top-k request {i} differs from the brute force:\n"
                f"  index ids {got_ids} dists {got_ds}\n"
                f"  brute ids {ids} dists {ds}")
    for i, (q, res) in enumerate(zip(qs_search, search)):
        ids, ds = ref.search(q, TAU)
        got_ids = np.flatnonzero(np.asarray(res.mask))
        got_ds = np.asarray(res.dist)[got_ids]
        if not (np.array_equal(got_ids, ids) and np.array_equal(got_ds, ds)):
            raise AssertionError(
                f"{label}: range request {i} differs from the brute force:\n"
                f"  index ids {got_ids[:20]} dists {got_ds[:20]}\n"
                f"  brute ids {ids[:20]} dists {ds[:20]}")
    hits = [len(ref.search(q, TAU)[0]) for q in qs_search]
    log(f"{label}: {len(topk)} top-k (k={K}, tau*={topk[0].tau}) and "
        f"{len(search)} range (tau={TAU}, hits {hits}) answers equal the "
        f"brute force")
    return dt, searcher_cache_info()["traces"] - traces0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--n", type=int, default=None,
                    help="corpus rows (default: the paper's 12,886,488)")
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="run on whatever backend JAX finds (CPU: Pallas "
                         "in interpret mode) — a rehearsal at small --n, "
                         "never a chip result")
    args = ap.parse_args(argv)

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax import monitoring

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu" and not args.cpu_rehearsal:
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r})",
              file=sys.stderr)
        return 2

    from repro.configs.registry import PAPER_DATASETS
    from repro.core.search import searcher_cache_info
    from repro.core.segments import dispatch_stats
    from repro.kernels import ops
    from repro.launch.compile_cache import use_compile_cache
    from repro.serving import CollectionConfig, Scheduler, SchedulerConfig

    cache_dir = use_compile_cache()
    log(f"device_kind: {dev.device_kind} (platform {dev.platform}, "
        f"{len(devices)} devices); compile cache {cache_dir}")

    compile_s = []
    monitoring.register_event_duration_secs_listener(
        lambda name, secs, **kw: compile_s.append(secs)
        if name == "/jax/core/compile/backend_compile_duration" else None)

    cfg = PAPER_DATASETS["review"]
    n = args.n or cfg.n
    delta_cap = 1 << ((n // 3).bit_length() - 1)   # ~3 seals + a delta
    rng = np.random.default_rng(args.seed)
    t0 = time.perf_counter()
    db = rng.integers(0, 1 << cfg.b, size=(n, cfg.L), dtype=np.uint8)
    ref = BruteForce(db, cfg.b)
    log(f"corpus: review n={n} L={cfg.L} b={cfg.b} seed={args.seed} "
        f"generated in {time.perf_counter() - t0:.3f} s")

    sched = Scheduler(config=SchedulerConfig(max_batch=MAX_BATCH))
    coll = sched.create_collection("review", CollectionConfig(
        L=cfg.L, b=cfg.b, delta_cap=delta_cap))
    index = coll.index
    t0 = time.perf_counter()
    chunk = max(1, delta_cap // 4)
    futs = [sched.submit_insert("review", db[lo:lo + chunk])
            for lo in range(0, n, chunk)]
    sched.pump()
    ids = np.concatenate([f.result() for f in futs])
    if not np.array_equal(ids, np.arange(n)):
        raise AssertionError("insert returned unexpected global ids")
    load_s = time.perf_counter() - t0
    st = index.stats()
    log(f"load: {load_s:.3f} s, {index.counters['flushes']} seals, "
        f"{index.counters['merges']} merges -> segments "
        f"{[seg.n for seg in index.segments]} + {st['delta_rows']} delta "
        f"rows (delta_cap {delta_cap}, layout {index.layout})")

    half = N_TOPK // 2
    qs_topk = np.concatenate([
        db[rng.integers(0, n, half)],
        rng.integers(0, 1 << cfg.b, size=(N_TOPK - half, cfg.L),
                     dtype=np.uint8)])
    qs_search = np.concatenate([
        db[rng.integers(0, n, N_SEARCH // 2)],
        rng.integers(0, 1 << cfg.b, size=(N_SEARCH - N_SEARCH // 2, cfg.L),
                     dtype=np.uint8)])

    c0 = sum(compile_s)
    t0 = time.perf_counter()
    first = sched.submit_topk("review", qs_topk[-1], K)
    sched.pump()
    first.result()
    log(f"first query (m=1 bucket, store upload + compile): "
        f"{time.perf_counter() - t0:.3f} s, of which compile "
        f"{sum(compile_s) - c0:.3f} s")

    c0 = sum(compile_s)
    t0 = time.perf_counter()
    w = sched.warmup(ks=(K,), taus=(TAU,))
    log(f"warmup: {w['calls']} calls over {w['buckets']} buckets, "
        f"{w['traces']} traces, {time.perf_counter() - t0:.3f} s "
        f"(compile {sum(compile_s) - c0:.3f} s)")

    dt, traces = check_round(sched, ref, qs_topk, qs_search, "loaded corpus")
    log(f"loaded corpus round: {dt:.3f} s, {traces} traces")

    # writes: rows equal to query rows (new ids tie at distance 0 with
    # the originals, from the delta buffer) and random rows; then
    # tombstone sealed ids, starting with the corpus queries' own rows
    new_rows = np.concatenate([
        np.repeat(qs_topk[:4], 4, axis=0),
        rng.integers(0, 1 << cfg.b, size=(N_INSERT - 16, cfg.L),
                     dtype=np.uint8)])
    sealed = sum(seg.n for seg in index.segments)
    own = [int(np.flatnonzero(ref.dists(q) == 0)[0]) for q in qs_topk[4:8]]
    doomed = np.unique(np.concatenate([
        np.array(own, np.int64), rng.integers(0, sealed, N_DELETE - len(own))]))
    ins = sched.submit_insert("review", new_rows)
    rm = sched.submit_delete("review", doomed)
    sched.pump()
    ref.insert(new_rows)
    ref.delete(doomed)
    log(f"writes: inserted {len(ins.result())} rows into the delta buffer "
        f"({index.stats()['delta_rows']} delta rows), deleted "
        f"{rm.result()} of {len(doomed)} sealed ids")

    dt, traces = check_round(sched, ref, qs_topk, qs_search,
                             "after insert/delete")
    log(f"after insert/delete round: {dt:.3f} s, {traces} traces")

    t0 = time.perf_counter()
    futs = [sched.submit_topk("review", q, K) for q in qs_topk]
    sched.pump()
    for f in futs:
        f.result()
    warm_ms = (time.perf_counter() - t0) / len(futs) * 1e3
    log(f"warm top-k: {warm_ms:.3f} ms per request over {len(futs)} "
        f"requests in m={MAX_BATCH} batches (smoke reading, not a "
        f"benchmark)")

    kstats = ops.kernel_stats()
    log(f"kernel_stats: {kstats}")
    if kstats.get("sparse_verify_arena_packed", 0) == 0 or any(
            key.startswith("sparse_verify_arena") and key.endswith(":ref")
            for key in kstats):
        raise AssertionError("the sealed-column verify did not run on its "
                             "kernel path")
    log(f"searcher_cache_info: {searcher_cache_info()}")
    log(f"dispatch_stats: {dispatch_stats()}")
    log(f"compiles: {len(compile_s)}, {sum(compile_s):.3f} s in the "
        f"backend compiler")
    mem = dev.memory_stats() or {}
    log(f"peak_bytes_in_use: {mem.get('peak_bytes_in_use', 'not reported')}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
