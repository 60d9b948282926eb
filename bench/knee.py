#!/usr/bin/env python3
"""Find a top-k cell's knee: the highest offered rate whose answers keep
up with it, with no growing backlog.  Not run by the benchmark itself:
run once when a cell's rate is set, on the chip the cell names.

    python3 bench/knee.py --workload <cell> --seed <n> --seconds <s> \
        --rates 6,8,10,12

Sets the cell up once, then for each rate sends an open-loop window of
the cell's mix (``run.py``'s generator) and prints one JSON line: the
offered and completed rates, the latency mean, median and 95th
percentile, the median latency of the window's last quarter of requests
over its first quarter (a backlog that grows all through the window
shows as a ratio well above 1), and the requests still unanswered at
the close.
A rate keeps up when the completed rate is within 3 % of the offered
one, at most two batches (``2 * max_batch`` requests) are unanswered at
the close, and the last quarter's median latency is at most
``GROWTH_MAX`` times the first quarter's.  The knee is the highest rate
that keeps up where every lower rate swept keeps up too.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:] = [p for p in sys.path
               if pathlib.Path(p or ".").resolve() != ROOT / "bench"]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

import numpy as np  # noqa: E402

from bench import corpus, drive, run, setup_limit, spec  # noqa: E402

# last quarter's median latency over the first quarter's, above which
# the backlog counts as growing
GROWTH_MAX = 1.5


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    import jax
    if jax.devices()[0].platform != "tpu":
        print("knee: JAX found no TPU", file=sys.stderr)
        return 3
    run.use_compile_cache()
    limit = setup_limit.SetupLimit(cell.name, cell.setup_limit_s, T_START)
    tc = drive.TopkCell(cell, args.seed, limit)
    tc.warm_up()
    drive.log(f"set-up: {limit.disarm():.3f} s")
    rates = [float(r) for r in args.rates.split(",")]
    knee = 0                            # rates[:knee] all keep up
    for j, rate in enumerate(rates):
        count = int(round(rate * args.seconds))
        qs = tc.queries(count, stream=10 + j)
        offsets = corpus.arrivals(cell.traffic, count, args.seconds)
        win = tc.window(qs, offsets, args.seconds)
        drive.describe_window(win, args.seconds, rate)
        lat = win.latency_s
        q = max(1, count // 4)
        growth = float(np.median(lat[-q:]) / np.median(lat[:q]))
        t_answers = win.t_last - win.t_win
        done_rate = win.answered / max(t_answers, args.seconds)
        # requests sent but not answered when the window closed
        backlog = int(np.sum(offsets + lat > args.seconds))
        ok = (done_rate >= 0.97 * rate and backlog <= 2 * tc.max_batch
              and growth <= GROWTH_MAX)
        if ok and knee == j:
            knee = j + 1
        print(json.dumps({
            "rate_per_s": rate, "requests": count,
            "answered": win.answered, "completed_per_s": done_rate,
            "mean_ms": float(lat.mean() * 1e3),
            "p50_ms": float(np.percentile(lat, 50) * 1e3),
            "p95_ms": float(np.percentile(lat, 95) * 1e3),
            "last_over_first_quarter": growth, "backlog_at_close": backlog,
            "dispatches": win.dispatch, "traces_in_window": win.traces,
            "keeps_up": ok}), flush=True)
    print(json.dumps({"knee_per_s": rates[knee - 1] if knee else None}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
