#!/usr/bin/env python3
"""Which span the scheduler's worker had open when the open-loop
generator sent a request late.

    python3 bench/late_sends.py --workload <top-k cell> --seed <n> --seconds <s>

Sets the cell up as ``run.py`` does, with the program's span tracer on
and the profiler off, runs one window, and logs to standard error, for
every send more than 40 ms late, the innermost span open on the worker
thread at the send's scheduled time and at the time it went out.  For
comparison it also counts the innermost span at the scheduled time of
every send: a span that holds the late sends far more often than it
holds sends at all is what delays the generator.  The last line of
standard output is one JSON object with those counts.
"""

from __future__ import annotations

import argparse
import collections
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:] = [p for p in sys.path
               if pathlib.Path(p or ".").resolve() != ROOT / "bench"]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

LATE_S = 0.040


def worker_spans(tracer) -> list:
    """Every span under the window's batches as (path, start, end) on
    ``time.perf_counter``'s clock."""
    out, seen = [], set()

    def walk(sp, path):
        path = f"{path}/{sp.name}" if path else sp.name
        out.append((path, sp.ts, sp.ts + sp.dur))
        for ch in sp.children:
            walk(ch, path)
    for root in tracer.roots():
        batch = next((c for c in root.children if c.name == "batch"), None)
        if batch is not None and id(batch) not in seen:
            seen.add(id(batch))
            walk(batch, "")
    return out


def innermost(spans, t: float) -> str:
    best = None
    for path, s, e in spans:
        if s <= t < e and (best is None or e - s < best[0]):
            best = (e - s, path)
    return "no batch open" if best is None else best[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    import jax
    from bench import corpus, drive, run, spec
    cell = spec.load_cell(args.workload, ROOT)
    run.use_compile_cache()
    tc = drive.TopkCell(cell, args.seed, trace=True)
    tc.traced = False                  # spans in the ring, no profiler
    rate = float(cell.settings["rate_per_s"])
    count = max(1, int(round(rate * args.seconds)))
    qs = tc.queries(count)
    offsets = corpus.arrivals(cell.traffic, count, args.seconds)
    tc.warm_up()
    win = tc.window(qs, offsets, args.seconds)
    drive.describe_window(win, args.seconds, rate)
    spans = worker_spans(tc.sched.tracer)
    scheduled = win.t_win + offsets
    every = collections.Counter(innermost(spans, t) for t in scheduled)
    late = collections.Counter()
    for i in range(count):
        if win.late_s[i] <= LATE_S:
            continue
        at = innermost(spans, scheduled[i])
        late[at] += 1
        drive.log(f"late send {i}: scheduled at +{offsets[i]:.3f} s, "
                  f"{win.late_s[i] * 1e3:.3f} ms late; worker at the "
                  f"scheduled time: {at}; when it went out: "
                  f"{innermost(spans, scheduled[i] + win.late_s[i])}")
    drive.log(f"sends more than {LATE_S * 1e3:.0f} ms late: "
              f"{sum(late.values())} of {count}; innermost worker span "
              f"at the scheduled time, late sends: {dict(late)}; every "
              f"send: {dict(every)}")
    dev = jax.devices()[0]
    print(json.dumps({"device": {"platform": dev.platform,
                                 "kind": dev.device_kind},
                      "sends": count, "late": dict(late),
                      "every": dict(every),
                      "late_ms": sorted(float(x) * 1e3 for x in win.late_s
                                        if x > LATE_S)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
