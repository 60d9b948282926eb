#!/usr/bin/env python3
"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs on the machine it is started on and needs as many TPU chips as the
cell asks for; with fewer, or none, it exits non-zero and prints no
result.  ``--trace 0`` reports the cell's end-to-end metrics, ``--trace
1`` its per-layer metrics from a profiler trace of the window.  The last
line of standard output is one JSON object (``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, with ``--trace 1`` also
``breakdown``, and last ``checks``: each number compared with its
limit); the last lines of standard error repeat the checks.

``--control 1`` puts the reference, one step down in precision, in the
program's place for the output check (``reference.py``): such a run
has to come out not correct.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
# the script's own directory is not a place to import from
sys.path[:] = [p for p in sys.path
               if pathlib.Path(p or ".").resolve() != ROOT / "bench"]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import spec  # noqa: E402

CACHE_DIR = ROOT / ".jax_cache"


def _parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def use_compile_cache() -> str:
    """JAX's persistent compilation cache at a fixed path in the
    checkout, unless the environment names one; every program cached."""
    import jax
    cache = os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                                  str(CACHE_DIR))
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return cache


def main(argv=None, *, root: pathlib.Path = ROOT, require_chip: bool = True,
         compile_cache: bool = True, peaks=None,
         t_start: float = T_START) -> int:
    """The command; the keywords let a test drive a run without the
    chip (``require_chip=False`` with ``peaks`` given for a traced run)
    and without touching the persistent compile cache."""
    args = _parse(argv)
    try:
        cell = spec.load_cell(args.workload, root)
    except (KeyError, FileNotFoundError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        import jax
        import repro  # noqa: F401 - the system under test must be here
    except ImportError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    devices = jax.devices()
    if require_chip:
        if devices[0].platform != "tpu" or len(devices) < cell.chips:
            print(f"bench: the cell needs {cell.chips} TPU chip(s); JAX "
                  f"found {len(devices)} {devices[0].platform} device(s)",
                  file=sys.stderr)
            return 3
        from bench import roofline
        peaks = roofline.peaks_for(devices[0].device_kind)
    cache = use_compile_cache() if compile_cache else "off"
    print(f"bench: {cell.name} on {len(devices)} {devices[0].device_kind} "
          f"device(s); compile cache {cache}", file=sys.stderr)

    from bench import drive, ingest
    kinds = {"topk": drive.run, "ingest": ingest.run}
    out = kinds[cell.traffic["kind"]](
        cell, args.seed, args.seconds, bool(args.trace), t_start,
        control=bool(args.control), peaks=peaks)
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": out["peak_bytes"]}
    metrics = {}
    breakdown = None
    if args.trace:
        win = out["window"]
        device["busy_s"] = win.busy_s()
        device["window_s"] = win.window_s()
        for m in cell.per_layer:
            value = spec.metric_reader(m["name"], root)(win)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        from bench import xplane
        ops = [op for evs in win.trace.ops.values() for op in evs
               if op[3] > win.lo_ns and op[2] < win.hi_ns]
        busy = [iv for ivs in win.device_intervals() for iv in ivs]
        breakdown = {
            "device_ops": xplane.top_ops(ops),
            "idle_gaps": xplane.idle_gaps_by_host(
                busy, win.lo_ns, win.hi_ns, win.host_spans())}
    else:
        for m in cell.end_to_end:
            if m["name"] in out["e2e"]:
                metrics[m["name"]] = {"value": out["e2e"][m["name"]],
                                      "unit": m["unit"]}
    checks = out["checks"]
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    line = {"correct": correct, "attempted": out["attempted"],
            "failed": out["failed"], "metrics": metrics,
            "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["checks"] = checks
    for name, c in checks.items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
