"""Reduction of a JAX profiler trace (``*.xplane.pb``) to device numbers.

``read_trace`` pulls out the device operations and the host annotations
of one trace; the functions below it work on plain interval lists, so
that the arithmetic is tested apart from the file format:

* busy time is the union of the intervals in which an operation ran on
  a device, averaged over the devices;
* the idle share is 1 - busy / traced window;
* each idle gap is named by the innermost host span open at its middle.

On a TPU the operations are the events of each ``/device:TPU:n`` plane's
"XLA Ops" line, and the programs those of its "XLA Modules" line.  A CPU
trace has no such plane: its operations are the host events that carry
an ``hlo_op`` stat, which is enough to test the code and never gives a
device metric.  A device plane is one with an "XLA Ops" line: a process
that has loaded the TPU library without running on it (a compile for a
described chip) also writes an empty ``/device:CUSTOM:...`` plane.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

Interval = Tuple[int, int]                 # [start_ns, end_ns)


@dataclasses.dataclass
class Trace:
    ops: Dict[str, List[Tuple[str, str, int, int]]]   # device -> (op, module, start, end)
    modules: Dict[str, List[Tuple[str, int, int]]]    # device -> (module, start, end)
    annotations: List[Tuple[str, int, int]]           # host TraceAnnotations


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def _stats(event) -> dict:
    try:
        return dict(event.stats)
    except Exception:                      # noqa: BLE001 - stat decoding
        return {}


def read_trace(path: str, annotation_prefix: str = "bench.") -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops: Dict[str, list] = defaultdict(list)
    modules: Dict[str, list] = defaultdict(list)
    annotations: list = []
    device_planes = [p for p in pd.planes if p.name.startswith("/device:")
                     and any(line.name == "XLA Ops" for line in p.lines)]
    for plane in device_planes:
        for line in plane.lines:
            if line.name == "XLA Ops":
                for e in line.events:
                    st = _stats(e)
                    ops[plane.name].append(
                        (e.name, str(st.get("hlo_module", "")),
                         int(e.start_ns), int(e.end_ns)))
            elif line.name == "XLA Modules":
                for e in line.events:
                    modules[plane.name].append(
                        (e.name, int(e.start_ns), int(e.end_ns)))
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(annotation_prefix):
                    annotations.append(
                        (e.name, int(e.start_ns), int(e.end_ns)))
                elif not device_planes:
                    st = _stats(e)
                    if "hlo_op" in st and e.duration_ns > 0:
                        dev = f"/host-device:{st.get('device_ordinal', 0)}"
                        ops[dev].append((e.name, str(st.get("hlo_module", "")),
                                         int(e.start_ns), int(e.end_ns)))
    if not device_planes:
        # a CPU trace: one module interval per (module, run) from its ops
        for dev, evs in ops.items():
            spans: Dict[str, List[int]] = {}
            for _, mod, s, e in evs:
                lo_hi = spans.setdefault(mod, [s, e])
                lo_hi[0], lo_hi[1] = min(lo_hi[0], s), max(lo_hi[1], e)
            modules[dev] = [(m, s, e) for m, (s, e) in spans.items()]
    return Trace(ops=dict(ops), modules=dict(modules),
                 annotations=annotations)


def clip(intervals: Sequence[Interval], lo: int, hi: int) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def union(intervals: Sequence[Interval]) -> List[Interval]:
    """Sorted, disjoint cover of the intervals."""
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_ns(intervals: Sequence[Interval]) -> int:
    return sum(e - s for s, e in union(intervals))


def gaps(intervals: Sequence[Interval], lo: int, hi: int) -> List[Interval]:
    """The idle intervals of [lo, hi) between the busy ones."""
    out, t = [], lo
    for s, e in union(clip(intervals, lo, hi)):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def name_gap(gap: Interval, spans: Sequence[Tuple[str, int, int]],
             default: str = "no batch open") -> str:
    """The innermost (shortest) span that holds the gap's middle."""
    mid = (gap[0] + gap[1]) // 2
    best: Optional[Tuple[int, str]] = None
    for name, s, e in spans:
        if s <= mid < e and (best is None or e - s < best[0]):
            best = (e - s, name)
    return default if best is None else best[1]


def idle_gaps_by_host(intervals: Sequence[Interval], lo: int, hi: int,
                      spans: Sequence[Tuple[str, int, int]],
                      top: int = 10) -> List[List[object]]:
    """The ``top`` longest idle gaps as [host span, seconds]."""
    gs = sorted(gaps(intervals, lo, hi), key=lambda g: g[0] - g[1])[:top]
    return [[name_gap(g, spans), (g[1] - g[0]) / 1e9] for g in gs]


def short_name(name: str) -> str:
    """A TPU op event is named by its HLO text: keep the instruction's
    name and result shape, without layouts."""
    if " = " not in name:
        return name
    head, rest = name.split(" = ", 1)
    shape = re.sub(r"\{[^}]*\}", "", rest.split(" ", 1)[0])
    if shape.startswith("("):
        shape = "(tuple)"
    return f"{head.lstrip('%')} {shape}"[:120]


def self_times(ops: Sequence[Tuple[str, str, int, int]]) -> List[Tuple[str, int]]:
    """(name, self ns) per op: its duration less that of the ops nested
    in it (a loop holds its body's ops on the same line)."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i][2], -ops[i][3]))
    own = [ops[i][3] - ops[i][2] for i in range(len(ops))]
    stack: List[int] = []
    for i in order:
        s, e = ops[i][2], ops[i][3]
        while stack and ops[stack[-1]][3] <= s:
            stack.pop()
        if stack and e <= ops[stack[-1]][3]:
            own[stack[-1]] -= e - s
        stack.append(i)
    return [(ops[i][0], own[i]) for i in range(len(ops))]


def top_ops(ops: Sequence[Tuple[str, str, int, int]],
            top: int = 10) -> List[List[object]]:
    """The ``top`` operations by summed device self time, as
    ["module/op", seconds]."""
    mods = {op[0]: op[1] for op in ops}
    tot: Dict[str, int] = defaultdict(int)
    for name, t in self_times(ops):
        mod = mods[name]
        short = short_name(name)
        tot[f"{mod}/{short}" if mod else short] += t
    ranked = sorted(tot.items(), key=lambda kv: -kv[1])[:top]
    return [[k, v / 1e9] for k, v in ranked]
