"""Roofline table assembler: reads the dry-run JSON cache and renders the
per-(arch x shape x mesh) three-term table for EXPERIMENTS.md §Roofline,
plus the analytic arithmetic-intensity model of the query-tiled verify
kernel — the "why" behind BLOCK_M batching."""

from __future__ import annotations

import glob
import json
import os
from typing import Dict, List, Optional

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "results", "dryrun")


# ---------------------------------------------------------------------------
# verify-kernel arithmetic intensity as a function of BLOCK_M
# ---------------------------------------------------------------------------

def verify_intensity(block_m: int, block_n: int = 2048, b: int = 4,
                     W: int = 1) -> Dict[str, float]:
    """Int-ops and HBM bytes of one (BLOCK_M, BLOCK_N) grid cell of
    ``sparse_verify_batch_pallas``.

    Bytes: the (b, W, BLOCK_N) db block is loaded ONCE per cell and
    amortized over BLOCK_M queries; the query tile, the base-distance
    plane and the one output (distance) plane scale with BLOCK_M.  Ops
    per (query, lane): b XORs + (b-1) ORs over W words, W popcounts,
    (W-1)+1 adds (word sum + base add), 1 min (the survival compare runs
    outside the kernel, fused into its consumer).  At BLOCK_M=1 this is
    the original ~1.5 int-ops/byte memory-bound scan; intensity grows
    ~linearly with BLOCK_M until the per-query planes dominate the byte
    count."""
    db_bytes = b * W * block_n * 4
    q_bytes = b * W * block_m * 4
    plane_bytes = block_m * block_n * 4          # base in, dist out
    bytes_total = db_bytes + q_bytes + 2 * plane_bytes
    ops_per_pair = (b * W) + (b - 1) * W + W + W + 1
    ops_total = block_m * block_n * ops_per_pair
    return {"ops": float(ops_total), "bytes": float(bytes_total),
            "intensity": ops_total / bytes_total,
            "db_streams_per_batch": 1.0 / block_m}


def render_intensity_table(block_ms=(1, 2, 4, 8, 16, 32, 64),
                           block_n: int = 2048, b: int = 4,
                           W: int = 1) -> str:
    head = (f"| BLOCK_M | int-ops/cell | HBM bytes/cell | intensity "
            f"(ops/byte) | db streams per m queries |\n|---|---|---|---|---|")
    rows = []
    for bm in block_ms:
        r = verify_intensity(bm, block_n=block_n, b=b, W=W)
        rows.append(f"| {bm} | {r['ops']:.0f} | {r['bytes']:.0f} | "
                    f"{r['intensity']:.2f} | m/{bm} |")
    return "\n".join([head] + rows)


def load_records(results_dir: str = RESULTS_DIR) -> List[dict]:
    records = []
    for path in sorted(glob.glob(os.path.join(results_dir, "*.json"))):
        with open(path) as f:
            records.append(json.load(f))
    return records


def fmt_row(r: dict) -> str:
    if r.get("status") != "ok":
        return (f"| {r['arch']} | {r['shape']} | {r.get('mesh','?')} | "
                f"ERROR: {r.get('error','')[:60]} | | | | | |")
    roof = r["roofline"]
    mem = r.get("memory", {})
    return ("| {arch} | {shape} | {mesh} | {tc:.4f} | {tm:.4f} | {tcoll:.4f} "
            "| {bn} | {uf:.2f} | {gb:.1f} |").format(
        arch=r["arch"], shape=r["shape"], mesh=r["mesh"],
        tc=roof["t_compute_s"], tm=roof["t_memory_s"],
        tcoll=roof["t_collective_s"], bn=roof["bottleneck"],
        uf=roof.get("useful_flops_ratio", 0.0),
        gb=mem.get("total_bytes", 0) / 1e9)


def render_table(records: List[dict], mesh: Optional[str] = None) -> str:
    head = ("| arch | shape | mesh | T_comp (s) | T_mem (s) | T_coll (s) "
            "| bottleneck | useful-FLOPs | bytes/dev (GB) |\n"
            "|---|---|---|---|---|---|---|---|---|")
    rows = [fmt_row(r) for r in records
            if mesh is None or r.get("mesh") == mesh]
    return "\n".join([head] + rows)


def run(csv=None) -> None:
    records = load_records()
    ok = [r for r in records if r.get("status") == "ok"]
    err = [r for r in records if r.get("status") != "ok"]
    print(render_table(records))
    print(f"\n{len(ok)} ok, {len(err)} errors")
    print("\n# verify-kernel arithmetic intensity vs BLOCK_M "
          "(b=4, W=1, BLOCK_N=2048):")
    print(render_intensity_table())
    if csv is not None:
        for r in ok:
            roof = r["roofline"]
            csv.add(f"roofline/{r['mesh']}/{r['arch']}/{r['shape']}", 0.0,
                    f"Tc={roof['t_compute_s']:.4f};Tm={roof['t_memory_s']:.4f};"
                    f"Tcoll={roof['t_collective_s']:.4f};"
                    f"bottleneck={roof['bottleneck']}")
        base = verify_intensity(1)["intensity"]
        for bm in (1, 8, 64):
            r = verify_intensity(bm)
            csv.add(f"roofline/verify_intensity/bm{bm}", 0.0,
                    f"ops_per_byte={r['intensity']:.2f};"
                    f"gain_vs_bm1={r['intensity'] / base:.2f}x;"
                    f"db_streams=m/{bm}")
        # intensity must grow with the query tile — the why of the kernel
        # (saturates near ops/8-bytes once the per-query base/dist
        # planes dominate; the db-stream term keeps falling as m/BLOCK_M)
        assert (verify_intensity(8)["intensity"]
                > 1.8 * verify_intensity(1)["intensity"])


if __name__ == "__main__":
    run()
