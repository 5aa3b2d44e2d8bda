"""Roofline analysis over dry-run artifacts.

Hardware model (TPU v5e-class, per chip):
    peak bf16 compute: 197 TFLOP/s
    HBM bandwidth:     819 GB/s
    ICI link bw:       ~50 GB/s  (per-link; scalar broadcast rides this)

Terms per (arch × shape × mesh) cell, per MGD step (or serve step):
    compute    = global_FLOPs / (chips × peak)
    memory     = global_bytes / (chips × HBM_bw)
    collective = per-device wire bytes / link_bw
                 (per-device HLO × chips / (chips × link_bw) — identical)

FLOPs/bytes are the scan-aware jaxpr costs (launch/jaxpr_cost.py) — XLA's
cost_analysis counts loop bodies once and is reported alongside for
reference only.  Bytes are a streaming estimate (dot/conv operands +
results): fusion can beat it, gathers can exceed it; treat as ±2×.
"""
from __future__ import annotations

import argparse
import glob
import json
import math
import os
from typing import Dict, List

# Unkeyed peaks of one chip kind, for the dry-run model only.  Nothing that
# measures on a chip may read them: a measured roofline share takes its
# peaks from a table keyed by the device's ``device_kind``.
PEAK_FLOPS = 197e12         # bf16 / chip
HBM_BW = 819e9              # bytes/s / chip
LINK_BW = 50e9              # bytes/s / link


def load_artifacts(art_dir: str) -> List[dict]:
    out = []
    for path in sorted(glob.glob(os.path.join(art_dir, "*.json"))):
        with open(path) as f:
            out.append(json.load(f))
    return out


def roofline_terms(rec: dict) -> dict:
    chips = rec["chips"]
    t_compute = rec["jaxpr_flops"] / (chips * PEAK_FLOPS)
    t_memory = rec["jaxpr_bytes"] / (chips * HBM_BW)
    t_coll = rec["collective_bytes_per_device"] / LINK_BW
    terms = {"compute": t_compute, "memory": t_memory,
             "collective": t_coll}
    dominant = max(terms, key=terms.get)
    bound = max(terms.values())
    useful = rec["model_flops"]
    return {
        **terms,
        "dominant": dominant,
        "step_time_bound": bound,
        "model_flops": useful,
        "flops_ratio": useful / max(rec["jaxpr_flops"], 1),
        # achievable fraction of compute roofline if perfectly overlapped
        "roofline_fraction": t_compute / max(bound, 1e-30),
        "mfu_bound": useful / max(bound, 1e-30) / (chips * PEAK_FLOPS),
    }


def fmt_s(x: float) -> str:
    if x >= 1:
        return f"{x:.2f}s"
    if x >= 1e-3:
        return f"{x*1e3:.1f}ms"
    return f"{x*1e6:.0f}us"


def table(records: List[dict], *, multi_pod=False, tag="") -> str:
    rows = []
    hdr = ("| arch | shape | chips | compute | memory | collective | "
           "dominant | roofline frac | MFU bound | MODEL/HLO flops |")
    sep = "|" + "---|" * 10
    rows.append(hdr)
    rows.append(sep)
    for r in records:
        if r["multi_pod"] != multi_pod or r.get("tag", "") != tag:
            continue
        t = roofline_terms(r)
        rows.append(
            f"| {r['arch']} | {r['shape']} | {r['chips']} "
            f"| {fmt_s(t['compute'])} | {fmt_s(t['memory'])} "
            f"| {fmt_s(t['collective'])} | {t['dominant']} "
            f"| {t['roofline_fraction']*100:.1f}% "
            f"| {t['mfu_bound']*100:.2f}% "
            f"| {t['flops_ratio']*100:.1f}% |")
    return "\n".join(rows)


def memory_table(records: List[dict], *, multi_pod=False) -> str:
    rows = ["| arch | shape | args GiB/dev | temp GiB/dev | fits 16G? |",
            "|---|---|---|---|---|"]
    for r in records:
        if r["multi_pod"] != multi_pod or r.get("tag", ""):
            continue
        m = r["memory"]
        total = (m["argument_bytes"] + m["temp_bytes"]
                 + m["output_bytes"]) / 2**30
        args = m["argument_bytes"] / 2**30
        temp = m["temp_bytes"] / 2**30
        rows.append(f"| {r['arch']} | {r['shape']} | {args:.2f} "
                    f"| {temp:.2f} | {'YES' if total < 16 else 'NO'} |")
    return "\n".join(rows)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--artifacts", default="artifacts/dryrun")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--tag", default="")
    args = ap.parse_args()
    recs = load_artifacts(args.artifacts)
    print(table(recs, multi_pod=args.multi_pod, tag=args.tag))
    print()
    print(memory_table(recs, multi_pod=args.multi_pod))


if __name__ == "__main__":
    main()
