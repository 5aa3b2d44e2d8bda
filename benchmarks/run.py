"""Benchmark harness: one module per paper table/figure.

``python -m benchmarks.run [--only fig4,table2] [--seed 7]`` runs each
benchmark, prints a CSV (bench,name,value,detail) and writes
artifacts/bench/*.json.  ``--list`` enumerates the registered benchmarks
without running anything.  Any selected benchmark that raises makes the
harness exit non-zero (after running the rest), so CI smoke cannot pass
on a broken benchmark.
"""
from __future__ import annotations

import argparse
import importlib
import inspect
import json
import os
import sys
import time
import traceback

BENCHES = [
    "scaling_laws",
    "fig4_equivalence",
    "fig5_angle",
    "fig6_tau_theta",
    "fig7_perturbations",
    "fig8_noise",
    "table2_datasets",
    "table3_hardware",
    "hardware_plants",
    "fused_probe",
    "farm_scaling",
    "drift_aging",
    "fault_tolerance",
    "online_serving",
    "roofline_report",
]


def _call_run(mod, seed, smoke=False):
    """Benchmarks that take run(seed=...) get the harness seed; the rest
    keep their built-in seed grids (their statistics are seed-medians
    already).  ``--smoke`` likewise forwards smoke=True only to
    benchmarks that declare it (reduced grids for CI).  Returns
    (rows, seed_used) — None when the benchmark ignores the flag, so
    artifacts never claim a seed that wasn't used."""
    params = inspect.signature(mod.run).parameters
    kwargs = {}
    if "seed" in params:
        kwargs["seed"] = seed
    if smoke and "smoke" in params:
        kwargs["smoke"] = True
    return mod.run(**kwargs), kwargs.get("seed")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated benchmark name substrings")
    ap.add_argument("--list", action="store_true",
                    help="print the registered benchmark names and exit")
    ap.add_argument("--seed", type=int, default=0,
                    help="base seed forwarded to benchmarks that accept "
                         "run(seed=...)")
    ap.add_argument("--smoke", action="store_true",
                    help="forward smoke=True to benchmarks that accept it "
                         "(reduced grids for CI)")
    ap.add_argument("--out", default="artifacts/bench")
    args = ap.parse_args(argv)

    if args.list:
        for name in BENCHES:
            print(name)
        return 0

    selected = BENCHES
    if args.only:
        keys = args.only.split(",")
        unknown = [k for k in keys if not any(k in b for b in BENCHES)]
        if unknown:
            print(f"--only matched no benchmark for {unknown}; "
                  f"registered: {BENCHES}", file=sys.stderr)
            return 2
        selected = [b for b in BENCHES if any(k in b for k in keys)]

    from repro.launch.cache import use_compile_cache
    use_compile_cache()
    os.makedirs(args.out, exist_ok=True)
    print("bench,name,value,detail")
    failures = []
    for name in selected:
        mod = importlib.import_module(f"benchmarks.{name}")
        t0 = time.time()
        try:
            rows, seed_used = _call_run(mod, args.seed, smoke=args.smoke)
        except Exception as e:    # noqa: BLE001
            failures.append((name, repr(e)))
            traceback.print_exc(limit=5, file=sys.stderr)
            continue
        dt = time.time() - t0
        for r in rows:
            detail = str(r.get("detail", "")).replace(",", ";")
            print(f"{r['bench']},{r['name']},{r['value']},{detail}")
        with open(os.path.join(args.out, f"{name}.json"), "w") as f:
            json.dump({"rows": rows, "seconds": dt, "seed": seed_used},
                      f, indent=1)
        print(f"# {name} done in {dt:.1f}s", file=sys.stderr)
    if failures:
        print(f"# FAILURES: {failures}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
