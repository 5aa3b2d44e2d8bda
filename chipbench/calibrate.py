#!/usr/bin/env python3
"""Readings that the correctness limits are set from, on the chip.

    python3 chipbench/calibrate.py --workload <cell> --seeds 11,12,... \\
        [--control-seeds 11,12,13] [--out chipbench/out/calibrate.json]

In one process (one compile), for every seed: the program's first steps
through the same set-up as a benchmark run, and the reference's, compared
as ``check.py`` compares them — the lower readings.  For each control
seed, the same reference put in the program's place with a fault
planted, each compared likewise — the upper readings:

* ``control_fp8``: every product of the reference in float8 e4m3, the
  precision below the configuration's bfloat16;
* ``half_batch``: the cost taken over the first half of the batch only;
* ``missing_pod`` (mixes with ``pods`` > 1 only): pod 1's C̃ left out of
  the update.

A step that returns its state unchanged reads change_gap = 1 with no run.
The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
if __name__ == "__main__":
    sys.path[0] = str(ROOT)

from chipbench import run  # noqa: E402  (puts src/ on the path)
from chipbench import bench, check  # noqa: E402

import jax  # noqa: E402

FAULTS = {
    "control_fp8": {"compute": "float8_e4m3fn"},
    "half_batch": {"token_frac": 0.5},
    "missing_pod": {"drop_pod": 1},
}


def calibrate(cell, seeds, control_seeds, devices, *, kernel_impl="pallas",
              log=print) -> dict:
    out = {"cell": cell.name, "sound": {}, "faults": {}}
    faults = {name: kw for name, kw in FAULTS.items()
              if "drop_pod" not in kw or check.pods(cell.traffic) > 1}
    for seed in seeds:
        t0 = time.perf_counter()
        sd = run.derive_seeds(seed)
        s = run.setup(cell, sd, devices, kernel_impl=kernel_impl)
        program = s.program
        del s
        ref = check.follow(cell, sd)
        nums = check.numbers(cell, program, ref)
        out["sound"][seed] = {k: v["value"] for k, v in nums.items()}
        log(f"[calibrate] {cell.name} seed {seed}: {out['sound'][seed]} "
            f"program {program.cost} {program.applied} reference {ref.cost} "
            f"{ref.applied} ({time.perf_counter() - t0:.1f} s)")
        if seed in control_seeds:
            for name, kw in faults.items():
                bad = check.follow(cell, sd, **kw)
                v = {k: x["value"] for k, x in
                     check.numbers(cell, bad, ref).items()}
                out["faults"].setdefault(name, {})[seed] = v
                log(f"[calibrate] {cell.name} seed {seed} {name}: {v} "
                    f"cost {bad.cost} C̃ {bad.applied}")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    cell = bench.resolve(args.workload)
    try:
        devices = run.tpu_devices(cell.chips)
    except run.NoChip as e:
        print(f"calibrate: {e}", file=sys.stderr)
        return 2
    run.use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    seeds = [int(x) for x in args.seeds.split(",")]
    ctl = [int(x) for x in args.control_seeds.split(",") if x]
    res = calibrate(cell, seeds, set(ctl), devices)
    text = json.dumps(res, indent=1)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(text)
    print(text)
    return 0


if __name__ == "__main__":
    sys.exit(main())
