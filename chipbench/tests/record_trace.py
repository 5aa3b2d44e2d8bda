"""Record the scoped trace fixture on a TPU: four steps of the online
cell's window, one step per program, traced as a ``--trace 1`` run traces
its window, gzipped to the path given.

    python3 -m chipbench.tests.record_trace <out.xplane.pb.gz> [--seed N]

Prints the run's result line, whose per-layer metrics are read from the
same four steps.
"""
import argparse
import gzip
import json
import shutil

from chipbench import bench, run
from chipbench import trace as tr

CELL = "mistral-nemo-12b.online-1x128"
STEPS = 4


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("out")
    ap.add_argument("--seed", type=int, default=2 ** 31 + 13)
    args = ap.parse_args(argv)
    cell = bench.resolve(CELL)
    run.TRACE_MAX_STEPS = STEPS * int(cell.traffic["chunk"])
    result = run.run_cell(cell, args.seed, 1.0, True,
                          run.tpu_devices(cell.chips))
    src = tr.find_trace(run.OUT / "trace" / cell.name)
    with open(src, "rb") as f, gzip.open(args.out, "wb") as g:
        shutil.copyfileobj(f, g)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
