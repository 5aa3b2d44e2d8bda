"""On-chip benchmark of MGD training (see BENCHMARK.json at the repo root).

``python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell once on the TPU it is started on and prints
one JSON line.  Everything a cell needs is found by name: its model
configuration in ``configs/``, its traffic mix in ``traffic/``, its
per-layer metric readers in ``metrics/``, its correctness limits in
``limits/``, and, under the architecture its configuration names, the
plain reference in ``references/`` and the build of the program's model
configuration in ``programs/``.
"""
