"""Loop layer (training/train_loop.py): device idle ms per step in the
gaps that the loop's host reads of each program's metrics (span
``repro.loop.read``) overlap the most, as ``trace.label_gap`` labels
gaps; in the online cells; moves step_p95_ms.  Defined by
``scopes.gap_ms_per_step``."""
from chipbench import scopes


def read(ctx):
    return scopes.gap_ms_per_step(ctx, "repro.loop.read")
