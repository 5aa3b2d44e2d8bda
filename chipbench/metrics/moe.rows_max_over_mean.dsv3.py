"""Expert layer (models/moe.py): the load of the busiest held expert over
the held experts' mean, from the routed-row counter, averaged over the
traced steps, MoE layers and probe streams (1 is an even load); in the
DeepSeek-V3 cells; moves train_tokens_per_s.  None without the counter."""
import numpy as np

from chipbench import counts_moe_mla as cm


def read(ctx):
    rows = cm.window_rows(ctx)
    if rows is None:
        return None
    rows = rows.astype(np.float64)
    mean = rows.mean(axis=-1)
    busy = np.where(mean > 0, rows.max(axis=-1) / np.maximum(mean, 1e-30),
                    1.0)
    return float(busy.mean())
