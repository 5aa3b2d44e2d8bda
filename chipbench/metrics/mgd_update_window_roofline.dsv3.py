"""Kernel layer (kernels/mgd_update.py): the update kernel's roofline
share in the DeepSeek-V3 cells, every ndim ≥ 2 leaf read and written once
(the expert banks and the float32 router among them;
``counts_moe_mla.update_calls``); moves train_tokens_per_s."""
from chipbench import counts_moe_mla as cm


def read(ctx):
    return ctx.roofline("mgd_update_window", cm.update_calls(ctx.config))
