"""Kernel layer (kernels/grouped_matmul.py): the grouped probe-pair
kernel's roofline share in the DeepSeek-V3 cells — for each call the
larger of its FLOPs (the rows each stream routed to each held expert, from
the counter) over the bf16 peak and its bytes (the held experts' W read
once, the routed rows read and written) over the HBM bandwidth, over its
device time; moves train_tokens_per_s.  None without the kernel or the
counter."""
from chipbench import counts_moe_mla as cm


def read(ctx):
    rows = cm.mean_rows(ctx)
    if rows is None:
        return None
    return ctx.roofline("perturbed_grouped_matmul_pair",
                        cm.grouped_calls(ctx.config, rows))
