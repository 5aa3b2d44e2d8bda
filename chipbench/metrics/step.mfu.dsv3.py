"""Step layer (core/mgd.py fused step): the two probe forwards' FLOPs
(weight products over every token, the routed rows' expert products from
the counter, causal MLA attention; ``counts_moe_mla.model_flops``) of the
traced steps over the window's length times the chips' bf16 peak, in
percent, in the DeepSeek-V3 cells; moves train_tokens_per_s.  None
without the counter."""
from chipbench import counts_moe_mla as cm


def read(ctx):
    rows = cm.mean_rows(ctx)
    if rows is None:
        return None
    t = ctx.traffic
    flops = cm.model_flops(ctx.config, int(t["batch"]), int(t["seq_len"]),
                           rows) * ctx.steps
    return 100.0 * flops / (ctx.window_s * ctx.chips * ctx.peak_flops)
