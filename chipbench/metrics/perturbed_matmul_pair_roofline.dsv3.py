"""Kernel layer (kernels/perturbed_matmul.py): the probe-pair kernel's
roofline share in the DeepSeek-V3 cells (MLA projections, the dense MLP,
the shared expert, the head; ``counts_moe_mla.pair_calls``); moves
train_tokens_per_s.  Defined by ``Context.roofline``."""
from chipbench import counts_moe_mla as cm


def read(ctx):
    return ctx.roofline("perturbed_matmul_pair",
                        cm.pair_calls(ctx.config, ctx.tokens_per_step))
