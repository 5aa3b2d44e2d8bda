"""Kernel layer (kernels/perturbed_matmul.py): the pair kernel's roofline
share on each chip of a mesh, whose calls take that chip's pod's rows,
the batch over the pods (``Context.tokens_per_step`` counts every pod's);
in the mesh cells; moves train_tokens_per_s.  Defined by
``Context.roofline``."""
from chipbench import check, counts


def read(ctx):
    rows = ctx.tokens_per_step // check.pods(ctx.traffic)
    return ctx.roofline("perturbed_matmul_pair",
                        counts.pair_calls(ctx.config, rows))
