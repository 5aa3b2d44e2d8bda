"""Kernel layer (kernels/perturbed_matmul.py): the pair kernel's roofline share,
in the training cells; moves train_tokens_per_s.  Defined by ``Context.pair_roofline``."""


def read(ctx):
    return ctx.pair_roofline()
