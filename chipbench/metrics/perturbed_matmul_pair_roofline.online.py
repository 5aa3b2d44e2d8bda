"""Kernel layer (kernels/perturbed_matmul.py): the pair kernel's roofline share,
in the online cells; moves step_p95_ms.  Defined by ``Context.pair_roofline``."""


def read(ctx):
    return ctx.pair_roofline()
