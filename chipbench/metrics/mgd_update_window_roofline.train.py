"""Kernel layer (kernels/mgd_update.py): the update kernel's roofline share,
in the training cells; moves train_tokens_per_s.  Defined by ``Context.update_roofline``."""


def read(ctx):
    return ctx.update_roofline()
