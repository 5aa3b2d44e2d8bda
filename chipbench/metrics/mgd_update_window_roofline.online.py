"""Kernel layer (kernels/mgd_update.py): the update kernel's roofline share,
in the online cells; moves step_p95_ms.  Defined by ``Context.update_roofline``."""


def read(ctx):
    return ctx.update_roofline()
