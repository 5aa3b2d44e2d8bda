"""Step layer (core/mgd.py fused step): the step's share of the bf16 peak,
in the online cells; moves step_p95_ms.  Defined by ``Context.mfu``."""


def read(ctx):
    return ctx.mfu()
