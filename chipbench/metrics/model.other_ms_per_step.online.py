"""Model layer (models/transformer.py): busy ms per step outside the kernels,
in the online cells; moves step_p95_ms.  Defined by ``Context.other_ms_per_step``."""


def read(ctx):
    return ctx.other_ms_per_step()
