"""Step layer (core/probe_parallel.py fused step): the two probe forwards
of every pod's rows (the whole batch) over the window times the mesh's
chips' bf16 peak, in the mesh cells; moves train_tokens_per_s.  Defined
by ``Context.mfu``."""


def read(ctx):
    return ctx.mfu()
