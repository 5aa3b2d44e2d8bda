"""Step layer (core/mgd.py fused step): the step's share of the bf16 peak,
in the training cells; moves train_tokens_per_s.  Defined by ``Context.mfu``."""


def read(ctx):
    return ctx.mfu()
