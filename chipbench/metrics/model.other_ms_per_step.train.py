"""Model layer (models/transformer.py): busy ms per step outside the kernels,
in the training cells; moves train_tokens_per_s.  Defined by ``Context.other_ms_per_step``."""


def read(ctx):
    return ctx.other_ms_per_step()
