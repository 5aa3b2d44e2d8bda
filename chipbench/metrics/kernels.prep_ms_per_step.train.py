"""Kernel layer (kernels/ops.py): device ms per step of the wrappers' pads,
reshapes and output slices around the Pallas calls, scope
``repro.kernels.prep`` (S6);
in the training cells; moves train_tokens_per_s.  Defined by
``scopes.Program.scope_ms_per_step``."""
from chipbench import scopes


def read(ctx):
    return scopes.of(ctx).scope_ms_per_step("repro.kernels.prep")
