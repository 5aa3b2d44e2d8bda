"""Model layer (models/transformer.py): device ms per step of the decoder
block's own ops: its norms and residual adds, scope ``repro.model.block``;
in the training cells; moves train_tokens_per_s.  Defined by
``scopes.Program.scope_ms_per_step``."""
from chipbench import scopes


def read(ctx):
    return scopes.of(ctx).scope_ms_per_step("repro.model.block")
