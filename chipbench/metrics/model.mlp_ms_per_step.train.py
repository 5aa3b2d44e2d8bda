"""Model layer (models/transformer.py): device ms per step of the gated MLP
outside the kernels: the SiLU gate, scope ``repro.model.mlp``; in the
training cells; moves train_tokens_per_s.  Defined by
``scopes.Program.scope_ms_per_step``."""
from chipbench import scopes


def read(ctx):
    return scopes.of(ctx).scope_ms_per_step("repro.model.mlp")
