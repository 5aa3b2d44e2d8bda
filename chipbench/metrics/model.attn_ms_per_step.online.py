"""Model layer (models/transformer.py): device ms per step of attention
outside the kernels: qk-norm, rope, the attention core and its softmax,
scope ``repro.model.attn``; in the online cells; moves step_p95_ms.
Defined by ``scopes.Program.scope_ms_per_step``."""
from chipbench import scopes


def read(ctx):
    return scopes.of(ctx).scope_ms_per_step("repro.model.attn")
