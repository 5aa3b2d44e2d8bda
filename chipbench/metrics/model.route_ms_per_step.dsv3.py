"""Model layer (models/moe.py): device ms per step of the expert layer's
router and top-k (route) ops, scope ``repro.model.moe.route``, in the
DeepSeek-V3 cells; moves train_tokens_per_s.  Defined by
``scopes.Program.scope_ms_per_step``."""
from chipbench import scopes


def read(ctx):
    return scopes.of(ctx).scope_ms_per_step("repro.model.moe.route")
