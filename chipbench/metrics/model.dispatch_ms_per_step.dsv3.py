"""Model layer (models/moe.py): device ms per step of the expert layer's
sort, gathers and weighted adds (dispatch) ops, scope ``repro.model.moe.dispatch``, in the
DeepSeek-V3 cells; moves train_tokens_per_s.  Defined by
``scopes.Program.scope_ms_per_step``."""
from chipbench import scopes


def read(ctx):
    return scopes.of(ctx).scope_ms_per_step("repro.model.moe.dispatch")
