"""Model layer (models/transformer.py): device ms per step of the cross-
entropy of both probes' logits, scope ``repro.model.loss``; in the online
cells; moves step_p95_ms.  Defined by
``scopes.Program.scope_ms_per_step``."""
from chipbench import scopes


def read(ctx):
    return scopes.of(ctx).scope_ms_per_step("repro.model.loss")
