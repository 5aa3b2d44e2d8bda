"""Model layer (models/transformer.py): device ms per step of the layer
scan's own ops outside the blocks, scope ``repro.model.layers``: each
layer's weights sliced out of the stacked banks;
in the online cells; moves step_p95_ms.  Defined by
``scopes.Program.scope_ms_per_step``."""
from chipbench import scopes


def read(ctx):
    return scopes.of(ctx).scope_ms_per_step("repro.model.layers")
