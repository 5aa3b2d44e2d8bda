"""Loop layer (training/train_loop.py): host ms per step in the dispatch of
each device program (span ``repro.loop.dispatch``: argument handling and
output allocation before the program is enqueued), on the host's clock;
in the online cells; moves step_p95_ms.  Defined by
``scopes.span_ms_per_step``."""
from chipbench import scopes


def read(ctx):
    return scopes.span_ms_per_step(ctx, "repro.loop.dispatch")
