"""Loop layer (training/train_loop.py): device-to-host copies per step,
the runtime's ``D2H Dispatch`` events inside the loop's reads of each
program's metrics (span ``repro.loop.read``); in the online cells; moves
step_p95_ms.  Defined by ``scopes.events_per_step``."""
from chipbench import scopes


def read(ctx):
    return scopes.events_per_step(ctx, "repro.loop.read", scopes.D2H)
