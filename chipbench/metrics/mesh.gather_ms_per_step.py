"""Mesh layer (core/probe_parallel.py): device ms per step of the
collective ops, the exchange of the pods' C̃ in whatever form the
compiler gives it (all-gather, all-reduce, their async start and done),
averaged over the chips; in the mesh cells; moves train_tokens_per_s.
None where the trace holds no collective."""
import re

COLLECTIVE = re.compile(
    r"^(all-gather|all-reduce|reduce-scatter|collective-permute|all-to-all)")


def read(ctx):
    names = {e.name for i in range(ctx.chips)
             for e in ctx.trace.devices.get(i, []) if COLLECTIVE.match(e.name)}
    seconds, calls = ctx.op_seconds(names)
    if not calls:
        return None
    return 1e3 * seconds / ctx.steps
