"""Kernel layer (kernels/mgd_update.py): the update kernel's roofline
share on each chip of a mesh, which applies every pod's signs in one pass
(W read and written once, as in one chip's update); in the mesh cells;
moves train_tokens_per_s.  Defined by ``Context.update_roofline``."""


def read(ctx):
    return ctx.update_roofline()
