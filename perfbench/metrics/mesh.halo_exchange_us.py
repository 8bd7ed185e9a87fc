"""Device time per super-step of the halo exchange on a mesh, in us: the
super-step loop's operations whose innermost program scope is
``stencil.halo_exchange`` (``core/distributed.py``: the slices of each
shard's edge strips, the collective-permutes that carry them to the
neighbours and the writes of the strips received), averaged over the
chips, over the kernel executions per chip in the traced window.  A
program whose exchange names no scope reads nothing."""
from perfbench.mesh_scopes import loop_scope_us


def read(cell):
    return loop_scope_us(cell, "stencil.halo_exchange")
