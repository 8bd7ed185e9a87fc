"""Device time per super-step of the super-step loop's other work, in us:
every operation inside the loop that is not the kernel and not under
``stencil.halo_refresh`` (nor ``stencil.pad`` / ``stencil.unpad``).  That is
the layout copies into and out of the kernel's layout, which XLA leaves in
the kernel's scope or in none, and the loop's scalar work, over the kernel
executions in the traced window."""
from perfbench.scopes import loop_split_us


def read(cell):
    return loop_split_us(cell, "relayout")
