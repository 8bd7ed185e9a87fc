"""Device time per super-step of the halo refresh, in us: the operations
whose innermost program scope is ``stencil.halo_refresh`` (the gathers and
selects of ``kernels/ops._reclamp_padded`` that rebuild the padded carry's
halo columns between two kernels), over the kernel executions in the
traced window.  With ``loop.relayout_us`` and the idle time inside the
loop it makes up ``loop.launch_gap_us``."""
from perfbench.scopes import loop_split_us


def read(cell):
    return loop_split_us(cell, "halo_refresh")
