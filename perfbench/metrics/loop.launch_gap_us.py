"""Mean time between consecutive kernel executions inside a chunk, in us.

The super-step loop (``kernels/ops.py`` ``fused_*_loop``) runs one kernel
per super-step; what lies between two of them on the device is the halo
refresh of the padded carry, the layout copies around the kernel, the loop
control and any idle time.  Gaps across a chunk boundary (host dispatch)
are left out: the traced window holds whole chunks, each with the same
number of kernel executions.
"""
from perfbench.trace import base_name

KERNEL = "superstep_chain"


def read(cell):
    tr = cell.trace_data
    if tr is None or len(tr.ops) != 1:
        return None
    (dev,) = tr.ops
    lo, hi = tr.window()
    ks = sorted((s, e) for op, s, e in tr.ops[dev]
                if base_name(op) == KERNEL and lo <= s < hi)
    chunks = cell.host.get("chunks", 0)
    if not chunks or len(ks) < 2 * chunks or len(ks) % chunks:
        return None
    per = len(ks) // chunks
    gaps = [ks[i + 1][0] - ks[i][1] for i in range(len(ks) - 1)
            if (i + 1) % per]
    return sum(gaps) / len(gaps) / 1e3
