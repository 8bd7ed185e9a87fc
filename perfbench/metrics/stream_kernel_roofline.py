"""Roofline share of the streaming Pallas kernel, in percent.

The least time the chip could take for the traced chunks' work, over the
summed device time of the kernel's executions in them:

    max(ops / peak FLOP/s, bytes / peak B/s) / kernel seconds

``ops`` is cells x iterations x the configuration's ``ops_per_update``,
counted from its published equation; ``bytes`` is the compulsory traffic
of a chunk, each state and aux field read once and the state written once
(4 bytes a cell in f32).  Neither depends on how the kernel is built.  On a
TPU v5e the operations term bounds every configuration here: the only
published compute peak is the bf16 MXU rate, which the kernel's VPU
arithmetic cannot approach, so the share reads low by construction.

The kernel is the ``tpu_custom_call`` instruction the trace names
``superstep_chain`` (the jitted function around today's ``pallas_call``).
No such instruction (the distributed backend runs no kernel): no reading.
"""
from perfbench.trace import base_name, clip

KERNEL = "superstep_chain"


def kernel_events(trace):
    lo, hi = trace.window()
    return [(s, e) for d in trace.ops for op, s, e in trace.ops[d]
            if base_name(op) == KERNEL and "tpu_custom_call" in op
            and clip([(s, e)], lo, hi)]


def read(cell):
    if cell.trace_data is None or cell.peaks is None:
        return None
    events = kernel_events(cell.trace_data)
    if not events:
        return None
    cfg, host = cell.config, cell.host
    updates = host["chunks"] * host["cells_per_chunk"] * host["iters_per_chunk"]
    ops = updates * cfg["ops_per_update"]
    fields = 2 * cfg["state_fields"] + cfg["aux_fields"]
    nbytes = host["chunks"] * host["cells_per_chunk"] * 4 * fields
    t_min = max(ops / cell.peaks["flops_per_s"],
                nbytes / cell.peaks["hbm_bytes_per_s"])
    kernel_s = sum(e - s for s, e in events) / 1e9
    return 100.0 * t_min / kernel_s
