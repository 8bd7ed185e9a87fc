"""Percent of the traced window in which a chip ran a collective-permute
(the halo exchange of ``core/distributed.py``) and no other operation,
the mean over the chips that ran one.  Exchange time hidden under
compute does not count."""
from perfbench.trace import base_name, clip, length


def read(cell):
    tr = cell.trace_data
    if tr is None:
        return None
    lo, hi = tr.window()
    shares = []
    for d in tr.ops:
        coll, other = [], []
        for op, s, e in tr.leaf_ops(d):
            (coll if base_name(op).startswith("collective-permute")
             else other).append((s, e))
        coll, other = clip(coll, lo, hi), clip(other, lo, hi)
        if not coll:
            continue
        exposed = length(coll + other) - length(other)
        shares.append(exposed / (hi - lo))
    return 100.0 * sum(shares) / len(shares) if shares else None
