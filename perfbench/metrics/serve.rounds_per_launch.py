"""Staged-advance rounds per coalesced launch (snapshot ``rounds /
batches``): each distinct iteration count in a batch costs one more
``run_batch`` round over the whole padded batch and one more host copy."""


def read(cell):
    batches = cell.counters.get("batches")
    if not batches:
        return None
    return cell.counters["rounds"] / batches
