"""Percent of the traced solve window in which no operation ran on the
device (the mean over the chips of the cell): 1 - union of device-op
intervals / window.  Container instructions (``while``) do not count as
work, so idle time inside the super-step loop shows."""
from perfbench.trace import idle_share


def read(cell):
    return None if cell.trace_data is None else idle_share(cell.trace_data)
