"""Percent of the traced serving window in which no operation ran on the
device: 1 - union of device-op intervals / window.  High when the host
(admission, staging, per-round transfers) holds the chip back."""
from perfbench.trace import idle_share


def read(cell):
    return None if cell.trace_data is None else idle_share(cell.trace_data)
