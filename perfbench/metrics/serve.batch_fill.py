"""Percent of launched batch slots that carried a real request
(``StencilService.snapshot()["batch_fill"]``, real / padded over the
window's launches)."""


def read(cell):
    fill = cell.counters.get("batch_fill")
    return None if fill is None else 100.0 * fill
