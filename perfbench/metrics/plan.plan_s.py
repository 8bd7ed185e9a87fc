"""Seconds of set-up spent planning and compiling: the host clock around
``plan()`` (the model autotune) plus the compile time JAX reports
(tracing, lowering, backend compile or compile-cache retrieval) while the
cell's programs were first run."""


def read(cell):
    return cell.host.get("plan_s")
