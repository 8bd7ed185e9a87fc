"""Find a cell's configuration, traffic, limits and metric readers by name.

Everything is data or a file of its own under ``perfbench/``, named in
``BENCHMARK.json``:

* ``configs/<config>.json``    one deployment (stencil, dtype, boundary,
                               coefficients, chip layout, work count);
* ``traffic/<mix>.json``       one traffic mix, read by ``generate.py`` and
                               the driver named by its ``kind``
                               (``drivers/<kind>.py``);
* ``limits/<workload>.json``   the limit of each number ``correct`` compares;
* ``metrics/<metric>.py``      one per-layer reader, ``read(ctx)``;
* ``peaks.json``               published peaks keyed by ``device_kind``.

Adding a cell, a mix or a metric adds files; no file here changes.
"""
from __future__ import annotations

import importlib
import importlib.util
import json
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return load_json(ROOT / "BENCHMARK.json")


def workload(name: str, bench: dict | None = None) -> dict:
    bench = bench or benchmark()
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                   f"{[w['name'] for w in bench['workloads']]}")


def config(name: str, bench: dict | None = None) -> dict:
    bench = bench or benchmark()
    for c in bench["configs"]:
        if c["name"] == name:
            return load_json(ROOT / c["file"])
    raise KeyError(f"no config {name!r} in BENCHMARK.json")


def traffic(name: str) -> dict:
    return load_json(HERE / "traffic" / f"{name}.json")


def limits(workload_name: str) -> dict:
    """``{number: {"limit": x, ...}}`` for one cell."""
    return load_json(HERE / "limits" / f"{workload_name}.json")


def peaks(device_kind: str) -> dict:
    table = load_json(HERE / "peaks.json")
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"perfbench/peaks.json has {sorted(table)}")
    return table[device_kind]


def driver(kind: str):
    """The module that runs a traffic mix of this ``kind``."""
    return importlib.import_module(f"perfbench.drivers.{kind}")


def metric_reader(name: str):
    """``read(ctx) -> float | None`` of ``metrics/<name>.py``."""
    path = HERE / "metrics" / f"{name}.py"
    if not path.exists():
        raise KeyError(f"no reader {path.relative_to(ROOT)} for metric "
                       f"{name!r}")
    spec = importlib.util.spec_from_file_location(
        f"perfbench.metrics.{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def metrics_for(workload_name: str, kind: str,
                bench: dict | None = None) -> list:
    """The ``end_to_end`` (kind) or ``per_layer`` metrics this cell reports:
    those that list it under ``workloads``, or, without that key, those
    whose ``moves`` metric the cell reports (per-layer) / every cell
    (end-to-end)."""
    bench = bench or benchmark()
    e2e = [m for m in bench["end_to_end"]
           if workload_name in m.get("workloads", [workload_name])]
    if kind == "end_to_end":
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if workload_name in m.get("workloads", [workload_name])
            and m["moves"] in names]
