"""The reader of the halo exchange's scope on a mesh, on a hand-made trace
of four chips whose answer can be worked out by hand
(``data/mesh_traces.json``), and the mesh program compiled again on four
virtual CPU devices."""
import json
import os
import subprocess
import sys
import types
from pathlib import Path

import pytest

from perfbench import spec, trace

ROOT = Path(__file__).resolve().parents[2]
DATA = json.loads((Path(__file__).parent / "data" /
                   "mesh_traces.json").read_text())
#: the same program's op names from a version whose exchange has no scope
UNSCOPED = {k: v.replace("stencil.halo_exchange/", "")
            for k, v in DATA["op_names"].items()}


def cell_with(op_names=None, **host):
    return types.SimpleNamespace(
        trace_data=trace.Trace.from_json(DATA["mesh"]), peaks=None,
        host=host, config={}, counters={},
        mesh_op_names=DATA["op_names"] if op_names is None else op_names)


def read(cell):
    return spec.metric_reader("mesh.halo_exchange_us")(cell)


def test_exchange_is_its_scope_over_the_kernels_of_every_chip():
    # in the loop, chip d spends 4 + 2 + (10 + d) + 4 ns per super-step on
    # the exchange; the first exchange before the loop and the strip
    # refresh do not count: mean over the chips 21.5 ns
    assert read(cell_with()) == pytest.approx(0.0215)


@pytest.mark.parametrize("op_names", [UNSCOPED, {}],
                         ids=["exchange-without-scope", "no-program"])
def test_program_without_the_scope_reads_nothing(op_names):
    assert read(cell_with(op_names)) is None


def test_no_reading_without_trace_or_kernels():
    assert read(types.SimpleNamespace(trace_data=None)) is None
    cell = cell_with()
    for ops in cell.trace_data.ops.values():
        ops[:] = [o for o in ops if not o[0].startswith("%superstep_chain")]
    assert read(cell) is None


def test_exposed_share_reads_the_same_trace():
    # collectives alone on chip d: 6 ns before the loop, 2 x (12 + d) in it
    share = spec.metric_reader("halo.exposed_share")(cell_with())
    assert share == pytest.approx(100 * (6 + 2 * 13.5) / 1000)


RELOWER_SCRIPT = r"""
import json, types
from perfbench import mesh_scopes, scopes, spec
cell = types.SimpleNamespace(
    config={**spec.load_json(spec.HERE / "configs" /
                             "diffusion2d-f32-2x2.json")},
    traffic={"grid": [64, 256]})
names = mesh_scopes.program_op_names(cell)
print(json.dumps(sorted({scopes.scope(p) for p in names.values()
                         if scopes.scope(p)})))
"""


def test_the_mesh_program_compiled_again_names_its_exchange():
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": os.environ.get("XLA_FLAGS", "")
           + " --xla_force_host_platform_device_count=4",
           "PYTHONPATH": f"{ROOT}{os.pathsep}{ROOT / 'src'}"}
    p = subprocess.run([sys.executable, "-c", RELOWER_SCRIPT], cwd=ROOT,
                       env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    found = set(json.loads(p.stdout.strip().splitlines()[-1]))
    assert {"stencil.superstep", "stencil.halo_refresh",
            "stencil.halo_exchange", "stencil.unpad"} <= found
