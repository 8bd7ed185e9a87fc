"""The mesh cell's path on four virtual CPU devices: a sound run is
correct; with the halo exchange left out, or with the bfloat16 control in
the program's place, ``correct`` comes out false."""
import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]


#: the mesh cell, kept out of BENCHMARK.json until it is proven on four
#: chips: its entries are added here, as a later change would add them
MESH_SCRIPT = r"""
import json, sys
import jax.numpy as jnp
from perfbench import run as bench_run, spec
import repro.core.distributed as dist

bench = spec.benchmark()
bench["configs"].append({"name": "diffusion2d-f32-2x2", "source": "x",
                         "file": "perfbench/configs/diffusion2d-f32-2x2.json",
                         "reduced": [], "why": "x"})
bench["workloads"].append({"name": "diffusion2d.mesh",
                           "config": "diffusion2d-f32-2x2",
                           "traffic": "solve_49152sq", "chips": 4,
                           "why": "x"})
for m in bench["end_to_end"]:
    if m["name"] == "gcells_per_s":
        m["workloads"].append("diffusion2d.mesh")
spec.benchmark = lambda: bench

def no_exchange(x, grid_axis, axis_names, h, periodic=False):
    strip = jnp.zeros_like(jnp.take(x, jnp.arange(h), axis=grid_axis))
    return jnp.concatenate([strip, x, strip], axis=grid_axis)

mode = sys.argv[1]
if mode == "no_exchange":
    dist._exchange_halo = no_exchange
cell = bench_run.Cell("diffusion2d.mesh", 2 ** 33 + 5, 0.3, False,
                      traffic_overrides={"grid": [64, 256],
                                         "iters_per_chunk": 6},
                      control="bfloat16" if mode == "control" else None)
res = bench_run.run_cell(cell)
print(json.dumps({"correct": res["correct"], "checks": res["checks"]}))
"""


@pytest.mark.parametrize("mode,correct", [("sound", True),
                                          ("no_exchange", False),
                                          ("control", False)])
def test_mesh_on_four_cpu_devices(mode, correct):
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": os.environ.get("XLA_FLAGS", "")
           + " --xla_force_host_platform_device_count=4",
           "PYTHONPATH": f"{ROOT}{os.pathsep}{ROOT / 'src'}"}
    p = subprocess.run([sys.executable, "-c", MESH_SCRIPT, mode], cwd=ROOT,
                       env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    res = json.loads(p.stdout.strip().splitlines()[-1])
    assert res["correct"] is correct, res
    if correct:
        assert res["checks"]["max_rel_err"]["value"] < 1e-6
    assert np.isfinite(res["checks"]["max_rel_err"]["value"])
