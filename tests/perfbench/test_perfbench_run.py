"""``run.py`` end to end on the CPU, without the look for a chip.

The timed path runs in Pallas interpret mode at a size a test can hold;
each fault the cell can have is planted underneath it, and ``correct``
must come out false.  The control (the reference in bfloat16 in the
program's place) must too.
"""
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax.numpy as jnp
import pytest

from perfbench import run as bench_run, spec
from repro.api.plan import StencilPlan

ROOT = Path(__file__).resolve().parents[2]
SMALL = {
    "hotspot2d.solve": {"grid": [16, 256], "iters_per_chunk": 24},
    "diffusion3d.solve": {"grid": [8, 16, 128], "iters_per_chunk": 10},
    "hotspot2d.serve": {"grid": [16, 128], "rate_per_s": 24.0, "pool": 3,
                        "iters": [1, 3, 5, 9], "sample_per_iters": 2},
}


#: the serving cell as BENCHMARK.json names it once it is proven on the chip
SERVE_CELL = {
    "workload": {"name": "hotspot2d.serve", "config": "hotspot2d-f32",
                 "traffic": "serve_1024sq", "chips": 1, "why": "x"},
    "end_to_end": [{"name": n, "unit": "ms", "better": "lower",
                    "bound": 0.2, "source": "host_clock",
                    "workloads": ["hotspot2d.serve"]}
                   for n in ("request_p95_ms", "request_p50_ms")],
}


@pytest.fixture
def serve_cell(monkeypatch):
    """BENCHMARK.json with the serving cell in it."""
    bench = spec.benchmark()
    if not any(w["name"] == "hotspot2d.serve" for w in bench["workloads"]):
        bench["workloads"].append(SERVE_CELL["workload"])
        bench["end_to_end"] += SERVE_CELL["end_to_end"]
    monkeypatch.setattr(spec, "benchmark", lambda: bench)


def run_small(workload, seconds=0.3, **kw):
    cell = bench_run.Cell(workload, 2 ** 33 + 1, seconds, False,
                          config_overrides={"backend": "pallas_interpret"},
                          traffic_overrides=SMALL[workload], **kw)
    return bench_run.run_cell(cell)


def state_unchanged(real):
    def run(self, grid, iters, *a, **k):
        return jnp.asarray(grid)
    return run


def one_cell_altered(real):
    def run(self, grid, iters, *a, **k):
        out = real(self, grid, iters, *a, **k)
        return out.at[(0,) * out.ndim].add(1.0)
    return run


def half_batch_left_out(real):
    def run_batch(self, grids, iters, *a, **k):
        out = real(self, grids, iters, *a, **k)
        keep = out.shape[0] // 2
        return jnp.concatenate([out[:keep], jnp.asarray(grids)[keep:]])
    return run_batch


def test_no_tpu_exits_nonzero_and_prints_no_result():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    p = subprocess.run([sys.executable, "perfbench/run.py", "--workload",
                        "hotspot2d.solve", "--seed", str(2 ** 33),
                        "--seconds", "1", "--trace", "0"], cwd=ROOT,
                       env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "correct" not in p.stdout
    assert "no TPU" in p.stderr


def test_checkout_of_the_benchmark_alone_exits_nonzero(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "perfbench", tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    p = subprocess.run([sys.executable, "perfbench/run.py", "--workload",
                        "hotspot2d.solve", "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=tmp_path,
                       env={**env, "JAX_PLATFORMS": "cpu"},
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "correct" not in p.stdout


@pytest.mark.parametrize("workload", ["hotspot2d.solve", "diffusion3d.solve"])
def test_solve_sound_run_is_correct(workload):
    res = run_small(workload)
    assert res["correct"], res
    assert res["checks"]["max_rel_err"]["value"] < 1e-6
    assert res["compiles_in_window"] == 0
    assert res["attempted"] >= 1 and res["failed"] == 0


@pytest.mark.parametrize("fault", [state_unchanged, one_cell_altered])
@pytest.mark.parametrize("workload", ["hotspot2d.solve", "diffusion3d.solve"])
def test_solve_fault_is_not_correct(workload, fault, monkeypatch):
    monkeypatch.setattr(StencilPlan, "run", fault(StencilPlan.run))
    res = run_small(workload)
    assert not res["correct"], res


@pytest.mark.parametrize("workload", ["hotspot2d.solve", "diffusion3d.solve"])
def test_solve_control_is_not_correct(workload):
    res = run_small(workload, control="bfloat16")
    assert not res["correct"], res


def test_serve_sound_run_is_correct(serve_cell):
    res = run_small("hotspot2d.serve", seconds=0.5)
    assert res["correct"], res
    assert res["window"]["compared"] == 8
    assert res["failed"] == 0
    assert {"request_p95_ms", "request_p50_ms", "setup_s"} == \
        set(res["metrics"])


@pytest.mark.parametrize("fault", [state_unchanged, one_cell_altered,
                                   half_batch_left_out])
def test_serve_fault_is_not_correct(fault, monkeypatch, serve_cell):
    monkeypatch.setattr(StencilPlan, "run_batch",
                        fault(StencilPlan.run_batch))
    res = run_small("hotspot2d.serve", seconds=0.5)
    assert not res["correct"], res


def test_serve_control_is_not_correct(serve_cell):
    res = run_small("hotspot2d.serve", seconds=0.5, control="bfloat16")
    assert not res["correct"], res
