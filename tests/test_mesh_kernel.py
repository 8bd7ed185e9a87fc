"""The distributed backend runs the streaming kernel on every shard: four
virtual CPU devices (``mesh_kernel_check.py``, one subprocess for every
case, so the main pytest process keeps its single-device view) against the
``kernels/ref.py`` oracle and against the one-device kernel."""
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
from mesh_kernel_check import CASES  # noqa: E402


@pytest.fixture(scope="module")
def results():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(HERE), "src")
    out = subprocess.run([sys.executable,
                          os.path.join(HERE, "mesh_kernel_check.py")],
                         env=env, capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("case", sorted(CASES))
def test_mesh_kernel_matches_oracle_and_one_device(results, case):
    """Within 1e-6 of the oracle, and bit for bit the one-device kernel's
    result: every cell is computed by the same arithmetic in the same
    order, only in another block of another shard, and the halo strips a
    shard receives are the neighbour's exact cells."""
    got = results[case]
    assert got["finite"], got
    assert got["rel_err"] <= 1e-6, got
    assert got["bit_equal"], got


def test_mesh_program_names_kernel_and_exchange(results):
    """The kernel ``superstep_chain`` runs under ``stencil.superstep``, and
    every collective-permute under ``stencil.halo_exchange``: per sharded
    axis one per direction before the loop and one inside it."""
    trace = results["trace"]
    assert trace["kernel_ops"] > 0
    assert len(trace["permutes"]) >= 8, trace["permutes"]
    assert all("/stencil.halo_exchange/" in p for p in trace["permutes"]), \
        trace["permutes"]
    assert sum("/while/body/" in p for p in trace["permutes"]) >= 4
