"""The benchmark's data: found by name, deterministic, counted right."""
import json
import re
import shutil
from collections import Counter

import numpy as np
import pytest

from perfbench import generate, spec

BENCH = spec.benchmark()
#: every configuration file, those of cells not yet in BENCHMARK.json too
CONFIGS = sorted(p.stem for p in (spec.HERE / "configs").glob("*.json"))

#: operations per cell update, counted by hand from each published equation
HAND_COUNT = {"hotspot2d": 15, "diffusion2d": 9, "diffusion3d": 13}
#: compulsory bytes per cell of a chunk: state read + aux read + state write
HAND_BYTES = {"hotspot2d": 12, "diffusion2d": 8, "diffusion3d": 8}


def test_every_name_in_benchmark_json_resolves():
    for w in BENCH["workloads"]:
        cfg = spec.config(w["config"])
        tr = spec.traffic(w["traffic"])
        assert spec.driver(tr["kind"]).run
        assert spec.limits(w["name"])
        assert cfg["chips"] == w["chips"]
        for kind in ("end_to_end", "per_layer"):
            for m in spec.metrics_for(w["name"], kind):
                if kind == "per_layer":
                    assert callable(spec.metric_reader(m["name"]))
        names = [m["name"] for m in spec.metrics_for(w["name"],
                                                     "end_to_end")]
        assert "setup_s" in names and len(names) >= 2
        assert spec.metrics_for(w["name"], "per_layer")


def test_unknown_device_kind_is_an_error():
    assert spec.peaks("TPU v5 lite")["flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        spec.peaks("TPU v9 imaginary")


@pytest.mark.parametrize("name", CONFIGS)
def test_work_count_matches_the_published_equation(name):
    cfg = spec.load_json(spec.HERE / "configs" / f"{name}.json")
    # binary operators of the equation as written: + - * between operands
    ops = len(re.findall(r"(?<=[\w)])\s*[-+*/]\s*(?=[\w(])", cfg["equation"]))
    assert ops == cfg["ops_per_update"] == HAND_COUNT[cfg["stencil"]]
    assert 4 * (2 * cfg["state_fields"] + cfg["aux_fields"]) == \
        HAND_BYTES[cfg["stencil"]]


def test_a_cell_added_as_files_is_found_without_edits(tmp_path, monkeypatch):
    root = tmp_path / "checkout"
    shutil.copytree(spec.HERE, root / "perfbench")
    bench = json.loads((spec.ROOT / "BENCHMARK.json").read_text())
    pb = root / "perfbench"
    cfg = json.loads((pb / "configs" / "diffusion3d-f32.json").read_text())
    (pb / "configs" / "diffusion3d-f32-small.json").write_text(
        json.dumps({**cfg, "name": "diffusion3d-f32-small",
                    "backend": "pallas_interpret"}))
    (pb / "traffic" / "solve_tiny.json").write_text(json.dumps(
        {"kind": "solve", "grid": [16, 16, 128], "iters_per_chunk": 4}))
    (pb / "limits" / "diffusion3d.tiny.json").write_text(json.dumps(
        {"max_rel_err": {"limit": 1e-5}}))
    (pb / "metrics" / "window.chunks.py").write_text(
        "def read(cell):\n    return cell.host.get('chunks')\n")
    bench["configs"].append({"name": "diffusion3d-f32-small", "source": "x",
                             "file": "perfbench/configs/"
                             "diffusion3d-f32-small.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "diffusion3d.tiny",
                               "config": "diffusion3d-f32-small",
                               "traffic": "solve_tiny", "chips": 1,
                               "why": "x"})
    bench["per_layer"].append({"name": "window.chunks", "unit": "chunks",
                               "better": "higher", "source": "host_clock",
                               "layer": "x", "moves": "gcells_per_s",
                               "workloads": ["diffusion3d.tiny"]})
    for m in bench["end_to_end"]:
        if m["name"] == "gcells_per_s":
            m["workloads"].append("diffusion3d.tiny")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    monkeypatch.setattr(spec, "HERE", pb)
    monkeypatch.setattr(spec, "ROOT", root)
    assert [m["name"] for m in spec.metrics_for("diffusion3d.tiny",
                                                "per_layer")] == \
        ["plan.plan_s", "window.chunks"]
    from perfbench import run
    cell = run.Cell("diffusion3d.tiny", 7, 0.2, False)
    res = run.run_cell(cell)
    assert res["correct"], res
    assert set(res["metrics"]) == {"gcells_per_s", "setup_s"}
    assert spec.metric_reader("window.chunks")(cell) == res["attempted"]


def test_open_loop_schedule_is_a_function_of_the_seed():
    tr = spec.traffic("serve_1024sq")
    big = 2 ** 33 + 12345
    a = generate.open_loop(tr, big, 20.0)
    b = generate.open_loop(tr, big, 20.0)
    for k in ("due_s", "iters", "pool", "sample"):
        np.testing.assert_array_equal(a[k], b[k])
    c = generate.open_loop(tr, big + 1, 20.0)
    assert not np.array_equal(a["iters"], c["iters"])
    # every seed asks for the same work, in another order
    assert Counter(a["iters"].tolist()) == Counter(c["iters"].tolist())
    np.testing.assert_allclose(np.sort(np.diff(a["due_s"])),
                               np.sort(np.diff(c["due_s"])), rtol=0,
                               atol=np.max(np.diff(a["due_s"])))
    assert a["due_s"][-1] == pytest.approx(c["due_s"][-1], rel=0.2)
    n = len(a["due_s"])
    assert n == round(tr["rate_per_s"] * 20.0)
    assert a["due_s"][-1] == pytest.approx(20.0, rel=0.15)
    # the compared sample holds every iteration count, the longest included
    assert Counter(a["iters"][a["sample"]].tolist()) == {
        it: tr["sample_per_iters"] for it in tr["iters"]}


def test_device_inputs_are_a_function_of_the_seed():
    from perfbench.drivers import solve
    import jax
    cfg = spec.config("hotspot2d-f32")
    one = jax.sharding.SingleDeviceSharding(jax.devices()[0])
    x1, a1 = solve._inputs(cfg, (8, 128), 2 ** 32 + 3, one)
    x2, a2 = solve._inputs(cfg, (8, 128), 2 ** 32 + 3, one)
    x3, _ = solve._inputs(cfg, (8, 128), 2 ** 32 + 4, one)
    np.testing.assert_array_equal(np.asarray(x1), np.asarray(x2))
    np.testing.assert_array_equal(np.asarray(a1), np.asarray(a2))
    assert not np.array_equal(np.asarray(x1), np.asarray(x3))
    lo, hi = cfg["inputs"]["state"]
    assert lo <= float(np.min(x1)) and float(np.max(x1)) <= hi


@pytest.mark.parametrize("name", CONFIGS)
def test_reference_agrees_with_the_programs_oracle(name):
    """A witness for the reference: at a small size on the CPU it gives
    what the program's own unblocked oracle gives for the same update."""
    import jax.numpy as jnp
    from perfbench import reference
    from repro.api import RunConfig, StencilProblem, plan
    cfg = spec.load_json(spec.HERE / "configs" / f"{name}.json")
    shape = (12, 10, 9) if cfg["stencil"] == "diffusion3d" else (12, 9)
    x = jnp.linspace(0.5, 2.0, int(np.prod(shape)),
                     dtype=jnp.float32).reshape(shape)
    aux = (x[::-1] * 0.05).reshape(shape) if cfg["aux_fields"] else None
    want = reference.run(cfg, x, 17, aux)
    p = plan(StencilProblem(cfg["stencil"], shape), RunConfig(
        backend="reference"))
    got = p.run(x, 17, dict(cfg["coefficients"]), aux=aux)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=0)
