"""Measured autotuning + the persistent schedule cache.

Acceptance surface of the measured-tuning subsystem: ``autotune="measure"``
selects a schedule by measured time and records per-candidate measured
seconds + model accuracy in ``StencilPlan.candidates``; a second ``plan()``
with the same key is served from the persistent cache without re-timing; a
code-version salt change invalidates the cache; ``cache=False`` disables
persistence; the measured winner still computes correct results.
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import (RunConfig, StencilProblem, TunedCandidate, plan,
                       tune)
from repro.api import schedule_cache, tuner
from repro.kernels.ref import oracle_run
from repro.core import STENCILS, default_coeffs


def _cfg(cache, **kw):
    kw.setdefault("backend", "engine")
    kw.setdefault("autotune", "measure")
    kw.setdefault("iters_hint", 8)
    kw.setdefault("tune_top_k", 2)
    kw.setdefault("tune_warmup", 1)
    kw.setdefault("tune_repeats", 2)
    return RunConfig(cache=cache, **kw)


def _spy(monkeypatch):
    """Count (and pass through) measured-tuner invocations."""
    calls = []
    real = tuner.measure_candidates

    def counting(problem, config, predictions):
        calls.append(problem.stencil.name)
        return real(problem, config, predictions)

    monkeypatch.setattr(tuner, "measure_candidates", counting)
    return calls


# --- measured selection (acceptance criterion) --------------------------------

@pytest.mark.parametrize("name,dims", [
    ("diffusion2d", (64, 512)),
    ("hotspot3d", (12, 72, 72)),
])
def test_measure_selects_by_time_and_records(name, dims, tmp_path):
    p = plan(StencilProblem(name, dims), _cfg(str(tmp_path / "s.json")))
    assert not p.tuned_from_cache
    assert len(p.candidates) == 2
    for c in p.candidates:
        assert isinstance(c, TunedCandidate)
        assert c.measured_s > 0 and c.measured_run_time > 0
        assert c.model_accuracy > 0
        assert not c.from_cache
    per_iter = [c.s_per_iter for c in p.candidates]
    assert per_iter == sorted(per_iter), \
        "candidates ranked by amortized per-iteration measured time"
    assert p.geometry.par_time == p.candidates[0].geom.par_time
    assert p.geometry.bsize == p.candidates[0].geom.bsize


def test_measured_winner_runs_correctly(tmp_path):
    st = STENCILS["diffusion2d"]
    g = jax.random.uniform(jax.random.PRNGKey(3), (48, 320), jnp.float32,
                           0.5, 2.0)
    c = default_coeffs(st)
    p = plan(StencilProblem("diffusion2d", (48, 320)),
             _cfg(str(tmp_path / "s.json")))
    np.testing.assert_allclose(np.asarray(p.run(g, 5, c)),
                               np.asarray(oracle_run(st, g, c, 5)),
                               rtol=2e-5, atol=2e-5)


# --- cache behavior (acceptance criterion) ------------------------------------

def test_cache_hit_skips_retiming(tmp_path, monkeypatch):
    calls = _spy(monkeypatch)
    cfg = _cfg(str(tmp_path / "s.json"))
    problem = StencilProblem("diffusion2d", (64, 512))
    p1 = plan(problem, cfg)
    assert calls == ["diffusion2d"] and not p1.tuned_from_cache
    p2 = plan(problem, cfg)
    assert calls == ["diffusion2d"], "second plan() must not re-time"
    assert p2.tuned_from_cache
    assert p2.geometry == p1.geometry
    (cached,) = p2.candidates
    assert cached.from_cache
    assert cached.measured_s == pytest.approx(p1.candidates[0].measured_s)
    assert cached.model_accuracy == pytest.approx(
        p1.candidates[0].model_accuracy)


def test_salt_change_invalidates(tmp_path, monkeypatch):
    calls = _spy(monkeypatch)
    cfg = _cfg(str(tmp_path / "s.json"))
    problem = StencilProblem("diffusion2d", (64, 512))
    monkeypatch.setattr(schedule_cache, "code_version_salt", lambda: "aaaa")
    plan(problem, cfg)
    assert plan(problem, cfg).tuned_from_cache and len(calls) == 1
    # editing kernel sources changes the salt -> the cached winner is stale
    monkeypatch.setattr(schedule_cache, "code_version_salt", lambda: "bbbb")
    p = plan(problem, cfg)
    assert not p.tuned_from_cache and len(calls) == 2


def test_key_differs_per_backend_device_and_pin(tmp_path):
    problem = StencilProblem("diffusion2d", (64, 512))
    dev = RunConfig().resolved_device()
    base = schedule_cache.schedule_key(problem, _cfg(None), dev, 1, None)
    for other_cfg, other_dev in [
            (_cfg(None, backend="pallas_interpret"), dev),
            (_cfg(None, par_time=4), dev),
            (_cfg(None, bsize=256), dev),
            (_cfg(None), RunConfig(device="tpu_v5p").resolved_device())]:
        assert schedule_cache.schedule_key(
            problem, other_cfg, other_dev, 1, None) != base
    # iters_hint deliberately does NOT key the cache (per-super-step timing)
    assert schedule_cache.schedule_key(
        problem, _cfg(None, iters_hint=999), dev, 1, None) == base
    # interpret-mode timings must never serve compiled plans (or vice versa)
    assert schedule_cache.schedule_key(
        problem, _cfg(None, backend="pallas"), dev, 1, None) != \
        schedule_cache.schedule_key(
            problem, _cfg(None, backend="pallas_interpret"), dev, 1, None)
    # sweep-constraining knobs key the cache: a winner tuned under a loose
    # par_time_max must not be served to (and violate) a tighter one
    assert schedule_cache.schedule_key(
        problem, _cfg(None, par_time_max=8), dev, 1, None) != base
    assert schedule_cache.schedule_key(
        problem, _cfg(None, tune_top_k=8), dev, 1, None) != base


def test_key_fingerprints_user_stencils_beyond_name():
    """Two different stencils under one name must not share a cache entry."""
    from repro.core.stencils import Stencil
    cheap = Stencil("mystencil", 2, 1, 1, 1, 1, False, ("c",),
                    lambda get, c, aux=None: c["c"] * get((0, 0)))
    heavy = Stencil("mystencil", 2, 1, 5, 1, 1, False, ("c",),
                    lambda get, c, aux=None: c["c"] * (
                        get((0, 1)) + get((0, -1)) + get((1, 0))))
    dev = RunConfig().resolved_device()
    keys = [schedule_cache.schedule_key(
        StencilProblem(st, (32, 160)), _cfg(None), dev, 1, None)
        for st in (cheap, heavy)]
    assert keys[0] != keys[1]


def test_unwritable_cache_warns_instead_of_discarding_tune(tmp_path):
    # a regular file as a path component makes mkdir fail even for root
    (tmp_path / "blocker").write_text("")
    bad = tmp_path / "blocker" / "s.json"
    with pytest.warns(RuntimeWarning, match="not persisted"):
        schedule_cache.ScheduleCache(bad).put("k", {"par_time": 2})
    # and plan() itself survives: winner is returned, nothing persisted
    with pytest.warns(RuntimeWarning, match="not persisted"):
        p = plan(StencilProblem("diffusion2d", (64, 512)), _cfg(str(bad)))
    assert p.geometry is not None and not p.tuned_from_cache


def test_mangled_cache_entry_is_a_miss_not_a_crash(tmp_path, monkeypatch):
    calls = _spy(monkeypatch)
    path = str(tmp_path / "s.json")
    cfg = _cfg(path)
    problem = StencilProblem("diffusion2d", (64, 512))
    plan(problem, cfg)
    # hand-edit the (documented human-editable) entry into garbage
    cache = schedule_cache.ScheduleCache(path)
    dev = cfg.resolved_device()
    key = schedule_cache.schedule_key(problem, cfg, dev, 1, None)
    for bad in ({"par_time": "soon", "note": "hand-edited"},
                {"par_time": 0, "bsize": [256], "measured_s": 0.1,
                 "model_accuracy": 1.0},          # ceil(iters/0) would crash
                {"par_time": 2, "bsize": [256, 256], "measured_s": 0.1,
                 "model_accuracy": 1.0}):         # wrong rank for a 2D grid
        cache.put(key, bad)
        n = len(calls)
        p = plan(problem, cfg)
        assert not p.tuned_from_cache and len(calls) == n + 1, \
            f"mangled entry {bad} must fall through to re-tuning"
    assert plan(problem, cfg).tuned_from_cache   # re-tune healed the entry


def test_cache_false_disables_persistence(tmp_path, monkeypatch):
    calls = _spy(monkeypatch)
    monkeypatch.setenv("REPRO_SCHEDULE_CACHE",
                       str(tmp_path / "untouched.json"))
    cfg = _cfg(False)
    problem = StencilProblem("diffusion2d", (64, 512))
    plan(problem, cfg)
    plan(problem, cfg)
    assert len(calls) == 2, "no cache -> every plan re-times"
    assert not (tmp_path / "untouched.json").exists()


def test_default_path_honors_env(monkeypatch, tmp_path):
    monkeypatch.setenv("REPRO_SCHEDULE_CACHE", str(tmp_path / "env.json"))
    assert schedule_cache.default_cache_path() == tmp_path / "env.json"


def test_cache_file_is_versioned_json_and_corruption_safe(tmp_path):
    path = tmp_path / "s.json"
    cache = schedule_cache.ScheduleCache(path)
    assert cache.get("k") is None            # missing file: miss, no crash
    cache.put("k", {"par_time": 4, "bsize": [256], "measured_s": 0.1,
                    "model_accuracy": 1.0})
    data = json.loads(path.read_text())
    assert data["version"] == schedule_cache.CACHE_FORMAT_VERSION
    assert cache.get("k")["par_time"] == 4
    path.write_text("{not json")             # corrupt: miss, then self-heal
    assert cache.get("k") is None
    cache.put("k2", {"par_time": 2})
    assert cache.get("k2")["par_time"] == 2


def test_tune_helper_forces_measure_mode(tmp_path):
    p = tune(StencilProblem("diffusion2d", (64, 512)),
             RunConfig(backend="engine", iters_hint=8, tune_top_k=2,
                       tune_repeats=2),
             cache=str(tmp_path / "s.json"))
    assert p.config.autotune == "measure"
    assert isinstance(p.candidates[0], TunedCandidate)
    # a redundant autotune= override must not crash replace()
    p2 = tune(StencilProblem("diffusion2d", (64, 512)),
              RunConfig(backend="engine", iters_hint=8, tune_top_k=1,
                        tune_repeats=1), autotune="measure",
              cache=str(tmp_path / "s.json"))
    assert p2.config.autotune == "measure"


# --- config surface -----------------------------------------------------------

def test_autotune_bool_aliases():
    assert RunConfig(autotune=True).autotune == "model"
    assert RunConfig(autotune=False).autotune is False
    assert RunConfig(autotune="measure").autotune == "measure"
    with pytest.raises(ValueError, match="autotune"):
        RunConfig(autotune="fastest")


def test_tuning_knob_validation():
    with pytest.raises(ValueError, match="tune_top_k"):
        RunConfig(tune_top_k=0)
    with pytest.raises(ValueError, match="tune_warmup"):
        RunConfig(tune_warmup=-1)
    with pytest.raises(ValueError, match="tune_iters"):
        RunConfig(tune_iters=0)
