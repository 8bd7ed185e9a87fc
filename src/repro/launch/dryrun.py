import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# ^ MUST be the very first two lines: jax locks the device count on first
# init, and the production meshes below need 512 placeholder devices.
# Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

# ruff: noqa: E402
import argparse
import json
import math
import subprocess
import sys
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import (ARCH_IDS, SHAPES, STENCIL_IDS, get_config,
                           input_specs, shape_applicable)
from repro.core import STENCILS, autotune
from repro.core.distributed import build_distributed_fn
from repro.launch import hlo_analysis
from repro.launch.mesh import make_production_mesh
from repro.models import (cache_axes, init_params, make_decode_caches,
                          param_axes)
from repro.optim import adamw_init
from repro.optim.adamw import AdamWConfig
from repro.parallel import use_sharding_rules
from repro.parallel.sharding import default_rules, resolve_spec
from repro.programs import chain_dag
from repro.train import make_decode_fn, make_prefill_fn, make_train_step

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "results", "dryrun")

# hardware constants (per chip) — DESIGN.md §7
PEAK_BF16 = 197e12
HBM_BW = 819e9
ICI_BW = 50e9

MICROBATCHES = 8
# NOTE (measured, EXPERIMENTS.md §Dry-run): raising microbatches to 32 for
# the >=70B single-pod train cells shrinks peak memory 37.9->24.5 GiB but
# multiplies per-layer FSDP weight gathers 4x (t_collective 119->492 s,
# fraction 0.199->0.018) — the right remedy for those two cells is the
# second pod (multi-pod FSDP), not deeper microbatching.

# stencil app cells (the paper's own benchmarks, spatially distributed)
STENCIL_DIMS = {
    "diffusion2d": (65536, 65536),
    "hotspot2d": (65536, 65536),
    "diffusion3d": (1024, 4096, 4096),
    "hotspot3d": (1024, 4096, 4096),
}
STENCIL_ITERS = 64


def _tree_with_shardings(struct_tree, axes_tree, mesh, rules):
    # Axes tree leads the map (its leaves are always tuples); the struct tree
    # may carry None leaves (e.g. AdamW master copies of f32 params), which a
    # struct-led map would treat as structural-empty and fail on.
    def one(ax, leaf):
        if leaf is None:
            return None
        spec = resolve_spec(leaf.shape, ax, mesh, rules)
        return jax.ShapeDtypeStruct(leaf.shape, leaf.dtype,
                                    sharding=NamedSharding(mesh, spec))
    return jax.tree.map(one, axes_tree, struct_tree,
                        is_leaf=lambda x: type(x) is tuple)


def _shardings_of(struct_tree):
    return jax.tree.map(lambda s: s.sharding, struct_tree,
                        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))


def _adamw_axes(p_axes):
    from repro.optim.adamw import AdamWState
    return AdamWState(step=(), m=p_axes, v=p_axes, master=p_axes)


def build_cell(arch: str, shape: str, multi_pod: bool,
               attn_impl: str | None = None):
    """Returns (jitted_fn, example_args) for the cell — ready to .lower()."""
    import dataclasses as _dc
    mesh = make_production_mesh(multi_pod=multi_pod)
    cfg = get_config(arch)
    if attn_impl:
        cfg = _dc.replace(cfg, attn_impl=attn_impl)
    rules = default_rules(multi_pod=multi_pod,
                          fsdp_over_pod=cfg.n_params > 5e10)
    info = SHAPES[shape]
    if shape == "long_500k":
        # 524288-cell cache / state shards over every mesh axis; batch=1
        rules["kv_seq"] = list(mesh.axis_names)
        rules["batch"] = None

    with use_sharding_rules(mesh, rules):
        params_struct = jax.eval_shape(
            lambda: init_params(jax.random.PRNGKey(0), cfg))
        p_axes = param_axes(cfg)
        params_struct = _tree_with_shardings(params_struct, p_axes, mesh,
                                             rules)
        batch = input_specs(cfg, shape, mesh=mesh, rules=rules)

        if info["kind"] == "train":
            opt_struct = jax.eval_shape(adamw_init, params_struct)
            opt_struct = _tree_with_shardings(opt_struct, _adamw_axes(p_axes),
                                              mesh, rules)
            step = make_train_step(cfg, AdamWConfig(total_steps=1000),
                                   microbatches=MICROBATCHES)
            fn = jax.jit(step, donate_argnums=(0, 1),
                         out_shardings=(_shardings_of(params_struct),
                                        _shardings_of(opt_struct), None))
            args = (params_struct, opt_struct, batch)
        elif info["kind"] == "prefill":
            caches_struct = jax.eval_shape(
                lambda: make_decode_caches(cfg, info["batch"], info["seq"]))
            caches_struct = _tree_with_shardings(caches_struct,
                                                 cache_axes(cfg), mesh, rules)
            fn = jax.jit(make_prefill_fn(cfg, info["seq"]),
                         out_shardings=(None, _shardings_of(caches_struct),
                                        None))
            args = (params_struct, batch)
        else:   # decode
            caches_struct = jax.eval_shape(
                lambda: make_decode_caches(cfg, info["batch"], info["seq"]))
            caches_struct = _tree_with_shardings(caches_struct,
                                                 cache_axes(cfg), mesh, rules)
            decode = make_decode_fn(cfg)
            fn = jax.jit(decode, donate_argnums=(2,),
                         out_shardings=(None, _shardings_of(caches_struct)))
            memory = batch.pop("memory", None)
            args = (params_struct, batch["tokens"], caches_struct, memory)
        return mesh, cfg, _Tracable(fn, mesh, rules), args


class _Tracable:
    """jit wrapper that re-enters the sharding-rules context at trace time.

    ``logical_shard`` reads thread-local rules; tracing (``.lower()``)
    happens after ``build_cell`` returns, so without this every interior
    ``with_sharding_constraint`` in the model would silently be a no-op —
    XLA then loses batch sharding through gather/scan boundaries and
    replicates activations (measured: 14x traffic inflation on
    granite train_4k; see EXPERIMENTS.md §Perf iteration 1).
    """

    def __init__(self, fn, mesh, rules):
        self._fn, self._mesh, self._rules = fn, mesh, rules

    def lower(self, *args, **kw):
        with use_sharding_rules(self._mesh, self._rules):
            return self._fn.lower(*args, **kw)

    def __call__(self, *args, **kw):
        with use_sharding_rules(self._mesh, self._rules):
            return self._fn(*args, **kw)


def build_stencil_cell(name: str, multi_pod: bool):
    mesh = make_production_mesh(multi_pod=multi_pod)
    st = STENCILS[name]
    dims = STENCIL_DIMS[name]
    names = mesh.axis_names
    if len(dims) == 2:
        axis_map = ((names[:-1]), (names[-1],))
    else:
        axis_map = ((names[:-1]), (names[-1],), None)
    # autotune block geometry on the local shard with the perf model
    from repro.core.distributed import shard_extents
    local = shard_extents(dims, tuple(tuple(a) if a else None
                                      for a in axis_map), mesh)
    cand = autotune(st, local, STENCIL_ITERS)
    best = cand[0]
    fn = build_distributed_fn(chain_dag(((st, None),)), dims, STENCIL_ITERS,
                              best.geom.par_time, best.geom.bsize, mesh,
                              axis_map)
    from repro.core.distributed import partition_spec
    spec = partition_spec(tuple(tuple(a) if a else None for a in axis_map))
    sh = NamedSharding(mesh, spec)
    g = jax.ShapeDtypeStruct(dims, jnp.float32, sharding=sh)
    aux = (jax.ShapeDtypeStruct(dims, jnp.float32, sharding=sh)
           if st.has_aux else jax.ShapeDtypeStruct((), jnp.float32))
    coeffs = ({k: jax.ShapeDtypeStruct((), jnp.float32)
               for k in st.coeff_names},)
    return mesh, st, fn, (g, aux, coeffs), best


def model_flops(cfg, shape: str) -> float:
    """Analytic MODEL_FLOPS (6·N·D train / 2·N·D inference; MoE: N_active)."""
    info = SHAPES[shape]
    n = cfg.n_active_params
    tokens = info["batch"] * (info["seq"] if info["kind"] != "decode" else 1)
    mult = 6 if info["kind"] == "train" else 2
    return mult * n * tokens


def run_cell(arch: str, shape: str, mesh_kind: str,
             variant: str = "baseline") -> dict:
    """variant: 'baseline' = paper-faithful XLA program; 'optimized' =
    the flash-attention stub billed at its DMA schedule (LM cells only:
    stencil cells always compile the real streaming kernel)."""
    multi_pod = mesh_kind == "multi"
    t0 = time.time()
    result = {"arch": arch, "shape": shape, "mesh": mesh_kind,
              "variant": variant}
    opt = variant == "optimized"
    if arch in STENCIL_IDS:
        mesh, st, fn, args, best = build_stencil_cell(arch, multi_pod)
        result["autotuned"] = {"bsize": best.geom.bsize,
                               "par_time": best.geom.par_time,
                               "predicted_gflops": best.gflops / 1e9,
                               "bound": best.bound}
        cfg = None
    else:
        cfg = get_config(arch)
        skip = shape_applicable(cfg, shape)
        if skip:
            result["skipped"] = skip
            return result
        mesh, cfg, fn, args = build_cell(arch, shape, multi_pod,
                                         attn_impl="stub" if opt else None)

    n_dev = mesh.devices.size
    t1 = time.time()
    lowered = fn.lower(*args)
    result["lower_s"] = round(time.time() - t1, 2)
    t2 = time.time()
    compiled = lowered.compile()
    result["compile_s"] = round(time.time() - t2, 2)

    ma = compiled.memory_analysis()
    result["memory"] = {
        "argument_bytes": ma.argument_size_in_bytes,
        "output_bytes": ma.output_size_in_bytes,
        "temp_bytes": ma.temp_size_in_bytes,
        "alias_bytes": ma.alias_size_in_bytes,
        "peak_per_device_gib": round(
            (ma.argument_size_in_bytes + ma.output_size_in_bytes
             + ma.temp_size_in_bytes - ma.alias_size_in_bytes) / 2**30, 3),
    }
    ca = compiled.cost_analysis()
    if isinstance(ca, list):
        ca = ca[0]
    result["xla_cost"] = {"flops_body_once": ca.get("flops", 0.0),
                          "bytes_body_once": ca.get("bytes accessed", 0.0)}

    hlo = compiled.as_text()
    an = hlo_analysis.analyze(hlo)
    result["hlo"] = an.as_dict()
    result["hlo_size"] = len(hlo)

    # --- roofline terms (per device == per chip; analyzer is per-device) ---
    t_compute = an.flops / PEAK_BF16
    t_memory = an.hbm_bytes / HBM_BW
    t_collective = an.coll_bytes / ICI_BW
    dominant = max((t_compute, "compute"), (t_memory, "memory"),
                   (t_collective, "collective"))[1]
    result["roofline"] = {
        "t_compute_s": t_compute, "t_memory_s": t_memory,
        "t_collective_s": t_collective, "dominant": dominant,
        "n_devices": n_dev,
    }
    if cfg is not None:
        mf = model_flops(cfg, shape)
        result["roofline"]["model_flops_total"] = mf
        result["roofline"]["model_flops_per_dev"] = mf / n_dev
        result["roofline"]["useful_ratio"] = (
            mf / n_dev / an.flops if an.flops else 0.0)
        # roofline fraction: useful model flops per device over peak, against
        # the bound set by the dominant term
        t_bound = max(t_compute, t_memory, t_collective)
        result["roofline"]["roofline_fraction"] = (
            (mf / n_dev / PEAK_BF16) / t_bound if t_bound else 0.0)
    result["total_s"] = round(time.time() - t0, 2)
    return result


def cell_path(arch, shape, mesh_kind, variant="baseline"):
    os.makedirs(RESULTS_DIR, exist_ok=True)
    return os.path.join(
        RESULTS_DIR, f"{arch}__{shape}__{mesh_kind}__{variant}.json")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single", choices=["single", "multi"])
    ap.add_argument("--variant", default="baseline",
                    choices=["baseline", "optimized"])
    ap.add_argument("--all", action="store_true",
                    help="run every cell in subprocesses (cached)")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--timeout", type=int, default=2400)
    args = ap.parse_args()

    if args.all:
        cells = []
        for variant in ("baseline", "optimized"):
            for arch in ARCH_IDS:
                for shape in SHAPES:
                    for mesh_kind in ("single", "multi"):
                        cells.append((arch, shape, mesh_kind, variant))
        # stencil cells run the real kernel: one variant
        for name in STENCIL_IDS:
            for mesh_kind in ("single", "multi"):
                cells.append((name, "superstep", mesh_kind, "baseline"))
        todo = [c for c in cells
                if args.force or not os.path.exists(cell_path(*c))]
        print(f"{len(todo)}/{len(cells)} cells to run", flush=True)
        failures = []
        for arch, shape, mesh_kind, variant in todo:
            cmd = [sys.executable, "-m", "repro.launch.dryrun",
                   "--arch", arch, "--shape", shape, "--mesh", mesh_kind,
                   "--variant", variant]
            t0 = time.time()
            r = subprocess.run(cmd, capture_output=True, text=True,
                               timeout=args.timeout,
                               env={**os.environ,
                                    "PYTHONPATH": os.environ.get(
                                        "PYTHONPATH", "src")})
            status = "ok" if r.returncode == 0 else "FAIL"
            print(f"[{status}] {arch} {shape} {mesh_kind} {variant} "
                  f"({time.time()-t0:.0f}s)", flush=True)
            if r.returncode != 0:
                failures.append((arch, shape, mesh_kind, variant))
                print(r.stdout[-2000:])
                print(r.stderr[-4000:])
        print(f"done; {len(failures)} failures: {failures}")
        sys.exit(1 if failures else 0)

    arch, shape, mesh_kind = args.arch, args.shape, args.mesh
    try:
        result = run_cell(arch, shape, mesh_kind, args.variant)
    except Exception:
        traceback.print_exc()
        sys.exit(1)
    path = cell_path(arch, shape, mesh_kind, args.variant)
    with open(path, "w") as f:
        json.dump(result, f, indent=2, default=str)
    print(json.dumps({k: v for k, v in result.items()
                      if k in ("arch", "shape", "mesh", "skipped", "memory",
                               "roofline", "compile_s", "autotuned")},
                     indent=2, default=str))


if __name__ == "__main__":
    main()
