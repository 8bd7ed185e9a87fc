#!/usr/bin/env python3
"""Bring-up check of the stencil system on a TPU, through its user API.

    python3 chip_smoke.py               # one chip: phases a-d below
    python3 chip_smoke.py --four-chips  # four chips: the distributed phase

One chip, one process, ``backend="pallas"`` planned with
``autotune="model"``, every result checked against the ``reference``
backend (the unblocked oracle of ``kernels/ref.py``) on the same chip
within ``repro.core.precision.tolerance``:

  a. diffusion2d f32 and hotspot2d bf16 at 16384^2, diffusion3d and
     hotspot3d f32 at 448^3: ``plan().run(grid, 1000)``;
  b. ``run_batch`` of 4 hotspot2d f32 4096^2 grids against 4 ``run()``;
  c. the two-field wave DAG program on a periodic 8192^2 grid;
  d. a ``StencilService`` with one pallas bucket (hotspot2d 4096^2,
     ``max_batch`` 8) answering 32 requests of mixed ``iters``.

Each phase prints one JSON line: compile and run seconds, the largest error
and the worst error/limit ratio, and whether the compiled HLO holds the
Pallas kernel (``tpu_custom_call``) — which every pallas phase asserts.
``--four-chips`` runs only diffusion2d f32 at 32768^2 on a 2x2 mesh of the
four devices (``backend="distributed"``) against the reference on one of
them, and prints each device's bytes in use and the collective-permute
count of the compiled program.

Inputs are random, drawn on the device from ``--seed``.  Any failure exits
non-zero; the last line of a passing run is exactly
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
import argparse
import asyncio
import json
import re
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ITERS = 1000
BACKEND = "pallas"
SHAPE_2D, SHAPE_3D = (16384, 16384), (448, 448, 448)
SHAPE_BATCH = SHAPE_SERVE = (4096, 4096)
SHAPE_WAVE = (8192, 8192)
SHAPE_FOUR = (32768, 32768)


class SmokeFailure(RuntimeError):
    """A phase's result or setup is wrong (exits non-zero)."""


def require(ok, what):
    if not ok:
        raise SmokeFailure(what)


def log(phase, **fields):
    print(json.dumps({"phase": phase, **fields}), flush=True)


def timed(fn):
    """(result, seconds) of ``fn()``, finished on the device."""
    import jax
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    return out, time.perf_counter() - t0


def check(got, want, dtype, iters, stages=1):
    """Largest |got - want| and the worst ratio of error to the
    ``precision.tolerance`` limit (<= 1 passes); asserts both are finite,
    the shapes agree and the ratio is within budget."""
    import jax
    import jax.numpy as jnp
    from repro.core import precision
    require(got.shape == want.shape, f"shape {got.shape} != {want.shape}")
    # atol = rtol * scale, the scale being the reference's largest magnitude
    rtol = precision.tolerance(dtype, iters, stages)["rtol"]

    @jax.jit
    def stats(g, w):
        g, w = g.astype(jnp.float32), w.astype(jnp.float32)
        err, mag = jnp.abs(g - w), jnp.abs(w)
        atol = rtol * jnp.max(mag)
        return (jnp.all(jnp.isfinite(g)), atol, jnp.max(err),
                jnp.max(err / (atol + rtol * mag)))

    finite, atol, max_err, ratio = (float(x) for x in stats(got, want))
    require(finite, "non-finite values in the result")
    require(ratio <= 1.0, f"error {max_err} over the limit (rtol {rtol}, "
            f"atol {atol}): {ratio} x")
    return {"max_err": max_err, "rtol": rtol, "atol": atol,
            "err_over_limit": ratio}


def kernel_in_hlo(lowered):
    """Compile ``lowered`` and assert the Pallas kernel is in its HLO."""
    t0 = time.perf_counter()
    text = lowered.compile().as_text()
    found = "tpu_custom_call" in text
    require(found, "no tpu_custom_call in the compiled HLO")
    return {"tpu_custom_call": found,
            "hlo_compile_s": time.perf_counter() - t0}


def bytes_in_use(device):
    return device.memory_stats()["bytes_in_use"]


def inputs(seed, shape, dtype, needs_aux):
    import jax
    from repro.data.pipeline import make_stencil_inputs
    grid, aux = jax.jit(make_stencil_inputs, static_argnums=(1, 2))(
        jax.random.PRNGKey(seed), shape, needs_aux)
    grid = grid.astype(dtype)
    return grid, None if aux is None else aux.astype(dtype)


def phase_single(seed):
    from repro.api import RunConfig, StencilProblem, plan
    for name, shape, dtype in (("diffusion2d", SHAPE_2D, "float32"),
                               ("hotspot2d", SHAPE_2D, "bfloat16"),
                               ("diffusion3d", SHAPE_3D, "float32"),
                               ("hotspot3d", SHAPE_3D, "float32")):
        problem = StencilProblem(name, shape, dtype=dtype)
        p = plan(problem, RunConfig(backend=BACKEND, autotune="model"))
        grid, aux = inputs(seed, shape, dtype, problem.needs_aux)
        hlo = kernel_in_hlo(p.lower(grid, aux=aux))
        _, first_s = timed(lambda: p.run(grid, 1, aux=aux))
        got, run_s = timed(lambda: p.run(grid, ITERS, aux=aux))
        ref = plan(problem, RunConfig(backend="reference"))
        want, ref_s = timed(lambda: ref.run(grid, ITERS, aux=aux))
        g = p.geometry
        log(f"a.{name}", shape=shape, dtype=dtype, iters=ITERS,
            bsize=g.bsize, par_time=g.par_time, par_vec=g.par_vec,
            compile_s=hlo["hlo_compile_s"], first_call_s=first_s,
            run_s=run_s, reference_s=ref_s,
            tpu_custom_call=hlo["tpu_custom_call"],
            **check(got, want, dtype, ITERS))
        del grid, aux, got, want


def phase_batch(seed):
    import jax.numpy as jnp
    from repro.api import RunConfig, StencilProblem, plan
    shape, iters, b = SHAPE_BATCH, ITERS // 2, 4
    problem = StencilProblem("hotspot2d", shape)
    p = plan(problem, RunConfig(backend=BACKEND, autotune="model"))
    grids = jnp.stack([inputs(seed + i, shape, "float32", False)[0]
                       for i in range(b)])
    _, aux = inputs(seed, shape, "float32", True)
    hlo = kernel_in_hlo(p.lower(grids, aux=aux, batch=True))
    _, first_s = timed(lambda: p.run_batch(grids, 1, aux=aux))
    got, run_s = timed(lambda: p.run_batch(grids, iters, aux=aux))
    want, seq_s = timed(lambda: jnp.stack(
        [p.run(grids[i], iters, aux=aux) for i in range(b)]))
    log("b.run_batch", shape=shape, batch=b, iters=iters,
        compile_s=hlo["hlo_compile_s"], first_call_s=first_s, run_s=run_s,
        sequential_s=seq_s, tpu_custom_call=hlo["tpu_custom_call"],
        **check(got, want, "float32", iters))


def wave_program():
    """The README's two-field leapfrog wave program."""
    from repro.api import StencilProgram, StencilStage
    from repro.core.stencils import make_combine, make_star
    lap = StencilStage(make_star(2, 1), name="lapu", inputs=("u",),
                       coeffs={"c0": -4.0, "c_0_-1": 1.0, "c_0_1": 1.0,
                               "c_1_-1": 1.0, "c_1_1": 1.0})
    unext = StencilStage(make_combine(2, 3), name="unext",
                         inputs=("u", "u_prev", "lapu"),
                         coeffs={"w0": 2.0, "w1": -1.0, "w2": 0.16})
    return StencilProgram((lap, unext), fields=("u", "u_prev"),
                          updates={"u": "unext", "u_prev": "u"})


def phase_dag(seed):
    import jax.numpy as jnp
    from repro.api import RunConfig, StencilProblem, plan
    shape = SHAPE_WAVE
    problem = StencilProblem(wave_program(), shape, boundary="periodic")
    p = plan(problem, RunConfig(backend=BACKEND, autotune="model"))
    u, _ = inputs(seed, shape, "float32", False)
    state = jnp.stack([u, u])
    hlo = kernel_in_hlo(p.lower(state))
    _, first_s = timed(lambda: p.run(state, 1))
    got, run_s = timed(lambda: p.run(state, ITERS))
    ref = plan(problem, RunConfig(backend="reference"))
    want, ref_s = timed(lambda: ref.run(state, ITERS))
    g = p.geometry
    log("c.wave_dag", shape=shape, iters=ITERS, bc="periodic",
        bsize=g.bsize, par_time=g.par_time, par_vec=g.par_vec,
        compile_s=hlo["hlo_compile_s"], first_call_s=first_s, run_s=run_s,
        reference_s=ref_s, tpu_custom_call=hlo["tpu_custom_call"],
        **check(got, want, "float32", ITERS, stages=2))


def phase_serve(seed):
    import jax.numpy as jnp
    from repro.api import RunConfig, StencilProblem, plan
    from repro.serve import StencilRequest, from_config
    shape, n, max_batch = SHAPE_SERVE, 32, 8
    iters_mix = (10, 50, 100, 200)
    problem = StencilProblem("hotspot2d", shape)
    run = {"backend": BACKEND, "autotune": "model"}
    grids = [inputs(seed + i, shape, "float32", False)[0] for i in range(n)]
    _, power = inputs(seed, shape, "float32", True)

    async def serve():
        t0 = time.perf_counter()
        service = await from_config({"buckets": [{
            "problem": problem, "run": run, "max_batch": max_batch,
            "max_wait_ms": 5.0, "queue_cap": 2 * n}]})
        boot_s = time.perf_counter() - t0
        async with service:
            t0 = time.perf_counter()
            results = await asyncio.gather(*[
                service.submit(StencilRequest(
                    problem, grids[i], iters_mix[i % len(iters_mix)],
                    aux=power)) for i in range(n)])
            serve_s = time.perf_counter() - t0
            snap = service.snapshot()
        return results, snap, boot_s, serve_s

    results, snap, boot_s, serve_s = asyncio.run(serve())
    require(snap["completed"] == n, f"{snap['completed']} of {n} completed")
    require(snap["failed_total"] == 0 and snap["rejected_total"] == 0,
            f"failed {snap['failed_total']}, rejected "
            f"{snap['rejected_total']}")
    # the bucket's plan shares the process-level executable cache: this is
    # the batch executable the service launched
    p = plan(problem, RunConfig(**run))
    hlo = kernel_in_hlo(p.lower(jnp.stack(grids[:max_batch]), aux=power,
                                batch=True))
    ref = plan(problem, RunConfig(backend="reference"))
    worst = {"max_err": 0.0, "err_over_limit": 0.0}
    for i, res in enumerate(results):
        c = check(res.grid, ref.run(grids[i], res.iters, aux=power),
                  "float32", res.iters)
        worst = {k: max(worst[k], c[k]) for k in worst}
    log("d.serve", shape=shape, requests=n, max_batch=max_batch,
        iters_mix=iters_mix, completed=snap["completed"],
        failed_total=snap["failed_total"],
        rejected_total=snap["rejected_total"], batches=snap["batches"],
        batch_fill=snap["batch_fill"], compile_s=boot_s, run_s=serve_s,
        latency_ms=snap["latency_ms"], tpu_custom_call=hlo["tpu_custom_call"],
        **worst)


def phase_four_chips(seed):
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec
    from repro.api import RunConfig, StencilProblem, plan
    from repro.kernels.ref import oracle_run
    devs = jax.devices()
    require(len(devs) == 4, f"--four-chips needs 4 devices, found {len(devs)}")
    shape, iters = SHAPE_FOUR, ITERS // 10
    problem = StencilProblem("diffusion2d", shape)
    mesh = jax.make_mesh((2, 2), ("x", "y"), devices=devs,
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    # the reference first, on device 0 alone: the oracle the reference
    # backend runs, with its input donated — input, output and the loop's
    # temporaries of a 4 GiB grid fill a 16 GB chip otherwise
    st, bc = problem.exec_stages[0]
    coeffs = problem.resolve_coeffs(dtype=jnp.float32)[0]
    oracle = jax.jit(lambda g, n: oracle_run(st, g, coeffs, n, bc=bc),
                     donate_argnums=0)
    with jax.default_device(devs[0]):
        grid0, _ = inputs(seed, shape, "float32", False)
        want, ref_s = timed(lambda: oracle(grid0, iters))
    del grid0
    # then spread over the mesh, to compare shard by shard
    sharding = NamedSharding(mesh, PartitionSpec("x", "y"))
    want = jax.device_put(want, sharding)
    grid = jax.jit(lambda k: inputs(seed, shape, "float32", False)[0],
                   out_shardings=sharding)(0)
    p = plan(problem, RunConfig(backend="distributed", mesh=mesh,
                                axis_map=(("x",), ("y",)),
                                autotune="model"))
    t0 = time.perf_counter()
    text = p.lower(grid, iters).compile().as_text()
    compile_s = time.perf_counter() - t0
    permutes = len(re.findall(r"\bcollective-permute(?:-start)?\(", text))
    _, first_s = timed(lambda: p.run(grid, 1))
    got, run_s = timed(lambda: p.run(grid, iters))
    in_use = [bytes_in_use(d) for d in devs]
    require(got.sharding.is_equivalent_to(sharding, 2),
            f"result not sharded over the mesh: {got.sharding}")
    require(permutes > 0, "no collective-permute in the distributed program")
    require("tpu_custom_call" in text,
            "no streaming kernel in the distributed program")
    g = p.geometry
    log("four_chips.distributed", shape=shape, iters=iters, mesh=(2, 2),
        bsize=g.bsize, par_time=g.par_time, compile_s=compile_s,
        first_call_s=first_s, run_s=run_s, reference_s=ref_s,
        par_vec=g.par_vec, bytes_in_use=in_use,
        collective_permutes=permutes,
        **check(got, want, "float32", iters))


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the distributed phase on a 2x2 mesh")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    try:
        from repro.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: the repro package is not next to this script "
              f"({e})", file=sys.stderr)
        return 2
    cache = enable_compile_cache()
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {devs[0].platform}); this "
              "check runs only on the chip", file=sys.stderr)
        return 1
    log("start", platform=devs[0].platform, kind=devs[0].device_kind,
        count=len(devs), jax=jax.__version__, compile_cache=cache)
    if args.four_chips:
        phase_four_chips(args.seed)
    else:
        for phase in (phase_single, phase_batch, phase_dag, phase_serve):
            phase(args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
