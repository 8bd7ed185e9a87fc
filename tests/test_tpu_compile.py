"""Compile the autotuned Pallas kernels for a described TPU v5e.

No chip is attached here: the TPU compiler that ships with jax compiles for
a topology that is only described (``jax.experimental.topologies``), and
refuses what the chip would refuse — unaligned DMA windows, primitives
Mosaic cannot lower, scratch beyond the VMEM limit.  Each case plans a
problem with ``backend="pallas"`` and ``autotune="model"`` exactly as a user
would, lowers the executable ``run()`` dispatches to for one described chip,
compiles it, and checks that the kernel is in the HLO and that the VMEM
estimate autotune pruned with covers the kernel's scratch buffers.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and the test workers
must all collect the same tests.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import SingleDeviceSharding

from repro.api import RunConfig, StencilProblem, StencilStage, plan
from repro.core.precision import sublanes_for
from repro.core.stencils import make_combine, make_star
from repro.kernels.builder import _scratch_shapes
from repro.programs import StencilProgram, chain_dag


def scratch_bytes(dag, geom, dtype) -> int:
    """VMEM bytes of the kernel's scratch buffers as Mosaic tiles them:
    the minor dim padded to 128 lanes, the second-minor to the dtype's
    sublanes."""
    dt = jnp.dtype(dtype)
    sub = sublanes_for(dt.itemsize)
    total = 0
    for buf in _scratch_shapes(dag, geom, dt):
        if buf.memory_space != pltpu.VMEM:
            continue                              # DMA semaphores
        *major, s2, s1 = (1,) * (2 - len(buf.shape)) + tuple(buf.shape)
        total += (int(np.prod(major)) * (-(-s2 // sub) * sub)
                  * (-(-s1 // 128) * 128) * dt.itemsize)
    return total


@pytest.fixture(scope="module")
def topo():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # compiles for a described chip are written to the persistent cache but
    # cannot be read back without one: keep the cache off around them
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _wave():
    lap = StencilStage(make_star(2, 1), name="lapu", inputs=("u",),
                       coeffs={"c0": -4.0, "c_0_-1": 1.0, "c_0_1": 1.0,
                               "c_1_-1": 1.0, "c_1_1": 1.0})
    unext = StencilStage(make_combine(2, 3), name="unext",
                         inputs=("u", "u_prev", "lapu"),
                         coeffs={"w0": 2.0, "w1": -1.0, "w2": 0.16})
    return StencilProgram((lap, unext), fields=("u", "u_prev"),
                          updates={"u": "unext", "u_prev": "u"})


CASES = [(name, shape, dtype, "clamp")
         for name, shape in (("diffusion2d", (16384, 16384)),
                             ("hotspot2d", (16384, 16384)),
                             ("diffusion3d", (448, 448, 448)),
                             ("hotspot3d", (448, 448, 448)))
         for dtype in ("float32", "bfloat16")]
CASES += [("diffusion2d", (4096, 4096), "float32", "reflect"),
          ("hotspot3d", (256, 256, 256), "float32", "periodic"),
          ("wave", (8192, 8192), "float32", "periodic")]


@pytest.mark.parametrize("name,shape,dtype,bc", CASES,
                         ids=[f"{n}-{'x'.join(map(str, s))}-{d}-{b}"
                              for n, s, d, b in CASES])
def test_autotuned_kernel_compiles_for_v5e(one_chip, name, shape, dtype, bc):
    stencil = _wave() if name == "wave" else name
    problem = StencilProblem(stencil, shape, dtype=dtype, boundary=bc)
    p = plan(problem, RunConfig(backend="pallas", autotune="model"))
    geom = p.geometry
    assert geom.align, "the compiled path must plan a tile-aligned geometry"

    def spec(s):
        return jax.ShapeDtypeStruct(s, jnp.dtype(dtype), sharding=one_chip)
    aux = spec(shape) if problem.needs_aux else None
    compiled = p.lower(spec(problem.state_shape), aux=aux).compile()
    assert "tpu_custom_call" in compiled.as_text()

    dag = (problem.exec_dag if problem.is_dag
           else chain_dag(problem.exec_stages))
    assert p.predicted().vmem_bytes >= scratch_bytes(dag, geom, dtype)
    assert p.predicted().vmem_bytes <= p.device.vmem_budget


def _op_names(hlo: str) -> dict:
    """Instruction name -> the ``op_name`` of its metadata ("" if none)."""
    out = {}
    for m in re.finditer(r"^\s*(?:ROOT )?%(\S+) = [^\n]*$", hlo, re.M):
        on = re.search(r'op_name="([^"]*)"', m.group(0))
        out[m.group(1)] = on.group(1) if on else ""
    return out


SOLVE_CELLS = [("hotspot2d", (16384, 16384)), ("diffusion3d", (640, 640, 640))]


@pytest.mark.parametrize("name,shape", SOLVE_CELLS,
                         ids=[f"{n}-{'x'.join(map(str, s))}"
                              for n, s in SOLVE_CELLS])
def test_super_step_phases_carry_their_scopes(one_chip, name, shape):
    """The solve cells' programs as compiled for the chip: the kernel keeps
    the instruction name the benchmark's readers match and sits under
    ``stencil.superstep`` at each of the loop's four launch sites (the first
    super-step, the two of the while body's pair, a trailing odd one); the
    halo refresh writes its padding strips with dynamic-update-slice fusions
    under ``stencil.halo_refresh``, with no gather anywhere and nothing else
    there the size of the carry; the while body holds no copy of the carry
    (the kernel writes into the other carry buffer); the final slice sits
    under ``stencil.unpad``."""
    problem = StencilProblem(name, shape, dtype="float32", boundary="clamp")
    p = plan(problem, RunConfig(backend="pallas", autotune="model"))
    spec = jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
    hlo = p.lower(spec, aux=spec if problem.needs_aux else None) \
        .compile().as_text()
    names = _op_names(hlo)
    kernels = [n for n in names
               if re.fullmatch(r"superstep_chain(\.\d+)?", n)]
    assert len(kernels) == 4
    for k in kernels:
        assert "stencil.superstep/" in names[k]
        kernel_line = re.search(rf"%{re.escape(k)} = [^\n]*", hlo)
        assert 'custom_call_target="tpu_custom_call"' in kernel_line.group(0)
    assert not re.search(r"\bgather\(", hlo)
    assert not any(o.endswith("/gather") for o in names.values())
    # the while body's own instructions, not those inside its fusions
    body = re.search(r"while\([^)]*\), condition=%\S+, body=%([\w.-]+)",
                     hlo).group(1)
    body = re.search(rf"^%{re.escape(body)} [^\n]*\{{\n(.*?)\n\}}", hlo,
                     re.M | re.S).group(1)
    carry = re.search(r"= (f32\[[\d,]+\])\S* custom-call",
                      kernel_line.group(0)).group(1)
    refresh = {m.group(1): m.group(2) for m in re.finditer(
        r"^\s*(?:ROOT )?%(\S+) = (\S+?)\{[^\n]*/stencil\.halo_refresh/",
        body, re.M)}
    strips = [n for n in refresh if "dynamic-update-slice" in n]
    # two per blocked axis, after each of the body's two kernels
    assert len(re.findall(r"^\s*%superstep_chain\.\d+ = ", body, re.M)) == 2
    assert len(strips) == 2 * 2 * (len(shape) - 1)
    assert all(refresh[n] == carry for n in strips)
    assert all(refresh[n] != carry for n in refresh if n not in strips)
    assert not re.search(rf"= {re.escape(carry)}\S* copy(-start)?\(", body)
    assert any("/stencil.unpad/" in o for o in names.values())


def test_mesh_cell_compiles_for_a_2x2_v5e(one_chip, topo):
    """The four-chip Diffusion 2D cell (49152², rows over ``x``, columns
    over ``y``) compiled for a described 2x2 v5e: every shard runs the
    kernel ``superstep_chain`` under ``stencil.superstep``; each super-step
    sends one strip per direction per sharded axis, every
    collective-permute under ``stencil.halo_exchange``; no gather, and no
    concatenate of a whole shard, anywhere in the program; the loop's while
    body runs two kernels, one into each carry buffer, and copies neither;
    and one chip holds it within its 16 GB, at no more than the 9.78 GB a
    loop with a single carry buffer took."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec
    shape = (49152, 49152)
    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("x", "y"))
    p = plan(StencilProblem("diffusion2d", shape),
             RunConfig(backend="distributed", autotune="model", mesh=mesh,
                       axis_map=(("x",), ("y",))))
    assert p.geometry.align and p.n_chips == 4
    spec = jax.ShapeDtypeStruct(shape, jnp.float32, sharding=NamedSharding(
        mesh, PartitionSpec("x", "y")))
    compiled = p.lower(spec).compile()
    hlo = compiled.as_text()
    names = _op_names(hlo)
    kernels = [n for n in names
               if re.fullmatch(r"superstep_chain(\.\d+)?", n)]
    assert len(kernels) == 4
    assert all("stencil.superstep/" in names[k] for k in kernels)
    permutes = [n for n in names if n.startswith("collective-permute")]
    assert permutes
    assert all("/stencil.halo_exchange/" in names[n] for n in permutes)
    body = re.search(r"while\([^)]*\), condition=%\S+, body=%([\w.-]+)",
                     hlo).group(1)
    body = re.search(rf"^%{re.escape(body)} [^\n]*\{{\n(.*?)\n\}}", hlo,
                     re.M | re.S).group(1)
    # four per super-step, two super-steps per pass of the body
    assert len(re.findall(r" collective-permute-start\(", body)) == 8
    assert len(re.findall(r"^\s*%superstep_chain\.\d+ = ", body, re.M)) == 2
    carry = re.search(rf"%{re.escape(kernels[0])} = (f32\[[\d,]+\])",
                      hlo).group(1)
    assert not re.search(rf"= {re.escape(carry)}\S* copy(-start)?\(", body)
    assert not re.search(r"\bgather\(", hlo)
    shard = (shape[0] // 2) * (shape[1] // 2)
    for dims in re.findall(r"= f32\[([\d,]+)\]\S* concatenate\(", hlo):
        assert np.prod([int(d) for d in dims.split(",")]) < shard
    mem = compiled.memory_analysis()
    per_chip = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert per_chip < 16e9, per_chip
    # the peak of the compiler's buffer assignment, which places both carry
    # buffers in one 4.94 GB temp allocation, as a one-buffer loop placed
    # its carry and the kernel's output; ``temp_size_in_bytes`` counts one
    # shard buffer more for this program (7.41 GB) than that allocation
    assert mem.peak_memory_in_bytes <= 9.78e9, mem.peak_memory_in_bytes
