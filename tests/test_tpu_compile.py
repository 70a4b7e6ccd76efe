"""Compile the fused kernels for a described TPU v5e, at real sizes.

Nothing runs: the TPU compiler that ships with jax compiles for a
``v5e:2x2`` topology that is described, not attached, and refuses what
the chip's Mosaic compiler would refuse (unaligned DMA windows, gathers
it cannot lower, VMEM overflow) — the faults interpret mode on the CPU
cannot see.  Every case asserts that the compiled program holds a
Mosaic kernel (``tpu_custom_call``).

The topology is described inside a module-scoped fixture, never while a
module is imported, so every test worker collects the same tests and
only the worker running this file loads the TPU library.
"""
import functools
import json
import math
import re

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro.core import PAPER_PIPELINES, PAPER_STENCILS, CasperEngine
from repro.core import perfmodel as _pm
from repro.core import plan as _plan
from repro.kernels import engine, tune

BOUNDARIES = ("zero", "constant(0.75)", "reflect", "periodic")


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:                      # pragma: no cover
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, *shapes, sharding, dtype=jnp.float32) -> str:
    args = [jax.ShapeDtypeStruct(s, dtype, sharding=sharding)
            for s in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("boundary", BOUNDARIES)
def test_padfree_kernel_compiles(boundary, one_chip):
    spec = PAPER_STENCILS["jacobi2d"].with_boundary(boundary)
    shape, tile = (4096, 4096), (32, 512)
    assert _plan.ghost_strategy_for(spec, shape, 4, 4, tile) == "pad-free"
    fn = functools.partial(engine.stencil_sweep, spec, tile=tile, sweeps=4,
                           interpret=False)
    assert "tpu_custom_call" in _compiled_text(fn, shape, sharding=one_chip)


def test_padded_window_kernel_compiles(one_chip):
    spec = PAPER_STENCILS["blur2d"].with_boundary("reflect")
    shape, tile = (4000, 3000), (32, 256)
    assert _plan.ghost_strategy_for(spec, shape, 4, 2, tile) \
        == "padded-window"
    fn = functools.partial(engine.stencil_sweep, spec, tile=tile, sweeps=2,
                           interpret=False)
    assert "tpu_custom_call" in _compiled_text(fn, shape, sharding=one_chip)


def test_pipeline_kernel_compiles(one_chip):
    pipe = PAPER_PIPELINES["reaction_diffusion2d"]
    fn = functools.partial(engine.stencil_sweep, pipe, tile=(32, 512),
                           sweeps=2, interpret=False)
    assert "tpu_custom_call" in _compiled_text(fn, (4096, 4096),
                                               sharding=one_chip)


@pytest.mark.parametrize("name,shape", [("jacobi2d", (16, 512, 512)),
                                        ("jacobi1d", (4, 2048)),
                                        ("heat3d", (2, 8, 16, 256))])
def test_serving_batch_kernel_compiles(name, shape, one_chip):
    """The vmapped bucket runner behind ``plan.batch_handle`` (a leading
    grid axis of the one kernel; rank 1 maps its rows)."""
    run = _plan.batch_runner(PAPER_STENCILS[name], "pallas", 2, "auto",
                             False)
    text = _compiled_text(lambda gs: run(gs, iters=8), shape,
                          sharding=one_chip)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("name,shape", [("jacobi1d", (1 << 22,)),
                                        ("heat3d", (512, 512, 512))])
def test_engine_run_rank1_and_rank3_compile(name, shape, one_chip):
    eng = CasperEngine(PAPER_STENCILS[name], backend="pallas", sweeps=4,
                       tile="auto", interpret=False)
    text = _compiled_text(lambda g: eng.run(g, iters=8), shape,
                          sharding=one_chip)
    assert "tpu_custom_call" in text
    plan = eng.plan_for(shape, jnp.float32)
    assert plan.ghost_strategy == "pad-free" and not plan.interpret


def test_shard_local_mesh_kernel_compiles(topo):
    """heat3d on a 2x2 mesh of the described chips: deep-halo exchange
    plus the shard-local kernel, the origin traced from axis_index."""
    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("sx", "sy"))
    axes = ("sx", "sy", None)
    eng = CasperEngine(PAPER_STENCILS["heat3d"], backend="pallas",
                       sweeps=4, tile="auto", interpret=False)
    step = eng.distributed_fn(mesh, axes, iters=4)
    arg = jax.ShapeDtypeStruct((1024, 1024, 512), jnp.float32,
                               sharding=NamedSharding(mesh, P(*axes)))
    text = step.lower(arg).compile().as_text()
    assert "tpu_custom_call" in text
    assert "collective-permute" in text


@pytest.mark.parametrize("name,shape", [("jacobi2d", (16384, 16384)),
                                        ("heat3d", (512, 512, 512))])
def test_autotuned_tile_compiles_at_benchmark_size(name, shape, one_chip):
    """The tile ``tune.autotune`` picks for the benchmark's grids
    (float32, zero boundary, sweeps=4) is one Mosaic accepts: aligned
    windows, and a resident set inside the scoped VMEM limit."""
    spec = PAPER_STENCILS[name].with_boundary("zero")
    tile = tune.autotune(spec, shape, sweeps=4).tile
    assert _plan.ghost_strategy_for(spec, shape, 4, 4, tile) == "pad-free"
    fn = functools.partial(engine.stencil_sweep, spec, tile=tile, sweeps=4,
                           interpret=False)
    assert "tpu_custom_call" in _compiled_text(fn, shape, sharding=one_chip)


BENCHMARKED = [("jacobi2d", (16384, 16384)), ("heat3d", (512, 512, 512))]


@pytest.fixture(scope="module")
def benchmarked_solves(one_chip):
    """The 1,000-step solves the benchmark times, compiled once per
    cell: ``{name: (engine, compiled)}``, filled on first use."""
    solves = {}

    def get(name, shape):
        if name not in solves:
            eng = CasperEngine(PAPER_STENCILS[name], backend="pallas",
                               sweeps=4, tile="auto", interpret=False)
            arg = jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
            solves[name] = (eng, jax.jit(lambda g: eng.run(g, iters=1000))
                            .lower(arg).compile())
        return solves[name]
    return get


def _loop_body(text: str) -> str:
    """The HLO text of the body of the compiled program's one ``while``
    loop (the scan of fused blocks)."""
    (name,) = re.findall(r"= .* while\(.*?body=%([\w.\-]+)", text)
    return re.search(r"^%" + re.escape(name) + r" .*?^}$", text,
                     flags=re.M | re.S).group(0)


@pytest.mark.parametrize("name,shape", BENCHMARKED)
def test_benchmarked_runner_carries_the_kernel_tag(name, shape,
                                                   benchmarked_solves):
    """The 1,000-step solves the benchmark times: the compiled kernel op
    is named ``casper_fused`` and its ``kernel_metadata`` states the
    plan's strategy, sweeps and tile, and the grid steps and bytes one
    block takes (each step fetches the aligned pad-free window).  The
    loop runs two fused blocks a step, so two kernel ops carry the
    same tag."""
    eng, compiled = benchmarked_solves(name, shape)
    text = compiled.as_text()
    assert re.search(r"%casper_fused[.\d]* = .* custom-call\(", text)
    tags = [json.loads(m) for m in re.findall(
        r"kernel_metadata=(\{.*?\})\}", text, flags=re.DOTALL)]
    plan = eng.plan_for(shape, jnp.float32)
    steps = math.prod(n // t for n, t in zip(shape, plan.tile))
    window = _pm.fetch_window(plan.tile, plan.deep_halo, 4)
    assert len(tags) == 2
    assert all(tag == {
        "casper": "fused", "strategy": "pad-free", "sweeps": "4",
        "tile": "x".join(map(str, plan.tile)), "grid_steps": str(steps),
        "fetch_bytes": str(steps * math.prod(window) * 4),
        "write_bytes": str(math.prod(shape) * 4)} for tag in tags)


@pytest.mark.parametrize("name,shape", BENCHMARKED)
def test_benchmarked_solve_carries_the_grid_without_a_copy(
        name, shape, benchmarked_solves):
    """The pad-free kernel reads the scan carry in place, so with one
    block a scan step XLA copied the whole grid after every block to
    hand the output back in the carry's buffer.  Two blocks a step
    leave one grid-shaped copy in the program, the input's, outside the
    loop; the loop body runs both kernels; and the temporaries stay at
    one grid."""
    _, compiled = benchmarked_solves(name, shape)
    text = compiled.as_text()
    grid_copy = r"= f32\[" + ",".join(map(str, shape)) + r"\][^ ]* copy\("
    assert len(re.findall(grid_copy, text)) == 1
    body = _loop_body(text)
    assert not re.search(grid_copy, body)
    assert len(re.findall(r"%casper_fused[.\d]* = .* custom-call\(",
                          body)) == 2
    grid_bytes = math.prod(shape) * 4
    assert compiled.memory_analysis().temp_size_in_bytes \
        <= grid_bytes + 2**20


@pytest.fixture(scope="module")
def mesh_cell(topo):
    """The mesh cell's 2x2 mesh of described chips and its 8 GiB grid's
    sharded shape (``bench/configs/heat3d-2k-mesh.json``)."""
    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("sx", "sy"))
    axes = ("sx", "sy", None)
    arg = jax.ShapeDtypeStruct((2048, 2048, 512), jnp.float32,
                               sharding=NamedSharding(mesh, P(*axes)))
    return mesh, axes, arg


@pytest.fixture(scope="module")
def mesh_solve(mesh_cell):
    """The mesh cell's 1,000-step solve, compiled once."""
    mesh, axes, arg = mesh_cell
    eng = CasperEngine(PAPER_STENCILS["heat3d"], backend="pallas", sweeps=4,
                       tile="auto", interpret=False)
    return eng.distributed_fn(mesh, axes, iters=1000).lower(arg).compile()


MESH_SHARD = (1024, 1024, 512)
MESH_WINDOW = (1032, 1032, 640)     # 4 ghosts a side, z rounded to 640


def test_mesh_cell_solve_fits_and_carries_the_mesh_tag(mesh_solve):
    """The mesh cell's 1,000-step solve: one shard-local kernel tagged
    with the 2x2 shards, the 16 MiB a shard receives per block (a
    4-deep 1024x512 face on sx, then a 1032x512 one on sy) and the
    bytes of the window it assembles, and the grid, its output and the
    temporaries inside a chip's HBM."""
    compiled = mesh_solve
    tags = [json.loads(m) for m in re.findall(
        r"kernel_metadata=(\{.*?\})\}", compiled.as_text(), flags=re.DOTALL)]
    assert [(t["strategy"], t["shards"], t["exchange_bytes"],
             t["window_bytes"]) for t in tags] == [
        ("window", "2x2", str((4 * 1024 + 4 * 1032) * 512 * 4),
         str(math.prod(MESH_WINDOW) * 4))]
    # one block a scan step: the kernel reads the exchanged window, not
    # the carry, so there is no carry copy for a second block to remove
    assert len(re.findall(r"%casper_fused[.\d]* = .* custom-call\(",
                          _loop_body(compiled.as_text()))) == 1
    mem = compiled.memory_analysis()
    shard = math.prod(MESH_SHARD) * 4
    assert mem.argument_size_in_bytes == mem.output_size_in_bytes == shard
    assert 2 * shard + mem.temp_size_in_bytes < 12e9


def _instructions(body: str) -> dict[str, tuple[str, tuple, list[str]]]:
    """``{name: (opcode, largest result shape, operand names)}`` of the
    instructions of one HLO computation."""
    out = {}
    for m in re.finditer(r"^\s*(?:ROOT )?%([\w.\-]+) = (.*?) ([\w\-]+)"
                         r"\(([^)]*)\)", body, flags=re.M):
        name, result, opcode, operands = m.groups()
        shapes = [tuple(int(v) for v in dims.split(",") if v)
                  for dims in re.findall(r"[a-z]\w*\[([\d,]*)\]", result)]
        out[name] = (opcode, max(shapes, key=math.prod, default=()),
                     re.findall(r"%([\w.\-]+)", operands))
    return out


def test_mesh_cell_solve_builds_the_window_once_a_block(mesh_solve):
    """The mesh path exchanges faces, not widened blocks, and assembles
    the kernel's window once a block: in the loop body exactly one op,
    neither kernel nor collective, makes the 1032x1032x640 window (one
    pass over the shard); every other op there of a shard's size or
    more writes one received face, under 64 MiB, into that window in
    place; and the temporaries hold one window and at most 64 MiB
    beside it, so no write copies the window."""
    body = _instructions(_loop_body(mesh_solve.as_text()))
    shard = math.prod(MESH_SHARD)
    aliases = ("parameter", "get-tuple-element", "tuple", "bitcast")
    big = {n: op for n, (op, shape, _) in body.items()
           if math.prod(shape) >= shard and op not in aliases
           and not n.startswith("casper_fused")}
    builds = [n for n, op in big.items() if op != "dynamic-update-slice"]
    assert len(builds) == 1, big
    (build,) = builds
    assert body[build][1] == MESH_WINDOW
    assert "collective" not in big[build] and big[build] != "custom-call"
    face_writes = set(big) - {build}
    for n in face_writes:
        window, face = body[n][2][:2]
        assert body[n][1] == MESH_WINDOW
        assert window == build or window in face_writes, (n, window)
        assert math.prod(body[face][1]) * 4 < 64 * 2**20, (n, face)
    window_bytes = math.prod(MESH_WINDOW) * 4
    assert mesh_solve.memory_analysis().temp_size_in_bytes \
        <= window_bytes + 64 * 2**20
    tags = [json.loads(m) for m in re.findall(
        r"kernel_metadata=(\{.*?\})\}", mesh_solve.as_text(),
        flags=re.DOTALL)]
    assert [(t["strategy"], t["exchange_bytes"], t["window_bytes"])
            for t in tags] == [("window", "16842752", str(window_bytes))]


def test_mesh_cell_reference_check_fits(mesh_cell):
    """The benchmark's check of the mesh cell (``bench/references/
    heat3d_mesh.py`` stepped 1,000 times over the sharded grid against
    the program's output) holds two grids' shares and two of its own a
    chip, with no padded copy of a shard."""
    import os
    import sys
    from jax import lax
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from bench import harness
    mesh, axes, arg = mesh_cell
    config = harness.load_config("heat3d-2k-mesh")
    step = harness.load_module("references", config["reference"]).make_step(
        config, mesh, axes)

    def gap(u, got):
        want = lax.fori_loop(0, 1000, lambda _, v: step(v), u)
        return jnp.max(jnp.abs(got - want))
    mem = jax.jit(gap).lower(arg, arg).compile().memory_analysis()
    shard = 1024 * 1024 * 512 * 4
    assert mem.argument_size_in_bytes == 2 * shard
    assert mem.temp_size_in_bytes < 2 * shard + 64 * 2**20
