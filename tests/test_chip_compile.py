"""The main path's Pallas kernels compile for a described TPU v5e.

Interpret mode (the CPU backend) cannot see what the chip's compiler
refuses: unaligned slices, too much VMEM, a kernel that cannot be
partitioned.  These tests hand the installed TPU compiler a ``v5e:2x2``
topology that is described, not attached, and compile the kernels at the
sizes ``chip_smoke.py`` runs them; nothing executes.

The topology is described inside a module fixture, never while a module is
imported: only one process at a time may load the TPU library, and pytest's
workers each import every test file.  Keep these tests in this one file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.core.cfa import CFAPipeline, IterSpace, Tiling, get_program
from repro.core.cfa.transform import _fetch_halo
from repro.kernels.stencil import execute_tiles, execute_tiles_sharded

CHIP_TILE = (16, 32, 128)  # chip_smoke's stencil tile (jacobi2d5p)
CHIP_WAVE = 64  # its largest wave, in tiles
HEAT3D_TILE = (4, 8, 8, 128)  # chip_smoke's sharded-phase tile
FDTD2D_TILE = (20, 50, 120)  # the fdtd2d-medium cell's tile (bench/configs/)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to compile with
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def as_on_chip():
    """Compile as ``chip_smoke.py`` runs: 64-bit types off (the test
    session turns them on for its f64 oracles; Mosaic refuses i64 block
    indices).  And keep the persistent cache out: a compile for an
    unattached chip is written to it but cannot be read back."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        with jax.enable_x64(False):
            yield
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        cc.reset_cache()


def _halo_batch(program, tile, batch, sharding):
    prog = get_program(program)
    shape = (batch, *prog.with_fields(
        tuple(wa + ta for wa, ta in zip(prog.widths, tile)), prog.n_fields))
    return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)


@pytest.mark.parametrize("program,tile,batch", [
    ("jacobi2d5p", CHIP_TILE, CHIP_WAVE),
    ("heat3d", HEAT3D_TILE, 4),
    # the fdtd2d-medium cell's tile: a (21, 3, 52, 122) halo block of three
    # fields, 8 tiles in its largest wave
    ("fdtd2d", FDTD2D_TILE, 8),
])
def test_execute_tiles_compiles_to_tpu_kernel(program, tile, batch, one_chip,
                                              as_on_chip):
    halos = _halo_batch(program, tile, batch, one_chip)
    compiled = execute_tiles.lower(program, halos, tile,
                                   interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_sharded_kernel_leg_compiles_over_four_chips(topo, as_on_chip):
    mesh = Mesh(topo.devices[:4], ("port",))
    halos = _halo_batch("heat3d", HEAT3D_TILE, 8,
                        NamedSharding(mesh, P("port")))
    run = jax.jit(execute_tiles_sharded, static_argnums=(0, 2, 3),
                  static_argnames=("interpret",))
    compiled = run.lower("heat3d", halos, HEAT3D_TILE, mesh,
                         interpret=False).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    # one shard of the wave per chip: each device runs 8 / 4 tiles
    w = get_program("heat3d").widths
    assert f"f32[2,{','.join(str(a + b) for a, b in zip(w, HEAT3D_TILE))}]" in text


@pytest.mark.parametrize("program,space,tile", [
    # the benchmark's cells (bench/configs/): PolyBench MEDIUM jacobi-2d
    # and heat-3d at their tiles
    ("jacobi2d5p", (200, 250, 250), (20, 50, 125)),
    ("heat3d", (48, 40, 40, 40), (4, 20, 20, 20)),
    ("fdtd2d", (100, 200, 240), FDTD2D_TILE),
    # fdtd-2d LARGE (fdtd2d-large): 2,000 tiles, tables of a few rows
    ("fdtd2d", (200, 1000, 1200), FDTD2D_TILE),
])
def test_compiled_fetch_compiles_for_the_chip(program, space, tile, one_chip,
                                              as_on_chip):
    """The fetch program at the cells' facet, table and halo shapes."""
    pipe = CFAPipeline(get_program(program), IterSpace(space), Tiling(tile))
    plan = pipe.fetch_classes()

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = _fetch_halo.lower(
        tuple(arg(pipe.facet_shape(k), jnp.float32) for k in plan.keys),
        tuple(arg(s.shape, jnp.int32) for s in plan.src),
        tuple(arg(d.shape, jnp.int32) for d in plan.dst),
        arg(plan.cls.shape, jnp.int32), arg(plan.shift.shape, jnp.int32),
        arg((), jnp.int32), shape=plan.shape).compile()
    text = compiled.as_text()
    assert "gather" in text and "scatter" in text
    assert f"f32[{','.join(map(str, plan.shape))}]" in text
