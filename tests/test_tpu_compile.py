"""Compile the main path for a described TPU v5e, with no chip attached.

Each case compiles what the public entry points would run on the chip, at
the paper's and the smoke run's real sizes, with the block the kernel's own
planner picks.  The TPU compiler refuses here what it would refuse on the
chip (a tile that overflows VMEM, an unaligned slice), so these guard the
planners at no chip time.  Nothing runs: a pass says nothing about results.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.mapping import map_1d
from repro.core.spec import heat_2d, heat_3d, paper_stencil_1d, paper_stencil_2d


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip cannot be read back from the
    # persistent cache, so keep it out of the cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", before)


@pytest.fixture
def on_tpu(monkeypatch):
    """The kernels pick Pallas over interpret mode from the default backend,
    which is the CPU here: steer that choice to the described chip."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _compile(fn, shape, sharding):
    x = jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)
    return jax.jit(fn).lower(x).compile()


@pytest.mark.parametrize("batch,timesteps,variant", [
    (1, 1, "vpu"), (8, 4, "vpu"), (1, 1, "mxu")])
def test_stencil1d_paper_grid_compiles(one_chip, on_tpu, batch, timesteps,
                                       variant):
    from repro.kernels.stencil1d.ops import stencil1d
    spec = paper_stencil_1d(dtype="float32")
    compiled = _compile(
        lambda x: stencil1d(x, spec.coeffs[0], timesteps=timesteps,
                            variant=variant),
        (batch,) + spec.grid_shape, one_chip)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("spec,timesteps", [
    (paper_stencil_2d(dtype="float32"), 1),
    (paper_stencil_2d(dtype="float32"), 4),
    (heat_2d(8192, 8192), 4)], ids=["seismic", "seismic-T4", "heat8192-T4"])
def test_stencil2d_planner_choice_compiles(one_chip, on_tpu, spec, timesteps):
    from repro.kernels.stencil2d.ops import stencil2d
    compiled = _compile(
        lambda x: stencil2d(x, *spec.coeffs, timesteps=timesteps),
        spec.grid_shape, one_chip)
    assert "tpu_custom_call" in compiled.as_text()


def test_stencil3d_512_cube_compiles(one_chip, on_tpu):
    from repro.kernels.stencil3d.ops import stencil3d
    spec = heat_3d(512, 512, 512)
    compiled = _compile(lambda x: stencil3d(x, *spec.coeffs),
                        spec.grid_shape, one_chip)
    assert "tpu_custom_call" in compiled.as_text()


def test_jax_engine_sweep_compiles(one_chip):
    from repro.core.engine import jax_engine
    from repro.core.engine.compile import compiled_for
    lp = jax_engine.lower(compiled_for(map_1d(paper_stencil_1d(), workers=6)))
    with jax.enable_x64(True):
        tables = {k: jax.ShapeDtypeStruct((1,) + np.shape(v),
                                          np.asarray(v).dtype,
                                          sharding=one_chip)
                  for k, v in lp.tables.items()}
        for k in ("epc", "cap4"):
            tables[k] = jax.ShapeDtypeStruct((1,), jnp.float64,
                                             sharding=one_chip)
        max_cycles = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
        jax_engine._sweep.lower(tables, max_cycles).compile()
