"""Compile the main path for a described TPU v5e, with no chip attached.

Each case compiles what the public entry points would run on the chip, at
the paper's and the smoke run's real sizes, with the block the kernel's own
planner picks.  The TPU compiler refuses here what it would refuse on the
chip (a tile that overflows VMEM, an unaligned slice), so these guard the
planners at no chip time.  Nothing runs: a pass says nothing about results.
"""
import re

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


@pytest.mark.parametrize("shape,timesteps", [
    ((194400,), 1), ((8, 194400), 4), ((196608,), 1)],
    ids=["signal", "batch8-T4", "aligned"])
def test_stencil1d_entry_is_one_program(one_chip, on_tpu, shape, timesteps):
    """The entry compiles to one program holding one kernel and no copy,
    and the kernel is the only boundary mask: no select or and op
    anywhere in the module (XLA would move a second mask past the slice,
    so its length says nothing).  The paper's 194,400 points are no
    multiple of the lane-aligned block, so the pad and slice stay, and at
    that length a rank-1 signal's reshapes in and out are relayouts (the
    way out fuses with the slice).  Where the block divides the signal,
    nothing but the kernel passes over it."""
    from repro.kernels.stencil1d.ops import stencil1d
    spec = paper_stencil_1d(dtype="float32")
    text = _compile(lambda x: stencil1d(x, spec.coeffs[0],
                                        timesteps=timesteps),
                    shape, one_chip).as_text()
    ops = set(re.findall(r"^\s*(?:ROOT )?%?[\w.-]+ = \w+\[[\d,]*\]\S* "
                         r"([\w-]+)\(", text, re.M))
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert not ops & {"copy", "select", "and"}, ops
    if shape[-1] % 4096 == 0:
        assert ops == {"parameter", "bitcast", "custom-call"}, ops


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


@pytest.mark.parametrize("spec,padded", [
    (heat_2d(16384, 16384), False),
    (paper_stencil_2d(dtype="float32"), True)], ids=["heat16384", "seismic"])
def test_stencil2d_entry_is_one_program(one_chip, on_tpu, spec, padded):
    """The entry compiles to one program holding one kernel and no copy.
    Where the grid needs no padding the reshapes are bitcasts and nothing
    but the kernel passes over the grid: no relayout copy, no second
    boundary mask.  A padded grid keeps its pad and slice, which are
    small beside the kernel."""
    from repro.kernels.stencil2d.ops import stencil2d
    text = _compile(lambda x: stencil2d(x, *spec.coeffs), spec.grid_shape,
                    one_chip).as_text()
    entry = text[text.index("\nENTRY"):]
    entry = entry[:entry.index("\n}")]
    ops = re.findall(r"^\s*(?:ROOT )?%?[\w.-]+ = \w+\[([\d,]*)\]\S* "
                     r"([\w-]+)\(", entry, re.M)
    assert entry.count('custom_call_target="tpu_custom_call"') == 1
    assert "copy" not in {op for _, op in ops}, ops
    if not padded:
        size = int(np.prod(spec.grid_shape))
        grid_passes = [op for dims, op in ops
                       if int(np.prod([int(d) for d in dims.split(",") if d]))
                       == size
                       and op not in ("parameter", "bitcast", "custom-call")]
        assert grid_passes == [], ops


def test_stencil2d_kernel_keeps_its_name(one_chip, on_tpu):
    """The kernel's custom call is named for its ``pallas_call``'s
    ``name``, not for the Python function that builds it."""
    from repro.kernels.stencil2d.ops import stencil2d
    spec = paper_stencil_2d(dtype="float32")
    text = _compile(lambda x: stencil2d(x, *spec.coeffs), spec.grid_shape,
                    one_chip).as_text()
    calls = re.findall(r"^\s*%?([\w.-]+) = .* custom-call\(.*"
                       r"custom_call_target=\"tpu_custom_call\"", text, re.M)
    assert calls and all(re.fullmatch(r"stencil2d(\.\d+)?", c)
                         for c in calls), calls


def test_stencil3d_512_cube_compiles(one_chip, on_tpu):
    from repro.kernels.stencil3d.ops import stencil3d
    spec = heat_3d(512, 512, 512)
    compiled = _compile(lambda x: stencil3d(x, *spec.coeffs),
                        spec.grid_shape, one_chip)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("spec,timesteps,padded", [
    (heat_3d(512, 512, 512), 1, False),
    (heat_3d(512, 512, 512), 2, False),
    (heat_3d(100, 40, 250), 1, True)], ids=["heat512", "heat512-T2", "padded"])
def test_stencil3d_entry_is_one_program(one_chip, on_tpu, spec, timesteps,
                                        padded):
    """The entry compiles to one program holding one kernel a sweep and no
    copy.  Where the block divides the grid the reshapes are bitcasts and
    nothing but the kernels passes over the grid: no relayout copy, no
    boundary mask of the entry's own.  A padded grid keeps its pad and
    slice.  (The padded grid's y is a multiple of 8: the TPU stores a grid
    whose y is not, such as (100, 60, 200), with z minor, and that array's
    relayout to the kernel's row-major operand is a copy of its own.)"""
    from repro.kernels.stencil3d.ops import stencil3d
    text = _compile(
        lambda x: stencil3d(x, *spec.coeffs, timesteps=timesteps),
        spec.grid_shape, one_chip).as_text()
    entry = text[text.index("\nENTRY"):]
    entry = entry[:entry.index("\n}")]
    ops = re.findall(r"^\s*(?:ROOT )?%?[\w.-]+ = \w+\[([\d,]*)\]\S* "
                     r"([\w-]+)\(", entry, re.M)
    assert (entry.count('custom_call_target="tpu_custom_call"')
            == timesteps)
    assert "copy" not in {op for _, op in ops}, ops
    if not padded:
        size = int(np.prod(spec.grid_shape))
        grid_passes = [op for dims, op in ops
                       if int(np.prod([int(d) for d in dims.split(",") if d]))
                       == size
                       and op not in ("parameter", "bitcast", "custom-call")]
        assert grid_passes == [], ops


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
