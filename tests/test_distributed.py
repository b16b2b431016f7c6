"""Multi-device tests: halo-exchange stencils == single-device oracle,
int8_psum, logical sharding rules.  Device-count-dependent tests run in a
subprocess with --xla_force_host_platform_device_count=8 so the main pytest
process keeps its single real device (per assignment)."""
import json
import os
import subprocess
import sys
from types import SimpleNamespace

import pytest

from repro.distributed.sharding import resolve_spec

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_SUBPROC = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.core.spec import StencilSpec
from repro.core.reference import stencil_reference_np
from repro.distributed.halo import (distributed_stencil1d,
                                    distributed_stencil2d,
                                    distributed_stencil3d)
from repro.distributed.collectives import int8_psum
from jax.sharding import AxisType

out = {}
mesh = jax.make_mesh((2, 4), ("pod", "data"), axis_types=(AxisType.Auto,) * 2)
rng = np.random.default_rng(0)

spec = StencilSpec((512,), (3,), (tuple((rng.normal(size=7)/7).tolist()),),
                   dtype="float32", timesteps=2)
f = distributed_stencil1d(spec, mesh, axis="data")
x = rng.normal(size=512).astype(np.float32)
out["d1"] = bool(np.allclose(np.asarray(f(jnp.asarray(x))),
                             stencil_reference_np(x, spec), atol=1e-5))

cx = rng.normal(size=5)/5; cx[2] = 0.0
spec2 = StencilSpec((64, 96), (2, 2),
                    (tuple((rng.normal(size=5)/5).tolist()), tuple(cx)),
                    dtype="float32", timesteps=2)
f2 = distributed_stencil2d(spec2, mesh, axes=("pod", "data"))
x2 = rng.normal(size=(64, 96)).astype(np.float32)
out["d2"] = bool(np.allclose(np.asarray(f2(jnp.asarray(x2))),
                             stencil_reference_np(x2, spec2), atol=1e-5))

cz3 = rng.normal(size=3)/3
cy3 = rng.normal(size=3)/3; cy3[1] = 0.0
cx3 = rng.normal(size=3)/3; cx3[1] = 0.0
spec3 = StencilSpec((16, 32, 48), (1, 1, 1),
                    (tuple(cz3), tuple(cy3), tuple(cx3)),
                    dtype="float32", timesteps=2)
f3 = distributed_stencil3d(spec3, mesh, axes=("pod", "data"))
x3 = rng.normal(size=(16, 32, 48)).astype(np.float32)
out["d3"] = bool(np.allclose(np.asarray(f3(jnp.asarray(x3))),
                             stencil_reference_np(x3, spec3), atol=1e-5))

mesh1 = jax.make_mesh((8,), ("d",), axis_types=(AxisType.Auto,))
xq = jnp.asarray(rng.normal(size=(8, 64)), jnp.float32)
g = jax.jit(jax.shard_map(lambda v: int8_psum(v, "d"), mesh=mesh1,
                          in_specs=P("d"), out_specs=P("d")))
y = g(xq)
true = jnp.sum(xq, axis=0)
out["psum_rel"] = float(jnp.abs(y[0] - true).max() / jnp.abs(true).max())
print("RESULT " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def subproc_results():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", _SUBPROC], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-2000:]
    line = [l for l in r.stdout.splitlines() if l.startswith("RESULT ")][-1]
    return json.loads(line[len("RESULT "):])


def test_distributed_stencil1d_matches_oracle(subproc_results):
    assert subproc_results["d1"]


def test_distributed_stencil2d_matches_oracle(subproc_results):
    assert subproc_results["d2"]


def test_distributed_stencil3d_matches_oracle(subproc_results):
    assert subproc_results["d3"]


def test_int8_psum_accuracy(subproc_results):
    assert subproc_results["psum_rel"] < 0.05


# ---- sharding rules (mesh-shape only; no devices needed) -------------------
MESH = SimpleNamespace(shape={"pod": 2, "data": 16, "model": 16})


def test_rules_batch_over_pod_and_data():
    assert resolve_spec((256, 4096), ("batch", None), MESH) == \
        __import__("jax").sharding.PartitionSpec(("pod", "data"))


def test_rules_divisibility_fallback():
    P = __import__("jax").sharding.PartitionSpec
    # kv_heads=8 cannot split 16 -> replicated
    assert resolve_spec((8, 128), ("kv_heads", None), MESH) == P()
    # odd vocab -> replicated
    assert resolve_spec((49155, 1024), ("vocab", "fsdp"), MESH) == \
        P(None, "data")
    # heads=96 divides 16
    assert resolve_spec((96, 128), ("heads", None), MESH) == P("model")


def test_rules_no_axis_reuse():
    P = __import__("jax").sharding.PartitionSpec
    # both dims want 'model'; second falls back
    got = resolve_spec((32, 32), ("heads", "mlp"), MESH)
    assert got == P("model")


def test_inference_rules_keep_tp_drop_fsdp():
    from repro.distributed.sharding import INFERENCE_RULES
    P = __import__("jax").sharding.PartitionSpec
    # fsdp dim replicated at serving; TP dims unchanged
    assert resolve_spec((4096, 4096), ("fsdp", "mlp"), MESH,
                        INFERENCE_RULES) == P(None, "model")
    assert resolve_spec((4096, 4096), ("fsdp", "mlp"), MESH) == \
        P("data", "model")


def test_cache_seq_and_expert_cap_fallbacks():
    P = __import__("jax").sharding.PartitionSpec
    # kv_heads=8 can't take model=16 -> the cache *positions* take it
    got = resolve_spec((128, 8, 32768, 128),
                       ("batch", "kv_heads", "cache_seq", None), MESH)
    assert got == P(("pod", "data"), None, "model")
    # 32 experts take model -> capacity falls back to replicated
    got = resolve_spec((128, 32, 160, 1024),
                       ("batch", "experts", "expert_cap", None), MESH)
    assert got == P(("pod", "data"), "model")
    # 40 experts can't -> capacity takes model (granite-3b case)
    got = resolve_spec((128, 40, 160, 1024),
                       ("batch", "experts", "expert_cap", None), MESH)
    assert got == P(("pod", "data"), None, "model")
