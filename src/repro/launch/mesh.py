"""Production mesh factories.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so that
importing this module never touches jax device state — the dry-run sets
``--xla_force_host_platform_device_count=512`` before first jax init and then
calls this.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_local_mesh(data: int = 1, model: int = 1):
    """Small mesh over whatever devices exist (CPU tests/examples)."""
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


# XLA flags recommended for the real-TPU launch scripts (latency-hiding
# scheduler = the compute/comm overlap knob; async collectives).
TPU_PERF_FLAGS = " ".join([
    "--xla_tpu_enable_latency_hiding_scheduler=true",
    "--xla_enable_async_all_gather=true",
    "--xla_enable_async_collective_permute=true",
    "--xla_tpu_enable_async_collective_fusion=true",
    "--xla_tpu_overlap_compute_collective_tc=true",
])
