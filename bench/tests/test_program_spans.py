"""The program's spans on the profiler's clock, and the readers of the
per-layer metrics built on them, on the CPU.

One profile holds a few interpret-mode ``stencil2d`` calls and one tiny
batched ``explore()`` inside the benchmark's ``bench.window``; it is
reduced with ``trace_reduce`` as a ``--trace 1`` run reduces it.  The
readers are also checked on hand-built traces with known span times.
"""
from __future__ import annotations

import re
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for p in (str(ROOT / "src"), str(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)

import run            # noqa: E402
import trace_reduce   # noqa: E402

from repro.telemetry.spans import SPANS  # noqa: E402

SWEEP_READERS = ("wrapper_host_us.host", "launch_us.host")
TUNE_READERS = ("plan_s.tune", "lower_s.tune", "value_pass_s.tune")
#: each span of the table and the span it runs inside
PARENT = {"repro.stencil2d": "bench.dispatch",
          "repro.stencil2d.pad": "repro.stencil2d",
          "repro.stencil2d.call": "repro.stencil2d",
          "repro.stencil2d.slice": "repro.stencil2d",
          "repro.explore": "bench.explore",
          "repro.explore.space": "repro.explore",
          "repro.explore.build": "repro.explore",
          "repro.jax_engine.lower": "repro.explore",
          "repro.jax_engine.loop": "repro.explore",
          "repro.jax_engine.value_pass": "repro.explore"}


@pytest.fixture(scope="module")
def traced():
    """A reduced CPU profile of three kernel calls and one explore() call,
    each inside the benchmark's own span, all inside ``bench.window``."""
    import tempfile

    import jax
    import jax.numpy as jnp
    from jax.profiler import TraceAnnotation

    from repro.core import CGRA
    from repro.core.spec import heat_2d, paper_stencil_2d
    from repro.explore import Budget, explore
    from repro.kernels.stencil2d.ops import stencil2d

    spec = heat_2d(32, 256)
    x = jnp.asarray(np.random.default_rng(0).normal(size=spec.grid_shape),
                    jnp.float32)

    def sweep():
        return stencil2d(x, *spec.coeffs, backend="pallas", block=(8, 128))

    tspec = paper_stencil_2d(24, 48, r=3)

    def tune():
        return explore(tspec, CGRA, budget=Budget(batch_size=8))

    sweep().block_until_ready()            # compile outside the profile
    tune()
    with tempfile.TemporaryDirectory() as d:
        with jax.profiler.trace(d, profiler_options=run.profiler_options()):
            with TraceAnnotation("bench.window"):
                for _ in range(3):
                    with TraceAnnotation("bench.dispatch"):
                        y = sweep()
                    y.block_until_ready()
                with TraceAnnotation("bench.explore"):
                    tune()
        tr = trace_reduce.load(d)
    return tr


def _inside(child, parent):
    return parent.start <= child.start and child.end <= parent.end


def test_every_span_is_recorded_and_nested(traced):
    names = {s.name for s in traced.spans}
    assert set(SPANS) <= names
    assert set(PARENT) == set(SPANS)
    for name, parent in PARENT.items():
        outer = [s for s in traced.spans if s.name == parent]
        for s in traced.spans:
            if s.name == name:
                assert any(_inside(s, o) for o in outer), (name, parent)
    for name in ("repro.stencil2d", "repro.stencil2d.call"):
        assert sum(s.name == name for s in traced.spans) == 3


def test_readers_read_the_recorded_spans(traced):
    ctx = {"trace": traced, "window": traced.window(), "calls": 1}
    values = {m: run.metric_reader(m)(ctx)
              for m in SWEEP_READERS + TUNE_READERS}
    assert all(v is not None and v > 0 for v in values.values()), values
    dispatch = run.metric_reader("dispatch_us.host")(ctx)
    assert sum(values[m] for m in SWEEP_READERS) <= dispatch


def _trace(spans):
    return trace_reduce.Trace(
        {}, [trace_reduce.Op("bench.window", 0, 10_000_000_000)]
        + [trace_reduce.Op(n, s, e) for n, s, e in spans])


# two kernel calls of 10 and 20 us, whose jitted calls take 4 and 6 us; a
# call span outside the window is not read
SWEEP = [("repro.stencil2d", 1_000, 11_000),
         ("repro.stencil2d.pad", 1_500, 3_000),
         ("repro.stencil2d.call", 3_000, 7_000),
         ("repro.stencil2d.slice", 7_000, 10_000),
         ("repro.stencil2d", 20_000, 40_000),
         ("repro.stencil2d.call", 25_000, 31_000),
         ("repro.stencil2d.call", 20_000_000_000, 20_000_009_000)]
# two explore() calls in the window
TUNE = [("repro.explore", 0, 9_000_000_000),
        ("repro.explore.space", 0, 100_000_000),
        ("repro.explore.build", 100_000_000, 400_000_000),
        ("repro.jax_engine.lower", 400_000_000, 450_000_000),
        ("repro.jax_engine.lower", 450_000_000, 460_000_000),
        ("repro.jax_engine.loop", 460_000_000, 4_000_000_000),
        ("repro.jax_engine.value_pass", 4_000_000_000, 4_070_000_000),
        ("repro.explore.build", 5_000_000_000, 5_200_000_000),
        ("repro.jax_engine.value_pass", 5_200_000_000, 5_230_000_000)]


@pytest.mark.parametrize("metric,spans,want", [
    ("wrapper_host_us.host", SWEEP, ((10 - 4) + (20 - 6)) / 2),
    ("launch_us.host", SWEEP, (4 + 6) / 2),
    ("plan_s.tune", TUNE, (0.1 + 0.3 + 0.2) / 2),
    ("lower_s.tune", TUNE, (0.05 + 0.01) / 2),
    ("value_pass_s.tune", TUNE, (0.07 + 0.03) / 2)])
def test_reader_on_known_span_times(metric, spans, want):
    tr = _trace(spans)
    ctx = {"trace": tr, "window": tr.window(), "calls": 2}
    assert run.metric_reader(metric)(ctx) == pytest.approx(want, rel=1e-12)
    empty = _trace([])
    assert run.metric_reader(metric)(
        {"trace": empty, "window": empty.window(), "calls": 2}) is None


def test_readers_name_only_the_programs_spans():
    """A reader finds its spans by the names the program records, and
    reads the trace alone: it never imports the program."""
    for metric in SWEEP_READERS + TUNE_READERS:
        text = run.metric_path(metric).read_text()
        names = set(re.findall(r'"(repro\.[A-Za-z0-9_.]+)"', text))
        assert names and names <= set(SPANS), (metric, names - set(SPANS))
        assert not re.search(r"^\s*(import|from)\s+repro\b", text, re.M)
