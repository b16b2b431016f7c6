"""Host spans on the profiler's clock (docs/telemetry.md, "Spans on the
profiler's clock").

``span(name)`` marks a stretch of host work as a span of a
``jax.profiler`` trace, on the same clock as the device's ops, so that a
trace shows which host pass the device waited on.  While no profile is
recorded a span costs about half a microsecond.  ``SPANS`` lists every
span the program records; the benchmark's readers
(``bench/metrics/``) find them by these names.

JAX is imported on the first span, so host-only users of
``repro.telemetry`` do not pull it in.
"""
from __future__ import annotations

#: every span the program records, with what it covers
SPANS = {
    "repro.stencil2d":
        "kernels/stencil2d/ops.py stencil2d, Pallas branch: the whole entry",
    "repro.stencil2d.pad":
        "the host's preparation of the jitted _entry's static arguments: "
        "the taps as floats and the choice of interpret mode (the block "
        "plan, reshape and pad run inside _entry)",
    "repro.stencil2d.call":
        "the call into the jitted _entry (reshape, pad, kernel, slice, "
        "reshape back: one program), until its enqueue returns",
    "repro.stencil2d.slice":
        "the hand-back of _entry's result to the caller (the slice runs "
        "inside _entry)",
    "repro.explore":
        "explore/search.py explore: the whole search",
    "repro.explore.space":
        "enumerate_space and prune_space",
    "repro.explore.build":
        "stage 1 batched, per chunk: target.build, static_prune_reason and "
        "make_input of the chunk's lanes",
    "repro.jax_engine.lower":
        "simulate_batch's compiled_for loop, and per lane group of "
        "run_compiled_batch: shared_dims, lower, np.stack and the tables' "
        "copy to the device",
    "repro.jax_engine.loop":
        "per lane group: the _sweep call and the copy of its outputs to the "
        "host, i.e. the host waiting on the device loop",
    "repro.jax_engine.value_pass":
        "per lane group: _finalize over the group's lanes (the value pass "
        "and the lanes' counts)",
}

_annotation = None          # jax.profiler.TraceAnnotation, once imported


def span(name: str):
    """A context manager that records ``name`` (a key of ``SPANS``) as a
    host span of the profile being recorded, if one is."""
    global _annotation
    if _annotation is None:
        from jax.profiler import TraceAnnotation
        _annotation = TraceAnnotation
    return _annotation(name)
