"""Host seconds per ``explore()`` call of the batched engine's value pass:
each lane's output grid and counts rebuilt on the host after its group's
device loop (``repro.jax_engine.value_pass``), from the program's spans in
the window."""

SPAN = "repro.jax_engine.value_pass"


def read(ctx):
    lo, hi = ctx["window"]
    ns = [s.end - s.start for s in ctx["trace"].spans
          if s.name == SPAN and lo <= s.start < hi]
    if not ns or ctx["calls"] <= 0:
        return None
    return sum(ns) / ctx["calls"] / 1e9
