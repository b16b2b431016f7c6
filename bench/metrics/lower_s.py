"""Host seconds per ``explore()`` call of the batched engine's lowering:
compiling the lanes' plans to tables, padding them to each lane group's
shared dims, stacking them and copying them to the device
(``repro.jax_engine.lower``), from the program's spans in the window."""

SPAN = "repro.jax_engine.lower"


def read(ctx):
    lo, hi = ctx["window"]
    ns = [s.end - s.start for s in ctx["trace"].spans
          if s.name == SPAN and lo <= s.start < hi]
    if not ns or ctx["calls"] <= 0:
        return None
    return sum(ns) / ctx["calls"] / 1e9
