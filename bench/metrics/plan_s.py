"""Host seconds per ``explore()`` call of the tuner's planning passes:
enumerating and pruning the search space (``repro.explore.space``) and
building, statically verifying and drawing the input of each stage-1 lane
(``repro.explore.build``), from the program's spans in the window."""

SPANS = ("repro.explore.space", "repro.explore.build")


def read(ctx):
    lo, hi = ctx["window"]
    ns = [s.end - s.start for s in ctx["trace"].spans
          if s.name in SPANS and lo <= s.start < hi]
    if not ns or ctx["calls"] <= 0:
        return None
    return sum(ns) / ctx["calls"] / 1e9
