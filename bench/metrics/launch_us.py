"""Host time, in us, of the 2D kernel entry's call into its jitted
``_dispatch`` per call, until the enqueue returns: the program's
``repro.stencil2d.call`` spans in the window over their number."""

CALL = "repro.stencil2d.call"


def read(ctx):
    lo, hi = ctx["window"]
    spans = [s.end - s.start for s in ctx["trace"].spans
             if s.name == CALL and lo <= s.start < hi]
    if not spans:
        return None
    return sum(spans) / len(spans) / 1e3
