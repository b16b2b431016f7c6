"""Host time, in us, of the 2D kernel entry's own work per call: the
program's ``repro.stencil2d`` span less the ``repro.stencil2d.call`` span
inside it (the input's reshape and pad, the output's slice and reshape,
the planner), summed over the calls in the window over their number."""
import bisect

ENTRY = "repro.stencil2d"
CALL = "repro.stencil2d.call"


def read(ctx):
    lo, hi = ctx["window"]
    spans = ctx["trace"].spans
    entries = sorted((s.start, s.end) for s in spans
                     if s.name == ENTRY and lo <= s.start < hi)
    if not entries:
        return None
    starts = [s for s, _e in entries]
    inner = 0
    for c in spans:
        if c.name != CALL:
            continue
        k = bisect.bisect_right(starts, c.start) - 1
        if k >= 0 and c.end <= entries[k][1]:
            inner += c.end - c.start
    own = sum(e - s for s, e in entries) - inner
    return own / len(entries) / 1e3
