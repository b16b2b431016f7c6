"""Share of its roofline that the halo-exchanged step reaches, in per cent.

The least time one step could take on the cell's chips (``work.least_time_s``:
one read and one write of the whole grid at peak HBM bandwidth of every
chip, or the flops at peak, whichever is longer) over the device's busy
time per step in the traced window, averaged over the chips.  The busy time
is the union of every op of the step (the sweep, the exchange and what XLA
wrote around them), so the share falls with any of them.  Nothing to read
where the window holds no collective: that is no halo step.
"""
import work


def read(ctx):
    lo, hi = ctx["window"]
    tr = ctx["trace"]
    if tr.op_seconds(lo, hi, kind="collective") <= 0 or ctx["calls"] <= 0:
        return None
    least, _bound = work.least_time_s(ctx["work"], ctx["peak"], ctx["chips"])
    return 100.0 * least / (tr.busy_s(lo, hi) / ctx["calls"])
