"""Share of its roofline that the 1D stencil kernel reaches, in per cent.

The least time one sweep could take on the chip (``work.least_time_s``:
one read and one write of the grid at peak HBM bandwidth, or the flops at
peak, whichever is longer) over the device time per sweep of the Mosaic
kernel named ``stencil1d`` (its ``pallas_call``'s name) in the traced
window.  Nothing to read where no such kernel ran.
"""
import work

KERNEL = "stencil1d"


def read(ctx):
    lo, hi = ctx["window"]
    kernel = ctx["trace"].op_seconds(lo, hi, kind="kernel", name=KERNEL)
    if kernel <= 0 or ctx["calls"] <= 0:
        return None
    least, _bound = work.least_time_s(ctx["work"], ctx["peak"], ctx["chips"])
    return 100.0 * least / (kernel / ctx["calls"])
