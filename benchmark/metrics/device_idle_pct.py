"""Device: share of the window in which no operation ran on the chip,
from the verify tile's profiler trace (1 - union of op intervals /
window); read only where the trace holds most of the window
(reduce.device_window)."""

from benchmark import reduce


def read(run):
    win = reduce.device_window(run.trace, run.rec.w0_real, run.rec.w1_real)
    if win is None:
        return None
    c0, c1 = win
    return 100.0 * (1.0 - reduce.busy_s(run.trace, c0, c1) / ((c1 - c0) / 1e9))
