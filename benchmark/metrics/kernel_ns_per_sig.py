"""Kernels: device time of the verify program's Pallas kernels (the SHA-512
kernel, ops/sha512_pallas.py, and the fused verify tail,
ops/curve_pallas.py) per signature lane dispatched, over the part of the
window the trace holds, where that is most of it (reduce.device_window).
The kernels are found by their custom-call target, which names every
Pallas kernel whatever its instruction is called.  The lanes are the batch widths of the verify
tile's dispatches in that part, filled and padding lanes alike (the
kernels run over both): its KIND_DISPATCH spans, or on the packed path,
which records none, its packed frames (KIND_BURST span counts), each one
dispatch of the batch."""

import numpy as np

from firedancer_tpu.disco import trace as trace_mod

from benchmark import reduce

KERNEL_TARGET = 'custom_call_target="tpu_custom_call"'


def read(run):
    tr, rec = run.trace, run.rec
    spans = rec.spans.get("verify:0")
    win = reduce.device_window(tr, rec.w0_real, rec.w1_real)
    if win is None or spans is None:
        return None
    c0, c1 = win
    ops = reduce.op_seconds(tr, c0, c1)
    ker = sum(s for n, s in ops.items() if KERNEL_TARGET in n)
    # span times are CLOCK_MONOTONIC; the window maps them to realtime
    t = spans["ts"].astype(np.int64) + (rec.w0_real - rec.w0)
    inside = (t >= c0) & (t < c1)
    widths = np.array(rec.buckets, np.int64)
    disp = spans["kind"] == trace_mod.KIND_DISPATCH
    if disp.any():
        idx = spans["iidx"][disp & inside] & (trace_mod.LANE_LAT - 1)
        lanes = int(widths[idx.astype(np.int64)].sum())
    else:
        frames = spans["cnt"][(spans["kind"] == trace_mod.KIND_BURST)
                              & inside]
        lanes = int(frames.astype(np.int64).sum()) * int(widths[0])
    if ker <= 0 or lanes <= 0:
        return None
    return ker * 1e9 / lanes
