"""Reduction of the verify tile's profiler trace to device metrics.

The tile records the trace for its whole run; only the measured window
counts.  Event times in an `.xplane.pb` are offsets from the profile's
start, whose realtime stamp the "Task Environment" plane keeps, so an
event's realtime is that stamp plus its offset, the clock the harness
used to mark the window.

`load` turns a trace into plain event lists, which is all the reducers
read: device operations (per device plane, from its "XLA Ops" line, or
every line where it has none) and host events.
"""

import glob
import os
from dataclasses import dataclass

import numpy as np


@dataclass
class Events:
    name: list          # str per event
    start: np.ndarray   # int64 realtime ns
    dur: np.ndarray     # int64 ns
    where: list         # device plane (device events) or host line


@dataclass
class Trace:
    device: Events
    host: Events
    chips: int


def _events(items) -> Events:
    return Events([i[0] for i in items],
                  np.array([i[1] for i in items], np.int64),
                  np.array([i[2] for i in items], np.int64),
                  [i[3] for i in items])


def load(trace_dir: str) -> Trace | None:
    """The newest trace under trace_dir, or None where there is none."""
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        return None
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(paths[-1])
    t0 = None
    for plane in pd.planes:
        for k, v in plane.stats:
            if k == "profile_start_time":
                t0 = int(v)
    if t0 is None:
        return None
    dev, host, chips = [], [], 0
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            lines = list(plane.lines)
            ops = [ln for ln in lines if ln.name == "XLA Ops"] or lines
            n0 = len(dev)
            for ln in ops:
                for e in ln.events:
                    dev.append((e.name, t0 + int(e.start_ns),
                                int(e.duration_ns), plane.name))
            chips += len(dev) > n0
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for e in ln.events:
                    host.append((e.name, t0 + int(e.start_ns),
                                 int(e.duration_ns), ln.name))
    return Trace(_events(dev), _events(host), max(chips, 1))


def covered(tr: Trace, w0: int, w1: int) -> tuple[int, int]:
    """The part of [w0, w1) the trace holds device events for.  The
    profiler keeps a bounded number of device events, so in a busy run
    the trace stops before the window does; `window_s` and the breakdown
    are taken over what it holds, device metrics only where that is most
    of the window (device_window)."""
    if not len(tr.device.start):
        return w0, w0
    return w0, int(min(w1, (tr.device.start + tr.device.dur).max()))


# least share of the window the trace has to hold before a device metric
# is read from it: the profiler's event buffers run out at a point that
# depends on how many device events the program under test emits, so a
# shorter part would measure two programs over different stretches
MIN_COVER = 0.9


def device_window(tr: Trace | None, w0: int, w1: int):
    """(c0, c1) to read device metrics over, or None where the trace
    holds less than MIN_COVER of [w0, w1)."""
    if tr is None:
        return None
    c0, c1 = covered(tr, w0, w1)
    if c1 - c0 < MIN_COVER * (w1 - w0):
        return None
    return c0, c1


def clip(ev: Events, w0: int, w1: int) -> Events:
    """Events cut to [w0, w1): each keeps only its part inside."""
    s = np.maximum(ev.start, w0)
    e = np.minimum(ev.start + ev.dur, w1)
    keep = np.nonzero(e > s)[0]
    return Events([ev.name[i] for i in keep], s[keep], (e - s)[keep],
                  [ev.where[i] for i in keep])


def union(ev: Events) -> list[tuple[int, int]]:
    """Merged [start, end) intervals of a set of events."""
    if not len(ev.start):
        return []
    order = np.argsort(ev.start, kind="stable")
    out = []
    cs, ce = int(ev.start[order[0]]), int(ev.start[order[0]]
                                          + ev.dur[order[0]])
    for i in order[1:]:
        s, e = int(ev.start[i]), int(ev.start[i] + ev.dur[i])
        if s > ce:
            out.append((cs, ce))
            cs, ce = s, e
        else:
            ce = max(ce, e)
    out.append((cs, ce))
    return out


def busy_s(tr: Trace, w0: int, w1: int) -> float:
    """Seconds in which an operation ran on a device, averaged over the
    device planes the trace holds."""
    ev = clip(tr.device, w0, w1)
    total = 0
    for plane in sorted(set(ev.where)):
        sel = [i for i, w in enumerate(ev.where) if w == plane]
        sub = Events([ev.name[i] for i in sel], ev.start[sel], ev.dur[sel],
                     [plane] * len(sel))
        total += sum(e - s for s, e in union(sub))
    return total / 1e9 / tr.chips


def op_seconds(tr: Trace, w0: int, w1: int) -> dict:
    """Device seconds per operation name inside the window."""
    ev = clip(tr.device, w0, w1)
    out: dict = {}
    for n, d in zip(ev.name, ev.dur.tolist()):
        out[n] = out.get(n, 0) + d
    return {k: v / 1e9 for k, v in out.items()}


def idle_gaps(tr: Trace, w0: int, w1: int, top: int = 10) -> list:
    """The longest stretches of the window with no device operation,
    each named by the shortest host event that spans its middle (what the
    host runtime was doing), longest first."""
    busy = union(clip(tr.device, w0, w1))
    gaps, cur = [], w0
    for s, e in busy:
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if cur < w1:
        gaps.append((cur, w1))
    gaps.sort(key=lambda g: g[0] - g[1])
    h = tr.host
    out = []
    for s, e in gaps[:top]:
        mid = (s + e) // 2
        cover = np.nonzero((h.start <= mid) & (h.start + h.dur > mid))[0]
        name = (h.name[int(cover[np.argmin(h.dur[cover])])]
                if len(cover) else "no host event")
        out.append([name, (e - s) / 1e9])
    return out
