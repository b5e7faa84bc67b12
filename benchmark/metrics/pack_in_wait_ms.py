"""The dedup->pack ring (dedup_pack, flow control in disco/mux.py): mean
time a transaction sat in the pack tile's in-link ring over the window,
consume time minus dedup's publish stamp: Δin_wait_ns / Δin_wait_cnt of
tile pack, in ms.  None where the program has no such counters."""

TILE = "pack"


def read(run):
    p0 = run.rec.counters["w0"].get(TILE, {})
    p1 = run.rec.counters["w1"].get(TILE, {})
    if not all(k in p0 and k in p1 for k in ("in_wait_ns", "in_wait_cnt")):
        return None
    n = p1["in_wait_cnt"] - p0["in_wait_cnt"]
    if n <= 0:
        return None
    return (p1["in_wait_ns"] - p0["in_wait_ns"]) / n / 1e6
