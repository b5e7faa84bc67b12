"""Pack (disco/tiles.py PackTile and its mux loop): the pack tile's working
wall time per transaction it took in over the window — its callbacks net
of credit stalls, the mux's own per-frag loop work, and housekeeping:
Δ(busy_ns + loop_ns + house_ns) / Δin_frag_cnt, in µs.  None where the
program has no loop_ns counter."""

TILE = "pack"
WORK = ("busy_ns", "loop_ns", "house_ns")


def read(run):
    p0 = run.rec.counters["w0"].get(TILE, {})
    p1 = run.rec.counters["w1"].get(TILE, {})
    if not all(k in p0 and k in p1 for k in WORK + ("in_frag_cnt",)):
        return None
    txns = p1["in_frag_cnt"] - p0["in_frag_cnt"]
    if txns <= 0:
        return None
    work = sum(p1[k] - p0[k] for k in WORK)
    return work / txns / 1e3
