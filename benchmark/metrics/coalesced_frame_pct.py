"""Verify pipeline: the share of packed frames that merged into a device
call already holding another frame's rows, over the window:
100 x Δcoalesced_frame_cnt / Δin_frag_cnt of verify:0.  None where the
program has no coalesced_frame_cnt counter."""

TILE = "verify:0"


def read(run):
    v0 = run.rec.counters["w0"].get(TILE, {})
    v1 = run.rec.counters["w1"].get(TILE, {})
    if not all("coalesced_frame_cnt" in v and "in_frag_cnt" in v
               for v in (v0, v1)):
        return None
    frames = v1["in_frag_cnt"] - v0["in_frag_cnt"]
    if frames <= 0:
        return None
    return (100.0 * (v1["coalesced_frame_cnt"] - v0["coalesced_frame_cnt"])
            / frames)
