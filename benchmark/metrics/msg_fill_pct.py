"""Verify pipeline and kernels, packed row width: the share of the hashed
message width that is message rather than padding, over the window:
100 x Δmsg_bytes_cnt / (Δlanes_dispatched_cnt x row_ml) of verify:0
(row_ml is the packed row's message width).  None where the program has
no msg_bytes_cnt counter."""

TILE = "verify:0"


def read(run):
    v0 = run.rec.counters["w0"].get(TILE, {})
    v1 = run.rec.counters["w1"].get(TILE, {})
    if not all(k in v0 and k in v1 for k in ("msg_bytes_cnt", "row_ml")):
        return None
    lanes = v1["lanes_dispatched_cnt"] - v0["lanes_dispatched_cnt"]
    if lanes <= 0 or v1["row_ml"] <= 0:
        return None
    return (100.0 * (v1["msg_bytes_cnt"] - v0["msg_bytes_cnt"])
            / (lanes * v1["row_ml"]))
