"""Verify pipeline: signature lanes filled per lane dispatched to the
device over the window (lanes_filled_cnt / lanes_dispatched_cnt)."""


def read(run):
    v0 = run.rec.counters["w0"]["verify:0"]
    v1 = run.rec.counters["w1"]["verify:0"]
    lanes = v1["lanes_dispatched_cnt"] - v0["lanes_dispatched_cnt"]
    if lanes <= 0:
        return None
    return 100.0 * (v1["lanes_filled_cnt"] - v0["lanes_filled_cnt"]) / lanes
