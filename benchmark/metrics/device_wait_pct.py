"""The device (TPU v5e) as the verify host sees it: share of the window
the verify tile spent blocked on verdicts, its is_ready poll loop plus the
verdict fetch: 100 × Δverdict_wait_ns of verify:0 / the window's ns.  None
where the program has no verdict_wait_ns counter."""

TILE = "verify:0"


def read(run):
    rec = run.rec
    v0 = rec.counters["w0"].get(TILE, {})
    v1 = rec.counters["w1"].get(TILE, {})
    if "verdict_wait_ns" not in v0 or "verdict_wait_ns" not in v1:
        return None
    window = rec.w1 - rec.w0
    if window <= 0:
        return None
    return 100.0 * (v1["verdict_wait_ns"] - v0["verdict_wait_ns"]) / window
