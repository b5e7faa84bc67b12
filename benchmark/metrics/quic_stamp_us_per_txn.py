"""Ingest, quic packed publisher: wall time the quic tile spends stamping a
transaction into packed rows (parse, one row per signature, frame
commit; credit waits left out), over the window: Δpacked_stamp_ns /
Δreasm_pub_cnt of tile quic, in µs.  None where the program has no
packed_stamp_ns counter."""

TILE = "quic"


def read(run):
    q0 = run.rec.counters["w0"].get(TILE, {})
    q1 = run.rec.counters["w1"].get(TILE, {})
    if not all(k in q0 and k in q1 for k in ("packed_stamp_ns",
                                             "reasm_pub_cnt")):
        return None
    txns = q1["reasm_pub_cnt"] - q0["reasm_pub_cnt"]
    if txns <= 0:
        return None
    return (q1["packed_stamp_ns"] - q0["packed_stamp_ns"]) / txns / 1e3
