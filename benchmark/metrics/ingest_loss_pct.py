"""Ingest (net and quic tiles): datagrams sent that never reached the
verify tile, over the whole run, from the verify tile's txn_in_cnt after
the run drained."""


def read(run):
    rec = run.rec
    sent = len(rec.send_pool)
    if not sent:
        return None
    got = rec.counters["end"]["verify:0"]["txn_in_cnt"]
    return 100.0 * (sent - got) / sent
