"""The verdict observer: a reliable consumer of the link on which the
dedup tile hands verified transactions to pack.

It runs in a process of its own, driven by the program's tile loop (the
same credit protocol as any consumer, so it never loses a frag), and
keeps for every frag the time it saw it on the benchmark's own clock, the
transaction's tag (the first 8 bytes of its first signature, read from
the payload) and a digest of the payload.  Closed loops read the running
count through `count`.
"""

import hashlib
import time

import numpy as np

OBSERVED_LINK = "dedup_pack"


class ObserverTile:
    def __init__(self, count, report, conn):
        self.count = count
        self.report = report
        self.conn = conn
        self.sent = False
        self.t, self.tags, self.digs = [], [], []

    def on_burst(self, ctx, iidx, metas, buf, offs, kept):
        now = time.monotonic_ns()
        tags = np.empty(kept, np.uint64)
        digs = np.empty(kept, np.uint64)
        for i in range(kept):
            p = buf[offs[i]:offs[i + 1]]
            tags[i] = int.from_bytes(p[1:9].tobytes(), "little")
            digs[i] = int.from_bytes(
                hashlib.blake2b(p, digest_size=8).digest(), "little")
        self.t.append(np.full(kept, now, np.int64))
        self.tags.append(tags)
        self.digs.append(digs)
        self.count.value += kept

    def house(self, ctx):
        if self.report.is_set() and not self.sent:
            self.sent = True

            def cat(xs, dt):
                return np.concatenate(xs) if xs else np.zeros(0, dt)
            self.conn.send({"t": cat(self.t, np.int64),
                            "tag": cat(self.tags, np.uint64),
                            "digest": cat(self.digs, np.uint64)})


def main(spec, name, count, report, conn):
    """Process entry: join the topology and consume until HALT."""
    from firedancer_tpu.disco import topo as topo_mod
    from firedancer_tpu.disco.mux import Mux

    jt = topo_mod.join(spec)
    try:
        Mux(jt, name, ObserverTile(count, report, conn)).run()
    finally:
        jt.close()
