#!/usr/bin/env python
"""(Re)generate seed corpora for the fuzz targets into tests/corpus/.

Seeds are VALID serializations (plus a few structured edge cases) of each
wire format, produced by the same builders the tests use — the role of the
reference's checked-in corpus/ seeds.  Deterministic: same seeds on every
run."""

import os
import random
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

from firedancer_tpu.utils.fuzz import corpus_name  # noqa: E402

OUT = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                   "tests", "corpus")


def emit(target: str, blobs):
    d = os.path.join(OUT, target)
    os.makedirs(d, exist_ok=True)
    for b in blobs:
        with open(os.path.join(d, corpus_name(b)), "wb") as f:
            f.write(b)
    print(f"{target}: {len(os.listdir(d))} seeds")


def main():
    rng = random.Random(7)
    rb = lambda n: bytes(rng.getrandbits(8) for _ in range(n))  # noqa: E731

    # ---- txn ----
    from firedancer_tpu.ballet import txn as txn_lib
    pk1, pk2, prog, bh = rb(32), rb(32), rb(32), rb(32)
    txns = []
    m = txn_lib.build_unsigned([pk1], bh, [(1, b"\x00", b"hello")], [prog])
    txns.append(txn_lib.assemble([rb(64)], m))
    m = txn_lib.build_unsigned([pk1, pk2], bh,
                               [(2, bytes([0, 1]), rb(40))], [prog],
                               readonly_signed_cnt=1)
    txns.append(txn_lib.assemble([rb(64), rb(64)], m))
    m = txn_lib.build_unsigned([pk1], bh, [(1, b"\x00", rb(900))], [prog])
    txns.append(txn_lib.assemble([rb(64)], m))  # near-MTU
    m = txn_lib.build_unsigned([pk1], bh, [(1, b"\x00", b"")], [prog],
                               version=txn_lib.V0,
                               lookups=[(rb(32), bytes([0, 1]), bytes([2]))])
    txns.append(txn_lib.assemble([rb(64)], m))  # v0 with lookups
    emit("txn", txns)

    # ---- compact_u16 ----
    from firedancer_tpu.ballet import compact_u16 as cu16
    emit("compact_u16",
         [cu16.encode(v) + rb(2) for v in (0, 1, 127, 128, 16383, 16384,
                                           65535)])

    # ---- shred ----
    from firedancer_tpu.ballet import entry as entry_lib
    from firedancer_tpu.ballet import shred as shred_lib
    batch = entry_lib.serialize_batch(
        [entry_lib.Entry(1, rb(32), [txns[0]])])
    fs = shred_lib.make_fec_set(batch, slot=3, parent_off=1, version=1,
                                fec_set_idx=0, sign_fn=lambda r: rb(64),
                                data_cnt=4, code_cnt=4, slot_complete=True)
    emit("shred", fs.data_shreds[:2] + fs.code_shreds[:2])

    # ---- entry batch ----
    emit("entry_batch", [
        batch,
        entry_lib.serialize_batch([entry_lib.Entry(5, rb(32), [])]),
    ])

    # ---- zstd ----
    import zstandard
    emit("zstd", [
        zstandard.ZstdCompressor(level=1).compress(b"seed " * 200),
        zstandard.ZstdCompressor(level=19).compress(rb(512) * 4),
        zstandard.ZstdCompressor(level=3,
                                 write_checksum=True).compress(b"\0" * 5000),
    ])

    # ---- gossip ----
    from firedancer_tpu.flamenco import gossip
    v = gossip.make_value(lambda m: rb(64), pk1, gossip.KIND_VOTE, b"vote")
    emit("gossip_msg", [
        gossip.encode_push([v]),
        gossip.encode_pull_req({v.digest()}),
        gossip.encode_pull_resp([v]),
        gossip.encode_ping(pk1, rb(32), rb(64)),
        gossip.encode_pong(pk1, rb(32), rb(64)),
        gossip.encode_prune(pk1, [pk2], rb(64)),
    ])

    # ---- appendvec ----
    from firedancer_tpu.flamenco.snapshot import write_appendvec
    from firedancer_tpu.flamenco.types import Account
    emit("appendvec", [
        write_appendvec([(pk1, Account(lamports=5, data=b"xyz")),
                         (pk2, Account(lamports=9, data=rb(100),
                                       executable=True))]),
    ])

    # ---- lookup table ----
    from firedancer_tpu.flamenco.alut_program import LookupTable
    emit("lookup_table", [
        LookupTable(authority=pk1, addresses=[pk2, prog]).serialize(),
        LookupTable().serialize(),
    ])

    # ---- quic datagrams ----
    emit("quic_datagram", [
        b"\xc3" + (1).to_bytes(4, "big") + bytes([8]) + rb(8)
        + bytes([8]) + rb(8) + b"\x00" + b"\x41\x00" + rb(60),
        b"\x43" + rb(24),  # short header
        rb(1200),
    ])

    # ---- repair ----
    from firedancer_tpu.flamenco import repair
    req = repair.RepairRequest(rb(64), pk1, repair.REQ_WINDOW_INDEX, 1, 7, 3)
    emit("repair_msg", [req.serialize()])


if __name__ == "__main__":
    main()
