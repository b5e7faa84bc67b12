"""The one traffic generator: reads a mix's parameters and makes its
transactions, their send schedule and the outcome each send must have.

Every transaction carries distinct valid ed25519 signatures, made with
the benchmark's own signer (ref/ed25519.py): signature i of the run uses
the nonce r0 + i, so its nonce point is one point addition from the last
and signing costs one addition and one SHA-512, not a scalar
multiplication.  Keys are a0 + j for j below the mix's key count.  A bad
transaction has one of its signatures (or its message) damaged after
signing.  Everything is drawn from the seed: the same seed gives the same
bytes, sizes and schedule.

Outcomes of a send: PASS (its verdict is published by dedup), FAIL (a
damaged signature: no verdict is published) and DUP (a retransmission of
an earlier send: its first copy's verdict is the only one).
"""

import hashlib
import math
from dataclasses import dataclass

import numpy as np

from .ref import ed25519 as ed
from .ref import txn as rtxn

PASS, FAIL, DUP = 0, 1, 2

SHAPES = ("transfer", "vote", "program")
BAD_KINDS = ("s_bit", "s_plus_l", "r_bit", "msg_byte")
SYSTEM_PROGRAM = bytes(32)
# program ids of the "vote" and "program" shapes: fixed so that every
# seed sends the same accounts layout
VOTE_PROGRAM = hashlib.sha256(b"vote-program").digest()
PROGRAM_IDS = [hashlib.sha256(b"program-%d" % i).digest() for i in range(8)]


@dataclass
class Pool:
    """Per distinct transaction: the attributes drawn in the parent,
    enough for a worker to rebuild any slice of the pool."""
    seed: int
    shape: np.ndarray       # index into SHAPES
    nsig: np.ndarray
    msg_len: np.ndarray     # target message length
    payer: np.ndarray       # key index of signer 0
    bad: np.ndarray         # index into BAD_KINDS, -1 = not damaged
    bad_sig: np.ndarray     # which signature is damaged
    sig0: np.ndarray        # first nonce index of the transaction
    base: int = 0           # pool index of the first entry

    def slice(self, lo: int, hi: int) -> "Pool":
        return Pool(self.seed, *(a[lo:hi] for a in (
            self.shape, self.nsig, self.msg_len, self.payer, self.bad,
            self.bad_sig, self.sig0)), base=self.base + lo)


@dataclass
class Traffic:
    wires: bytes            # every pool transaction, back to back
    offs: np.ndarray        # int64 (n+1,): wire i = wires[offs[i]:offs[i+1]]
    nsig: np.ndarray
    tags: np.ndarray        # uint64: first 8 bytes of the first signature
    digest: np.ndarray      # uint64 blake2b-8 of each wire
    bad: np.ndarray
    # the send schedule (open loop; a closed loop sends the pool in turn)
    send_pool: np.ndarray | None = None   # pool index of each send
    send_due: np.ndarray | None = None    # seconds from the loop's start
    send_outcome: np.ndarray | None = None


def _seed_int(seed: int, what: bytes) -> int:
    return int.from_bytes(hashlib.sha512(
        what + seed.to_bytes(16, "little", signed=True)).digest(), "little")


def _draw_pool(mix: dict, seed: int, n: int, rng) -> Pool:
    kinds = mix["kinds"]
    share = np.array([k["share"] for k in kinds], float)
    kind = rng.choice(len(kinds), size=n, p=share / share.sum())
    shape = np.zeros(n, np.int8)
    nsig = np.ones(n, np.int16)
    msg_len = np.zeros(n, np.int32)
    for ki, k in enumerate(kinds):
        sel = np.nonzero(kind == ki)[0]
        m = len(sel)
        shape[sel] = SHAPES.index(k["shape"])
        # sigs: [[count, p], [lo, p, hi], ...]; lo..hi uniform
        opts = k.get("sigs", [[1, 1.0]])
        p = np.array([o[1] for o in opts], float)
        pick = rng.choice(len(opts), size=m, p=p / p.sum())
        for oi, o in enumerate(opts):
            s2 = sel[pick == oi]
            hi = o[2] if len(o) > 2 else o[0]
            nsig[s2] = rng.integers(o[0], hi + 1, size=len(s2))
        d = k.get("msg")
        if d is None:
            continue
        if d["dist"] == "uniform":
            msg_len[sel] = rng.integers(d["lo"], d["hi"] + 1, size=m)
        elif d["dist"] == "lognormal":
            msg_len[sel] = np.round(rng.lognormal(
                math.log(d["median"]), d["sigma"], size=m))
        else:
            raise ValueError(f"unknown size distribution {d['dist']!r}")
    pay = mix["payers"]
    nkeys = int(pay["count"])
    if pay["dist"] == "zipf":
        w = 1.0 / np.arange(1, nkeys + 1) ** float(pay["s"])
        payer = rng.choice(nkeys, size=n, p=w / w.sum())
    elif pay["dist"] == "uniform":
        payer = rng.integers(0, nkeys, size=n)
    else:
        raise ValueError(f"unknown payer distribution {pay['dist']!r}")
    bad = np.full(n, -1, np.int8)
    isbad = rng.random(n) < float(mix.get("bad_share", 0.0))
    kinds_bad = np.array([BAD_KINDS.index(x) for x in
                          mix.get("bad_kinds", BAD_KINDS)])
    bad[isbad] = kinds_bad[rng.integers(0, len(kinds_bad),
                                        size=int(isbad.sum()))]
    bad_sig = (rng.random(n) * nsig).astype(np.int16)
    sig0 = np.zeros(n, np.int64)
    np.cumsum(nsig[:-1], out=sig0[1:])
    return Pool(seed, shape, nsig, msg_len, payer.astype(np.int32), bad,
                bad_sig, sig0)


def key_pubs(seed: int, nkeys: int) -> list[bytes]:
    a0 = _seed_int(seed, b"keys") % ed.L
    return ed.chain_encodings(ed.mul(a0, ed.B), ed.B, nkeys)


def _filler(seed: int, i: int, n: int) -> bytes:
    return hashlib.shake_256(
        b"%d:%d" % (seed, i)).digest(n) if n > 0 else b""


def _build_message(pool: Pool, j: int, signers: list[bytes],
                   extra: bytes) -> bytes:
    """The message of entry j of a pool slice, at its target length where
    the shape allows (a message is never shorter than its accounts
    need)."""
    i = pool.base + j
    shape = SHAPES[pool.shape[j]]
    seed = pool.seed
    blockhash = extra[:32]
    if shape == "transfer":
        # the shape of fddev bench's benchg transfer: payer -> one
        # destination through the system program (message 150 bytes)
        lamports = 1 + (int.from_bytes(extra[32:40], "little") % 10**9)
        data = (2).to_bytes(4, "little") + lamports.to_bytes(8, "little")
        return rtxn.message(signers, [extra[40:72]], [SYSTEM_PROGRAM],
                            blockhash, [(2, bytes([0, 1]), data)])
    prog = VOTE_PROGRAM if shape == "vote" else PROGRAM_IDS[i % 8]
    want_others = 1 if shape == "vote" else 2 + i % 4
    room_all = rtxn.MTU - (1 + 64 * len(signers))
    # a many-signer transaction keeps fewer other accounts, and at twelve
    # signers its instruction names no accounts: the packet holds no more
    for nothers, with_accts in [(o, True) for o in range(want_others, -1, -1)
                                ] + [(0, False)]:
        others = [extra[32 + 32 * k:64 + 32 * k] for k in range(nothers)]
        nacct = len(signers) + len(others)
        accts = bytes(range(nacct)) if with_accts else b""
        base = rtxn.message(signers, others, [prog], blockhash,
                            [(nacct, accts, b"")])
        if len(base) <= room_all:
            break
    # data length that makes the message its target length: the data's
    # compact-u16 prefix grows by one byte from 128 on
    dlen = max(0, min(int(pool.msg_len[j]) - len(base),
                      room_all - len(base)))
    if dlen >= 128:
        dlen -= 1
    return rtxn.message(signers, others, [prog], blockhash,
                        [(nacct, accts, _filler(seed, ~i, dlen))])


def build_slice(args) -> tuple:
    """Worker: wires of the transactions of a pool slice.  Returns (joined
    wires, lengths, first-signature tags, wire digests)."""
    pool, pubs = args
    seed = pool.seed
    nkeys = len(pubs)
    a0 = _seed_int(seed, b"keys") % ed.L
    r0 = _seed_int(seed, b"nonce") % ed.L
    n = len(pool.nsig)
    s_lo, s_hi = int(pool.sig0[0]), int(pool.sig0[-1] + pool.nsig[-1])
    nonces = ed.chain_encodings(ed.mul((r0 + s_lo) % ed.L, ed.B), ed.B,
                                s_hi - s_lo)
    out, lens, tags, digs = [], [], [], []
    for j in range(n):
        i = pool.base + j
        k = int(pool.nsig[j])
        # blockhash, account addresses, then 32 bytes of per-transaction
        # randomness: co-signer keys (2 bytes each) and the damage spot
        extra = _filler(seed, i, 32 * 9)
        rnd = extra[256:]
        keys = [int(pool.payer[j])] + [
            int.from_bytes(rnd[2 * c:2 * c + 2], "little") % nkeys
            for c in range(k - 1)]
        signers = [pubs[key] for key in keys]
        msg = _build_message(pool, j, signers, extra)
        sigs = []
        n0 = int(pool.sig0[j])
        for s, key in enumerate(keys):
            r_enc = nonces[n0 + s - s_lo]
            ks = ed.challenge(r_enc, pubs[key], msg)
            sv = (r0 + n0 + s + ks * ((a0 + key) % ed.L)) % ed.L
            sigs.append(r_enc + sv.to_bytes(32, "little"))
        bad = int(pool.bad[j])
        if bad >= 0:
            b = int(pool.bad_sig[j])
            sig = bytearray(sigs[b])
            kind = BAD_KINDS[bad]
            if kind == "s_bit":
                sig[32 + rnd[24] % 28] ^= 1 << (rnd[25] % 8)
            elif kind == "s_plus_l":
                sv = int.from_bytes(sig[32:], "little") + ed.L
                sig[32:] = sv.to_bytes(32, "little")
            elif kind == "r_bit":
                sig[8 + rnd[24] % 24] ^= 1 << (rnd[25] % 8)
            elif kind == "msg_byte":
                # a byte of the recent blockhash: every signature fails
                m = bytearray(msg)
                m[4 + 32 * m[3] + rnd[24] % 32] ^= 0xFF
                msg = bytes(m)
            sigs[b] = bytes(sig)
        wire = rtxn.assemble(sigs, msg)
        out.append(wire)
        lens.append(len(wire))
        tags.append(int.from_bytes(wire[1:9], "little"))
        digs.append(digest(wire))
    return (b"".join(out), np.array(lens, np.int32),
            np.array(tags, np.uint64), np.array(digs, np.uint64))


def digest(b) -> int:
    return int.from_bytes(hashlib.blake2b(b, digest_size=8).digest(),
                          "little")


def plan(mix: dict, seed: int, seconds: float, pool_min: int = 0):
    """(pool attributes, open-loop schedule or None, key count).  An open
    loop's pool is what its schedule sends; a closed loop's pool has
    pool_min transactions, sent in turn."""
    rng = np.random.default_rng(seed & (2**63 - 1) ^ 0x5EED)
    sched = None
    if mix["loop"] == "open":
        span = float(mix["warmup_s"]) + seconds
        rate = float(mix["rate_txn_s"])
        nest = int(rate * span * 1.1) + 1000
        due = np.cumsum(rng.exponential(1.0 / rate, size=nest))
        while due[-1] < span:
            due = np.concatenate([due, due[-1] + np.cumsum(
                rng.exponential(1.0 / rate, size=nest))])
        due = due[due < span]
        nsend = len(due)
        within = float(mix.get("dup_within_s", 1.0))
        lo = np.searchsorted(due, due - within)
        isdup = (rng.random(nsend) < float(mix.get("dup_share", 0.0))) \
            & (lo < np.arange(nsend))
        pick = rng.random(nsend)
        send_pool = np.zeros(nsend, np.int64)
        send_pool[~isdup] = np.arange(int((~isdup).sum()))
        for s in np.nonzero(isdup)[0]:
            j = int(lo[s]) + int(pick[s] * (s - int(lo[s])))
            send_pool[s] = send_pool[j]
        npool = int((~isdup).sum())
        sched = (send_pool, due, isdup)
    else:
        npool = int(pool_min)
    pool = _draw_pool(mix, seed, npool, rng)
    return pool, sched, int(mix["payers"]["count"])


def assemble_traffic(pool: Pool, sched, parts) -> Traffic:
    wires = b"".join(p[0] for p in parts)
    lens = np.concatenate([p[1] for p in parts])
    offs = np.zeros(len(lens) + 1, np.int64)
    np.cumsum(lens, out=offs[1:])
    t = Traffic(wires, offs, pool.nsig.copy(),
                np.concatenate([p[2] for p in parts]),
                np.concatenate([p[3] for p in parts]), pool.bad.copy())
    if sched is not None:
        send_pool, due, isdup = sched
        t.send_pool, t.send_due = send_pool, due
        t.send_outcome = np.where(isdup, DUP, np.where(
            pool.bad[send_pool] >= 0, FAIL, PASS)).astype(np.int8)
    return t


def slices(n: int, parts: int) -> list[tuple[int, int]]:
    step = max(1, -(-n // parts))
    return [(lo, min(n, lo + step)) for lo in range(0, n, step)]
