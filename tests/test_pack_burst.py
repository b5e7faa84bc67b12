"""PackTile on the mux's burst rx path: on_burst inserts a whole burst and
schedules once, with the same inserted and scheduled set as one-frag
bursts, conflict-free microblocks each published as one burst, counted
parse failures and sheds, and a block that ends every slot."""

import collections
import types

import numpy as np
import pytest

from firedancer_tpu.ballet import txn as txn_lib
from firedancer_tpu.ballet.pack import COMPUTE_BUDGET_PROG_ID
from firedancer_tpu.disco.tiles import PackTile
from firedancer_tpu.tango.ring import FRAG_META_DTYPE

SYSTEM_PROG = bytes(32)
MAX_TXN = 4


class _Metrics:
    def __init__(self):
        self.d = collections.Counter()

    def add(self, k, v=1):
        self.d[k] += v

    def set(self, k, v):
        self.d[k] = v


class _Ctx:
    def __init__(self, nbank=2):
        self.cfg = {"max_txn": MAX_TXN}
        self.tile = types.SimpleNamespace(
            out_links=[f"pack_bank{i}" for i in range(nbank)])
        self.metrics = _Metrics()
        self.out = []        # (out, sig, payload) per published frag
        self.mbs = []        # published microblocks, one burst each

    def publish_burst(self, buf, starts, lens, sigs, out=0, tsorig=0):
        mb = [bytes(buf[s:s + n]) for s, n in zip(starts, lens)]
        assert all(int(s) == out for s in sigs)
        self.out += [(out, out, p) for p in mb]
        self.mbs.append(mb)


def _transfer(i: int) -> bytes:
    """Single-signature SOL transfer from payer i; recipients repeat every
    five payers, so some txns share a writable account."""
    payer = i.to_bytes(4, "little") + b"\x01" * 28
    to = (i % 5).to_bytes(4, "little") + b"\x02" * 28
    data = (2).to_bytes(4, "little") + (1000 + i).to_bytes(8, "little")
    msg = txn_lib.build_unsigned([payer], b"\x11" * 32,
                                 [(2, bytes([0, 1]), data)],
                                 extra_accounts=[to, SYSTEM_PROG],
                                 readonly_unsigned_cnt=1)
    return txn_lib.assemble([bytes([i & 0xFF]) * 64], msg)


def _heavy(i: int) -> bytes:
    """A program call from payer i that asks for the 1.4 M CU limit: a
    48 M CU block holds about 34 of them."""
    payer = i.to_bytes(4, "little") + b"\x03" * 28
    limit = bytes([2]) + (1_400_000).to_bytes(4, "little")
    msg = txn_lib.build_unsigned([payer], b"\x11" * 32,
                                 [(1, b"", limit), (2, bytes([0]), b"\x01")],
                                 extra_accounts=[COMPUTE_BUDGET_PROG_ID,
                                                 b"\x07" * 32],
                                 readonly_unsigned_cnt=2)
    return txn_lib.assemble([bytes([i & 0xFF]) * 64], msg)


def _tile(nbank=2):
    ctx = _Ctx(nbank)
    tile = PackTile()
    tile.init(ctx)
    return tile, ctx


def _rx(wires):
    """The mux's rx scratch for one burst: payloads back to back behind a
    prefix-sum offsets table, with slack past the last one."""
    offs = np.zeros(len(wires) + 1, np.int64)
    offs[1:] = np.cumsum([len(w) for w in wires])
    buf = np.zeros(int(offs[-1]) + 4096, np.uint8)
    buf[:offs[-1]] = np.frombuffer(b"".join(wires), np.uint8)
    metas = np.zeros(len(wires), FRAG_META_DTYPE)
    return metas, buf, offs


def _burst(tile, ctx, wires):
    metas, buf, offs = _rx(wires)
    tile.on_burst(ctx, 0, metas, buf, offs, len(wires))


def _writable(payload):
    p = txn_lib.parse(payload)
    o = p.acct_addr_off
    return {payload[o + i * 32:o + (i + 1) * 32]
            for i in range(p.acct_addr_cnt) if p.is_writable(i)}


N = 40


def test_on_burst_inserts_counts_and_schedules():
    tile, ctx = _tile()
    valid = [_transfer(i) for i in range(N)]
    bad = valid[0][:70]                          # truncated wire
    _burst(tile, ctx, valid[:20] + [bad] + valid[20:])
    m = ctx.metrics.d
    assert m["txn_insert_cnt"] == N
    assert m["parse_fail_cnt"] == 1
    assert m["burst_cnt"] == 1
    # a fresh block budget schedules every valid txn, each once
    published = [p for _, _, p in ctx.out]
    assert collections.Counter(published) == collections.Counter(valid)
    assert not tile.pack.pending
    assert m["microblock_cnt"] == len(ctx.mbs) > 1
    assert m["sched_txn_cnt"] == sum(map(len, ctx.mbs)) == N
    for mb in ctx.mbs:
        assert 1 <= len(mb) <= MAX_TXN
        seen = set()
        for p in mb:
            w = _writable(p)
            assert not (w & seen), "writable account shared in a microblock"
            seen |= w


def test_burst_cnt_counts_calls():
    tile, ctx = _tile()
    for k in range(3):
        _burst(tile, ctx, [_transfer(10 * k + j) for j in range(10)])
    assert ctx.metrics.d["burst_cnt"] == 3
    assert ctx.metrics.d["txn_insert_cnt"] == 30
    assert ctx.metrics.d["parse_fail_cnt"] == 0


@pytest.mark.parametrize("nbank", [1, 2])
def test_whole_burst_matches_single_frag_bursts(nbank):
    """A burst inserts and schedules the same set as the same txns taken
    one frag a burst; only how txns group into microblocks may differ."""
    wires = [_transfer(i) for i in range(N)] + [b"\x01\x02\x03"]
    tb, cb = _tile(nbank)
    _burst(tb, cb, wires)
    tf, cf = _tile(nbank)
    for w in wires:
        _burst(tf, cf, [w])
    for k in ("txn_insert_cnt", "parse_fail_cnt", "sched_txn_cnt"):
        assert cb.metrics.d[k] == cf.metrics.d[k]
    assert cb.metrics.d["sched_txn_cnt"] == N
    assert (collections.Counter(p for _, _, p in cb.out)
            == collections.Counter(p for _, _, p in cf.out))
    assert len(cb.mbs) < len(cf.mbs) == N


def test_pack_metrics_sync_by_delta():
    """txn_insert_cnt, sched_txn_cnt and heap_full_drop_cnt follow
    Pack.metrics by delta: a burst past a heap cap of 8 sheds its tail,
    counted once however often the tile syncs."""
    tile, ctx = _tile()
    tile.pack.max_pending = 8
    _burst(tile, ctx, [_transfer(i) for i in range(12)])
    tile.house(ctx)
    tile.house(ctx)                              # no change, no re-add
    m = ctx.metrics.d
    assert m["txn_insert_cnt"] == 8
    assert m["heap_full_drop_cnt"] == 4
    assert m["sched_txn_cnt"] == 8
    assert m["pending"] == 0
    _burst(tile, ctx, [_transfer(i) for i in range(20, 23)])
    tile.house(ctx)
    assert m["sched_txn_cnt"] == 11
    assert m["heap_full_drop_cnt"] == 4


def test_block_ends_every_slot():
    """A full block holds the rest of the heap until the slot has passed;
    then house ends the block and schedules what was held."""
    tile, ctx = _tile(nbank=1)
    wires = [_heavy(i) for i in range(N)]
    _burst(tile, ctx, wires)
    m = ctx.metrics.d
    first = m["sched_txn_cnt"]
    assert 0 < first < N
    assert m["pending"] == N - first
    tile.house(ctx)                              # same slot: still full
    assert m["sched_txn_cnt"] == first
    tile._block_t0 -= tile.BLOCK_NS              # the slot has passed
    tile.house(ctx)
    assert m["sched_txn_cnt"] == N
    assert m["pending"] == 0
    assert (collections.Counter(p for _, _, p in ctx.out)
            == collections.Counter(wires))
