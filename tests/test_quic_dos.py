"""DoS front-door hardening (waltz/quic.py abuse bounds, the net tile's
pps bucket, and the quic tiles' packed-row publish mode).

Attack traffic is forged with disco.faultinject.WireFaultGen — AEAD-valid
Initials that pass the admission probe, malformed mutations that must die
in the parser, and never-FIN partial stream frames — against raw
endpoints (no sockets, no processes)."""

import os

import numpy as np
import pytest

from firedancer_tpu.disco.faultinject import WireFaultGen
from firedancer_tpu.waltz.aio import Aio, Pkt
from firedancer_tpu.waltz.quic import (CID_SZ, TXN_MTU, QuicConfig,
                                       QuicEndpoint)


def _server(**kw):
    sent = []
    sv = QuicEndpoint(
        QuicConfig(identity_seed=os.urandom(32), is_server=True, **kw),
        Aio(lambda p: sent.extend(p) or len(p)))
    return sv, sent


def _mem_pair(**server_kw):
    c2s, s2c = [], []
    cl = QuicEndpoint(QuicConfig(identity_seed=os.urandom(32)),
                      Aio(lambda p: c2s.extend(p) or len(p)))
    sv = QuicEndpoint(
        QuicConfig(identity_seed=os.urandom(32), is_server=True,
                   **server_kw),
        Aio(lambda p: s2c.extend(p) or len(p)))
    return cl, sv, c2s, s2c


def _handshake(cl, sv, c2s, s2c, now=0.0, iters=40):
    conn = cl.connect(("10.0.0.9", 9001))
    for _ in range(iters):
        now += 0.01
        if c2s:
            pkts, c2s[:] = list(c2s), []
            sv.rx(pkts, now)
        if s2c:
            pkts, s2c[:] = list(s2c), []
            cl.rx(pkts, now)
        if conn.handshake_done:
            break
    assert conn.handshake_done
    return conn, now


def _pump(cl, sv, c2s, s2c, now, steps=20):
    for _ in range(steps):
        now += 0.01
        if c2s:
            pkts, c2s[:] = list(c2s), []
            sv.rx(pkts, now)
        if s2c:
            pkts, s2c[:] = list(s2c), []
            cl.rx(pkts, now)
        cl.service(now)
        sv.service(now)
    return now


# ------------------------------------------------------- admission bounds


def test_per_peer_conn_cap_rejects_flood():
    sv, _ = _server(max_conns=64, max_conns_per_peer=4)
    g = WireFaultGen(3)
    addr = ("9.9.9.9", 1111)
    for d in g.conn_flood(12):
        sv.rx([Pkt(d, addr)], now=1.0)
    assert len(sv.conns) == 4
    assert sv.metrics["conn_reject"] == 8
    assert sv._peer_conns[("9.9.9.9", 1111)[0]] == 4
    # a different peer is still welcome
    sv.rx([Pkt(g.forged_initial()[0], ("8.8.8.8", 2))], now=1.0)
    assert len(sv.conns) == 5


def test_half_open_accounting_decrements_on_drop():
    sv, _ = _server(max_conns=64, idle_timeout=1.0)
    g = WireFaultGen(4)
    for d in g.conn_flood(5):
        sv.rx([Pkt(d, ("7.7.7.7", 1))], now=1.0)
    assert sv.half_open == 5
    sv.service(3.0)                          # idle reaper drops them all
    assert sv.half_open == 0
    assert len(sv.conns) == 0
    assert sv._peer_conns == {}              # peer table can't leak


def test_global_cap_lru_evicts_idle_conn():
    sv, _ = _server(max_conns=3, lru_evict_idle=1.0)
    g = WireFaultGen(5)
    for i, d in enumerate(g.conn_flood(3)):
        sv.rx([Pkt(d, (f"1.1.1.{i}", 1))], now=1.0 + i * 0.1)
    assert len(sv.conns) == 3
    # table full and everyone FRESH (< lru_evict_idle): admission refused —
    # a flood can't churn conns that are actively handshaking
    sv.rx([Pkt(g.forged_initial()[0], ("3.3.3.3", 1))], now=1.5)
    assert len(sv.conns) == 3
    assert sv.metrics["conn_reject"] == 1
    assert sv.metrics["conn_evict"] == 0
    # later, with everyone idle >= lru_evict_idle: the oldest is evicted
    sv.rx([Pkt(g.forged_initial()[0], ("2.2.2.2", 1))], now=5.0)
    assert len(sv.conns) == 3
    assert sv.metrics["conn_evict"] == 1
    assert sv.metrics["conn_reject"] == 1
    assert "2.2.2.2" in sv._peer_conns


def test_retry_threshold_and_token_redeem():
    sv, sent = _server(max_conns=64, retry_half_open_threshold=2)
    g = WireFaultGen(6)
    addr = ("6.6.6.6", 42)
    for d in g.conn_flood(5):
        sv.rx([Pkt(d, addr)], now=1.0)
    # 2 half-opens admitted, then stateless Retries only
    assert len(sv.conns) == 2
    assert sv.metrics["retry_tx"] == 3
    retries = [p.payload for p in sent if (p.payload[0] & 0xF0) == 0xF0]
    assert len(retries) == 3
    r0_scid, r0_tok = WireFaultGen.redeem_retry(retries[0])
    r1_scid, r1_tok = WireFaultGen.redeem_retry(retries[1])
    assert len(r0_scid) == CID_SZ and r0_tok
    # a token presented from a DIFFERENT address is refused silently
    # (the address is AAD in the token AEAD: it fails to open)
    sv.rx([Pkt(g.forged_initial(dcid=r1_scid, token=r1_tok)[0],
               ("66.66.66.66", 42))], now=1.4)
    assert sv.metrics["retry_token_reject"] == 1
    assert len(sv.conns) == 2
    # redeemed from the SAME address: admitted, path validated
    sv.rx([Pkt(g.forged_initial(dcid=r0_scid, token=r0_tok)[0], addr)],
          now=1.5)
    assert sv.metrics["retry_token_accept"] == 1
    assert len(sv.conns) == 3
    conn = sv._initial_conns[r0_scid]
    assert conn.addr_validated


def test_malformed_storm_no_conn_state_no_crash():
    sv, _ = _server(max_conns=64)
    g = WireFaultGen(7)
    for d in g.malformed(160):
        sv.rx([Pkt(d, ("5.5.5.5", 5))], now=1.0)
    assert sv.conns == {}
    m = sv.metrics
    assert m["pkt_malformed"] + m["pkt_undecryptable"] > 0
    assert m["conn_created"] == 0


def test_initial_key_cache_lru_bounds_random_dcid_flood():
    """Every distinct client dcid derives an Initial key schedule at the
    admission probe; the per-endpoint LRU must bound that memory under a
    random-dcid flood and count the evictions."""
    sv, _ = _server(max_conns=128, initial_key_cache=16)
    g = WireFaultGen(11)
    for i in range(64):  # 64 distinct dcids from distinct source IPs
        d = g.forged_initial()[0]
        sv.rx([Pkt(d, (f"9.9.{i}.1", 9))], now=1.0)
    assert len(sv._initial_keys) <= 16
    assert sv.metrics["initial_keys_evict"] >= 64 - 16
    # cache hit path: the SAME dcid probes and admits on one derivation
    sv2, _ = _server(initial_key_cache=16)
    d, dcid, _ = g.forged_initial()
    sv2.rx([Pkt(d, ("8.8.8.8", 8))], now=1.0)
    assert sv2.metrics["conn_created"] == 1
    assert dcid in sv2._initial_keys
    conn = sv2._initial_conns[dcid]
    # the admitted conn holds the CACHED schedule object, not a re-derive
    assert conn.rx_keys[0] is sv2._initial_keys[dcid][0]


def test_initial_key_cache_disabled_derives_direct():
    sv, _ = _server(initial_key_cache=0)
    g = WireFaultGen(12)
    sv.rx([Pkt(g.forged_initial()[0], ("7.7.7.8", 7))], now=1.0)
    assert sv.metrics["conn_created"] == 1
    assert len(sv._initial_keys) == 0


# --------------------------------------------------- stream-level budgets


def test_conn_reasm_budget_evicts_oldest_partials():
    cl, sv, c2s, s2c = _mem_pair(conn_reasm_budget=1000)
    conn, now = _handshake(cl, sv, c2s, s2c)
    g = WireFaultGen(8)
    # 4 x 400 B never-FIN partials on distinct streams > 1000 B budget
    for i in range(4):
        cl.ep_frame = WireFaultGen.partial_stream_frame(
            4_002 + 4 * i, 0, g.oversize_stream_payload(400))
        cl._emit(conn, 2, cl.ep_frame, True, None)
    cl._flush(conn)
    cl._send_pending()
    now = _pump(cl, sv, c2s, s2c, now)
    sconn = next(iter(sv.conns.values()))
    assert sv.metrics["reasm_evict"] >= 1
    assert sconn.reasm_bytes <= 1000
    # whole txns still deliver on the same conn after the shed
    got = []
    sv.on_stream = lambda c, sid, data: got.append(data)
    assert conn.send_txn(b"post-shed" + bytes(64)) is not None
    cl.service(now)
    now = _pump(cl, sv, c2s, s2c, now)
    assert got and got[0][:9] == b"post-shed"


def test_conn_txn_rate_bucket_sheds_and_refills():
    cl, sv, c2s, s2c = _mem_pair(conn_txn_rate=10.0, conn_txn_burst=4)
    conn, now = _handshake(cl, sv, c2s, s2c)
    got = []
    sv.on_stream = lambda c, sid, data: got.append(data)
    for t in range(12):
        assert conn.send_txn(b"txn-%02d" % t) is not None
    cl.service(now)
    now = _pump(cl, sv, c2s, s2c, now, steps=4)  # ~0.04 s: no real refill
    assert len(got) <= 5                     # burst 4 (+<=1 refill token)
    assert sv.metrics["rate_drop"] >= 7
    # a second of refill at 10/s admits more
    n0 = len(got)
    now = _pump(cl, sv, c2s, s2c, now + 1.0, steps=2)
    for t in range(4):
        assert conn.send_txn(b"more-%02d" % t) is not None
    cl.service(now)
    now = _pump(cl, sv, c2s, s2c, now, steps=4)
    assert len(got) > n0


def test_oversize_stream_capped_by_stream_window():
    cl, sv, c2s, s2c = _mem_pair()
    conn, now = _handshake(cl, sv, c2s, s2c)
    sconn = next(iter(sv.conns.values()))
    big = WireFaultGen(9).oversize_stream_payload(2 * TXN_MTU)
    frame = WireFaultGen.partial_stream_frame(4002, sv.rx_max_stream_data,
                                              big[:100])
    cl._emit(conn, 2, frame, True, None)
    cl._flush(conn)
    cl._send_pending()
    now = _pump(cl, sv, c2s, s2c, now, steps=5)
    # data past the advertised stream window is discarded, not buffered
    assert 4002 not in sconn.recv_streams
    assert sconn.reasm_bytes == 0


# ---------------------------------------------------- service deadlines


def test_next_timeout_deadline_driven_service():
    sv, _ = _server(idle_timeout=10.0)
    assert sv.next_timeout() == 0.0          # first service runs at once
    sv.service(100.0)
    assert sv.next_timeout() == pytest.approx(110.0)  # empty: idle horizon
    g = WireFaultGen(10)
    sv.rx([Pkt(g.conn_flood(1)[0], ("4.4.4.4", 4))], now=101.0)
    sv.service(102.0)
    # conn idle deadline (last_rx 101 + 10) bounds the recomputed horizon
    assert sv.next_timeout() <= 111.0 + 1e-9
    # an in-flight ack-eliciting send pulls a CLIENT's deadline to ~now+pto
    c2s = []
    cl = QuicEndpoint(QuicConfig(identity_seed=os.urandom(32)),
                      Aio(lambda p: c2s.extend(p) or len(p)))
    cl.service(50.0)
    assert cl.next_timeout() == pytest.approx(50.0 + cl.idle_timeout)
    cl.connect(("10.0.0.9", 9001), now=50.0)
    assert c2s                               # Initial flight is in flight
    assert cl.next_timeout() <= 50.0 + cl.cfg.pto + 1e-9


def test_service_at_deadline_reaps_idle():
    cl, sv, c2s, s2c = _mem_pair(idle_timeout=1.0)
    conn, now = _handshake(cl, sv, c2s, s2c)
    assert len(sv.conns) == 1
    sv.service(now)
    # drive service() PURELY off next_timeout() (the tile's after_credit
    # loop): the deadlines must converge on the idle reap in bounded time
    t = now
    for _ in range(64):
        t = max(sv.next_timeout(), t) + 1e-3
        sv.service(t)
        if not sv.conns:
            break
    assert len(sv.conns) == 0
    assert t <= now + 5.0
    assert sv.metrics["conn_closed"] == 1


# ------------------------------------------------- packed publish parity


def test_wire_row_matches_txn_parse():
    from firedancer_tpu.ballet import txn as txn_lib
    from firedancer_tpu.disco.tiles import _wire_row
    from firedancer_tpu.ops import ed25519 as ed

    rng = np.random.default_rng(11)
    seed = rng.bytes(32)
    pub, _, _ = ed.keypair_from_seed(seed)
    msg = txn_lib.build_unsigned([pub], rng.bytes(32),
                                 [(1, bytes([0]), b"payload8")],
                                 extra_accounts=[rng.bytes(32)])
    wire = txn_lib.assemble([ed.sign(seed, msg)], msg)
    t = txn_lib.parse(wire)
    row = _wire_row(wire, 256)
    assert not isinstance(row, str)
    k, m, sig, p = row
    assert k == 1
    assert m == t.message(wire)
    assert sig == t.signatures(wire)[0]
    assert p == t.signer_pubkeys(wire)[0] == pub
    # the drop set == the legacy parse-fail, too-long and sig-overflow
    # sets, each under its reason
    from firedancer_tpu.disco.tiles import DROP_LONG, DROP_PARSE, DROP_SIGS
    assert _wire_row(wire[:10], 256) == DROP_PARSE    # truncated
    assert _wire_row(wire, len(m) - 1) == DROP_LONG   # too long for bucket
    assert _wire_row(b"", 256) == DROP_PARSE
    assert _wire_row(wire, 256, max_sigs=0) == DROP_SIGS


class _FakeCtx:
    """Just enough TileCtx for _PackedWirePublisher: one reservation."""

    trace = None        # no span ring
    tsorig = 0          # outside frag processing

    def __init__(self, rows, stride):
        self.buf = np.zeros(rows * stride, np.uint8)
        self.commits = []
        self.metrics = _NetMetrics()

    def out_reserve(self, nbytes):
        assert nbytes == len(self.buf)
        return 7, self.buf

    def out_commit(self, chunk, nbytes, sig=0, sz=None, tsorig=0):
        self.commits.append((chunk, nbytes, sig, sz, self.buf.copy()))


def test_packed_wire_publisher_row_layout():
    from firedancer_tpu.ballet import txn as txn_lib
    from firedancer_tpu.disco.tiles import _PackedWirePublisher
    from firedancer_tpu.ops import ed25519 as ed
    from firedancer_tpu.tango.ring import PACKED_ROW_EXTRA, packed_row_ml

    rows, ml = 4, packed_row_ml(256)
    stride = ml + PACKED_ROW_EXTRA
    ctx = _FakeCtx(rows, stride)
    pub_ = _PackedWirePublisher(ctx, rows=rows, ml=ml)

    rng = np.random.default_rng(12)
    wires = []
    for i in range(rows):
        seed = rng.bytes(32)
        pk, _, _ = ed.keypair_from_seed(seed)
        msg = txn_lib.build_unsigned(
            [pk], rng.bytes(32), [(1, bytes([0]), i.to_bytes(8, "little"))],
            extra_accounts=[rng.bytes(32)])
        wires.append(txn_lib.assemble([ed.sign(seed, msg)], msg))
    for w in wires:
        assert pub_.add(w)
    # auto-flushed at rows
    assert len(ctx.commits) == 1
    chunk, nbytes, sig, sz, blk = ctx.commits[0]
    assert (chunk, nbytes, sz) == (7, rows * stride, rows)
    blk = blk.reshape(rows, stride)
    for i, w in enumerate(wires):
        t = txn_lib.parse(w)
        m = t.message(w)
        assert bytes(blk[i, :len(m)]) == m
        assert bytes(blk[i, ml:ml + 64]) == t.signatures(w)[0]
        assert bytes(blk[i, ml + 64:ml + 96]) == t.signer_pubkeys(w)[0]
        assert int.from_bytes(bytes(blk[i, ml + 96:ml + 100]),
                              "little") == len(m)
    # sig tag = first row's sig64 with the latency bit masked off
    from firedancer_tpu.disco.tiles import LAT_PRIO_BIT
    w0 = wires[0]
    want = (int.from_bytes(txn_lib.parse(w0).signatures(w0)[0][:8],
                           "little") & (LAT_PRIO_BIT - 1))
    assert sig == want
    # garbage is refused without opening a reservation, and counted
    assert not pub_.add(b"\x00")
    assert len(ctx.commits) == 1
    assert ctx.metrics.vals["packed_drop_parse_cnt"] == 1
    assert ctx.metrics.vals["sig_rows_cnt"] == rows


# ------------------------------------------------------- net tile knobs


class _NetMetrics:
    def __init__(self):
        self.vals = {}

    def add(self, k, v=1):
        self.vals[k] = self.vals.get(k, 0) + v

    def set(self, k, v):
        self.vals[k] = v


class _NetCtx:
    def __init__(self):
        self.metrics = _NetMetrics()


def test_net_tile_pps_bucket_and_lru_map():
    from firedancer_tpu.disco.tiles import NetTile

    nt = NetTile.__new__(NetTile)
    nt._pps, nt._pps_burst = 10.0, 2.0
    from collections import OrderedDict
    nt._src_buckets = OrderedDict()
    nt._last_shed = -1e9
    ctx = _NetCtx()
    # burst of 2 admitted, then shed until refill
    assert nt._admit(ctx, "1.2.3.4", 0.0)
    assert nt._admit(ctx, "1.2.3.4", 0.0)
    assert not nt._admit(ctx, "1.2.3.4", 0.0)
    assert ctx.metrics.vals["rate_drop_cnt"] == 1
    assert nt._admit(ctx, "1.2.3.4", 0.2)    # +2 tokens after 0.2 s
    # other sources are independent
    assert nt._admit(ctx, "4.3.2.1", 0.2)
    # the source map is LRU-bounded
    nt._SRC_MAP_CAP = 4
    for i in range(8):
        nt._admit(ctx, f"10.0.0.{i}", 0.3)
    assert len(nt._src_buckets) <= 4


def test_net_tile_fini_idempotent_and_ordered():
    from firedancer_tpu.disco.tiles import NetTile

    closed = []

    class _S:
        def __init__(self, n):
            self.n = n

        def close(self):
            closed.append(self.n)

    nt = NetTile.__new__(NetTile)
    nt._xdp_fds = ()
    nt.socks = [(_S("a"), 0), (_S("b"), 1)]
    nt.fini(None)
    assert closed == ["a", "b"]
    assert nt.socks == [] and nt._xdp_fds == ()
    nt.fini(None)                            # re-entrant: a no-op
    nt.fini(None)
    assert closed == ["a", "b"]


# --------------------------------------------------------- forged packets


def test_forged_initial_is_aead_valid_and_deterministic():
    g1, g2 = WireFaultGen(77), WireFaultGen(77)
    d1 = [g1.forged_initial()[0] for _ in range(3)]
    d2 = [g2.forged_initial()[0] for _ in range(3)]
    assert d1 == d2                          # seeded: replays identically
    sv, _ = _server(max_conns=64)
    sv.rx([Pkt(d1[0], ("1.2.3.4", 9))], now=1.0)
    assert sv.metrics["conn_created"] == 1
    assert sv.metrics["pkt_undecryptable"] == 0
