"""Multi-signature and long transactions on the zero-copy packed path.

The quic tile's packed publisher stamps a txn of k signatures as k
contiguous rows, one per signature, their len words marked with the
signature index and count (tango/ring.py); the verify pipeline tags and
dedups per txn, passes a txn only if all its rows pass, and rebuilds its
wire byte for byte.  All CPU, no device compile: verdicts come from the
host verifier, which the degraded-mode tests hold bit-identical to the
device graph, or from a script.

  (a) packed path == legacy burst path: verdict stream and wires
  (b) both == the plain reference (benchmark/ref), independent of both
  (c) a frame never splits a txn; the frame closes when the next does
      not fit; the quic coalesce span counts rows and txns
  (d) fd_hostpath_finish_rows == _np_finish on multi-signature frames
  (e) only a txn's first signature reaches the tcache
  (f) txn_in_cnt counts txns, lanes_* count rows
  (g) a message longer than the row is counted under its own reason
"""

import numpy as np
import pytest

from benchmark import gen
from benchmark.ref import ed25519 as ref_ed
from benchmark.ref import txn as ref_txn
from firedancer_tpu.disco.pipeline import PackedVerdicts, VerifyPipeline
from firedancer_tpu.disco.tiles import _PackedWirePublisher, _wire_row
from firedancer_tpu.ops import ed25519 as ed
from firedancer_tpu.tango.ring import (PACKED_LEN_MASK, PACKED_ROW_EXTRA,
                                       packed_row_ml)

ML = packed_row_ml(476)          # 476: a 12-signer message fits the row
STRIDE = ML + PACKED_ROW_EXTRA
B = 32                           # rows per frame and per legacy batch
SHAPE = {s: i for i, s in enumerate(gen.SHAPES)}
BAD = {k: i for i, k in enumerate(gen.BAD_KINDS)}


def _build(seed, specs):
    """Wires of txns given as (signatures, message length or 0 for a
    transfer, damage kind or None, damaged signature index)."""
    n = len(specs)
    nsig = np.array([s[0] for s in specs], np.int16)
    sig0 = np.zeros(n, np.int64)
    np.cumsum(nsig[:-1], out=sig0[1:])
    pool = gen.Pool(
        seed,
        np.array([SHAPE["program" if s[1] else "transfer"] for s in specs],
                 np.int8),
        nsig, np.array([s[1] for s in specs], np.int32),
        np.arange(n, dtype=np.int32) % 16,
        np.array([-1 if s[2] is None else BAD[s[2]] for s in specs],
                 np.int8),
        np.array([s[3] for s in specs], np.int16), sig0)
    buf, lens, _, _ = gen.build_slice((pool, gen.key_pubs(seed, 16)))
    offs = np.r_[0, np.cumsum(lens)]
    return [buf[offs[i]:offs[i + 1]] for i in range(n)]


def _mix_specs():
    """1 to 12 signatures, messages up to the row and past it, each kind
    of damage, and damage at every signature index of a 12-signature
    txn."""
    rng = np.random.default_rng(26)
    kinds = list(BAD)
    specs = [(1, 0, None, 0), (1, 0, "s_bit", 0), (1, ML, None, 0),
             (2, ML, None, 0)]
    for k in range(2, 13):
        specs.append((k, int(rng.integers(150, ML + 1)), None, 0))
        specs.append((k, int(rng.integers(150, ML + 1)),
                      kinds[k % len(kinds)], int(rng.integers(0, k))))
    specs += [(12, ML, kinds[j % len(kinds)], j) for j in range(12)]
    specs += [(3, ML + 40, None, 0), (1, 1000, None, 0)]   # too long
    order = rng.permutation(len(specs))
    return [specs[i] for i in order]


class _Metrics:
    def __init__(self):
        self.vals = {}

    def add(self, k, v=1):
        self.vals[k] = self.vals.get(k, 0) + v

    def set(self, k, v):
        self.vals[k] = v

    def get(self, k):
        return self.vals.get(k, 0)


class _Trace:
    def __init__(self):
        self.recs = []

    def record(self, kind, ts, dur, **kw):
        self.recs.append((kind, kw))


class _FrameCtx:
    """Just enough TileCtx for _PackedWirePublisher: keeps every
    committed frame as (rows, row count)."""

    tsorig = 0

    def __init__(self, rows, stride, trace=None):
        self.rows, self.stride = rows, stride
        self.frames = []
        self.metrics = _Metrics()
        self.trace = trace

    def out_reserve(self, nbytes):
        self._buf = np.zeros(nbytes, np.uint8)
        return 1, self._buf

    def out_commit(self, chunk, nbytes, sig=0, sz=None, tsorig=0):
        self.frames.append(
            (self._buf.reshape(self.rows, self.stride).copy(), sz))
        return len(self.frames)


def _frames(wires, rows=B, ml=ML, trace=None):
    ctx = _FrameCtx(rows, ml + PACKED_ROW_EXTRA, trace)
    pub = _PackedWirePublisher(ctx, rows=rows, ml=ml)
    stamped = [w for w in wires if pub.add(w)]
    pub.flush()
    return ctx, stamped


class _HostVerifier:
    """Packed and 4-array verifier on the host (models.verifier
    host_verify_arrays semantics), memoised per (sig, pub, msg) so two
    paths over one mix pay for each signature once."""

    mode = "strict"
    _memo = {}

    def _verify(self, msgs, lens, sigs, pubs):
        out = np.zeros(len(sigs), bool)
        for i in range(len(sigs)):
            sig, pub = bytes(sigs[i]), bytes(pubs[i])
            if not (any(sig) or any(pub)):
                continue        # padding lane
            key = (sig, pub, bytes(msgs[i, :int(lens[i])]))
            if key not in self._memo:
                self._memo[key] = ed.verify_one_host(key[0], key[2],
                                                     key[1])
            out[i] = self._memo[key]
        return out

    def __call__(self, msgs, lens, sigs, pubs):
        return self._verify(np.asarray(msgs), np.asarray(lens),
                            np.asarray(sigs), np.asarray(pubs))

    def dispatch_blob(self, blob, maxlen=None):
        ml = blob.shape[1] - PACKED_ROW_EXTRA
        lens = np.ascontiguousarray(blob[:, ml + 96:ml + 100]).view(
            np.uint32).ravel() & PACKED_LEN_MASK
        return self._verify(blob[:, :ml], lens, blob[:, ml:ml + 64],
                            blob[:, ml + 64:ml + 96])


def _packed_run(frames, native, egress_packed=False, fn=None):
    pipe = VerifyPipeline(fn or _HostVerifier(), buckets=[(B, ML)],
                          tcache_depth=1 << 10, max_inflight=0,
                          native_hostpath=native,
                          egress_packed=egress_packed)
    if native and pipe._hp is None:
        pytest.skip("native hostpath library unavailable")
    out = []
    for rows, n in frames:
        for v in pipe.submit_packed_rows(rows, n=n):
            out += v.wires() if isinstance(v, PackedVerdicts) else [v[0]]
    return pipe, out


@pytest.fixture(scope="module")
def mix():
    specs = _mix_specs()
    return specs, _build(2**31 + 26, specs)


@pytest.mark.parametrize("native,egress_packed", [
    (True, False), (True, True), (False, False), (False, True)])
def test_packed_path_matches_legacy_burst_path(mix, native, egress_packed):
    """(a): the same verdict stream, wire for wire and in order, as the
    legacy per-txn burst path (native parser, segmented minimum)."""
    _, wires = mix
    ctx, _ = _frames(wires)
    pipe, packed = _packed_run(ctx.frames, native, egress_packed)
    legacy = VerifyPipeline(_HostVerifier(), batch=B, msg_maxlen=ML,
                            tcache_depth=1 << 10, max_inflight=0)
    want = [w for w, _ in legacy.submit_burst(wires)]
    want += [w for w, _ in legacy.flush()]
    assert packed == want
    assert len(want) > 10
    for k in ("verify_pass", "verify_fail", "dedup_drop", "lanes_filled"):
        assert pipe.metrics.snapshot()[k] == legacy.metrics.snapshot()[k]


def test_packed_verdicts_match_reference(mix):
    """(b): a txn passes the packed path iff the plain reference verifies
    every one of its signatures over its message, and its message fits
    the row; each passing wire is the bytes sent."""
    specs, wires = mix
    _, packed = _packed_run(_frames(wires)[0].frames, native=True)
    want = []
    for (k, _, bad, _), w in zip(specs, wires):
        sigs, pubs, msg = ref_txn.parse(w)
        ok = all(ref_ed.verify(p, msg, s) for s, p in zip(sigs, pubs))
        assert ok == (bad is None)
        if ok and len(msg) <= ML:
            want.append(w)
    assert packed == want
    assert any(w[0] == 12 for w in packed)


def test_frames_never_split_a_txn():
    """(c): each frame holds whole txns; a txn whose rows do not fit
    closes the frame first; the coalesce span counts rows and txns."""
    specs = [(5, 300, None, 0), (2, 200, None, 0), (3, 300, None, 0),
             (1, 0, None, 0), (8, 460, None, 0), (4, 300, None, 0)]
    trace = _Trace()
    ctx, stamped = _frames(_build(7, specs), rows=8, trace=trace)
    assert len(stamped) == len(specs)
    # 5+2 | 3+1+(8 does not fit: closes) | 8 | 4
    assert [n for _, n in ctx.frames] == [7, 4, 8, 4]
    for rows, n in ctx.frames:
        word = np.ascontiguousarray(rows[:, ML + 96:ML + 100]).view(
            np.uint32).ravel()
        idx, more = (word >> 16) & 0xFF, word >> 24
        r = 0
        while r < n:
            k = int(more[r]) + 1
            assert list(idx[r:r + k]) == list(range(k))
            assert r + k <= n
            r += k
        assert not word[n:].any()
    co = [kw for kind, kw in trace.recs]
    assert [(kw["cnt"], kw["txn_cnt"]) for kw in co] == [
        (7, 2), (4, 2), (8, 1), (4, 1)]
    m = ctx.metrics.vals
    assert m["sig_rows_cnt"] == 23
    assert m["packed_stamp_ns"] > 0


class _VerdictFn:
    """Scripted packed verifier: row i of dispatch j passes iff
    script[j][i]."""

    def __init__(self, script):
        self.script = list(script)
        self.calls = 0

    def __call__(self, m, ln, s, p):
        return np.ones(m.shape[0], bool)

    def dispatch_blob(self, blob, maxlen=None):
        ok = np.zeros(blob.shape[0], bool)
        want = self.script[self.calls]
        self.calls += 1
        ok[:len(want)] = want
        return ok


def _odd_frames():
    """Multi-signature frames with every harvest case: a failing row in
    several txns, a resubmitted frame (submit-time dups), a txn twice in
    one frame (a harvest-time dup), rows before the first txn, a marker
    that claims more rows than the txn has, a dead first row, zero
    padding."""
    specs = [(1, 0, None, 0), (3, 200, None, 0), (12, 460, None, 0),
             (2, 150, None, 0), (4, 300, None, 0), (3, 250, None, 0),
             (2, 180, None, 0), (4, 320, None, 0), (2, 160, None, 0),
             (4, 330, None, 0), (2, 170, None, 0), (3, 210, None, 0)]
    w = _build(11, specs)

    def frame(ws):
        rows, n = _frames(ws)[0].frames[0]
        return rows, n

    def words(rows):
        return rows[:, ML + 96:ML + 100].view(np.uint32)[:, 0]

    a, na = frame(w[0:5])                        # rows 1-3 | 4-15 | 16-17
    ok_a = np.ones(na, bool)
    ok_a[[2, 10, 16]] = False
    c, nc = frame([w[5], w[6], w[5], w[7]])
    d, nd = frame([w[8], w[9]])
    words(d)[0:2] |= 1 << 16                     # w8's rows: no first row
    words(d)[2:6] = (words(d)[2:6] & 0x00FFFFFF) | (4 << 24)   # claims 5
    e, ne = frame([w[10], w[11]])
    e[0, ML:ML + 8] = 0                          # w10's first row dead
    return [(a, na, ok_a), (a, na, np.ones(na, bool)),
            (c, nc, np.ones(nc, bool)), (d, nd, np.ones(nd, bool)),
            (e, ne, np.ones(ne, bool))]


def _scripted(frames, native, egress_packed):
    fn = _VerdictFn([ok for _, _, ok in frames])
    pipe, out = _packed_run([(r, n) for r, n, _ in frames], native,
                            egress_packed, fn=fn)
    return pipe, out


@pytest.mark.parametrize("egress_packed", [False, True])
def test_native_finish_matches_numpy_on_multisig_frames(egress_packed):
    """(d): the C submit/finish and the NumPy twin give the same wires,
    tags and counters on multi-signature frames."""
    frames = _odd_frames()
    nat, w_nat = _scripted(frames, True, egress_packed)
    npy, w_np = _scripted(frames, False, egress_packed)
    assert w_nat == w_np
    counts = ("txns_in", "dedup_drop", "verify_fail", "verify_pass",
              "lanes_filled", "lanes_dispatched", "msg_bytes",
              "multisig_txns")
    assert ({k: nat.metrics.snapshot()[k] for k in counts}
            == {k: npy.metrics.snapshot()[k] for k in counts})
    m = nat.metrics
    # a: 3 of 5 fail; a again: 2 submit-time dups, 3 pass; c: 3 pass and
    # the repeat a harvest-time dup; d: w8 belongs to no txn, w9 fails on
    # its marker; e: w10 dead, w11 passes
    assert (m.verify_pass, m.verify_fail, m.dedup_drop) == (9, 4, 3)
    assert m.txns_in == 5 + 5 + 4 + 1 + 2
    for wire in w_nat:
        t_sigs, _, msg = ref_txn.parse(wire)
        assert wire == bytes([len(t_sigs)]) + b"".join(t_sigs) + msg


@pytest.mark.parametrize("native", [True, False])
def test_only_first_signature_reaches_tcache(mix, native):
    """(e): a passing txn's first signature is its dedup tag; no later
    signature is ever queried or inserted as one."""
    _, wires = mix
    pipe, packed = _packed_run(_frames(wires)[0].frames, native)
    assert any(w[0] > 1 for w in packed)
    for w in packed:
        sigs, _, _ = ref_txn.parse(w)
        tags = [int.from_bytes(s[:8], "little") for s in sigs]
        assert pipe.tcache.query(tags[0])
        assert not any(pipe.tcache.query(t) for t in tags[1:])


@pytest.mark.parametrize("native", [True, False])
def test_counters_count_txns_and_rows(mix, native):
    """(f): txn_in_cnt counts transactions, lanes_* count rows; the
    message bytes and multi-signature count are per row and per txn."""
    _, wires = mix
    ctx, stamped = _frames(wires)
    pipe, _ = _packed_run(ctx.frames, native)
    parsed = [ref_txn.parse(w) for w in stamped]
    m = pipe.metrics
    assert m.txns_in == len(stamped)
    assert m.multisig_txns == sum(len(s) > 1 for s, _, _ in parsed)
    assert m.lanes_filled == sum(len(s) for s, _, _ in parsed)
    assert m.lanes_dispatched == len(ctx.frames) * B
    assert m.msg_bytes == sum(len(s) * len(msg) for s, _, msg in parsed)
    assert m.verify_pass + m.verify_fail == len(stamped)


def test_quic_tile_counts_packed_drops_by_reason():
    """(g): at the quic tile, a message longer than the row and a txn
    that fails to parse each land under their own packed_drop reason;
    reasm_drop_cnt stays for the reasm's own drops."""
    from firedancer_tpu.disco.tiles import QuicTile

    class Ctx(_FrameCtx):
        cfg = {"packed_publish": 1, "packed_rows": B, "packed_ml": ML}

    wires = _build(3, [(2, 300, None, 0), (1, 1000, None, 0),
                       (4, ML + 1, None, 0), (1, 0, None, 0)])
    ctx = Ctx(B, STRIDE)
    tile = QuicTile()
    tile.init(ctx)
    data = wires + [wires[0][:40], b""]
    buf = np.frombuffer(b"".join(data), np.uint8)
    offs = np.r_[0, np.cumsum([len(d) for d in data])]
    tile.on_burst(ctx, 0, None, buf, offs, len(data))
    tile.fini(ctx)
    m = ctx.metrics.vals
    assert m["reasm_pub_cnt"] == 2
    assert m["packed_drop_long_cnt"] == 2
    assert m["packed_drop_parse_cnt"] == 1
    assert m["packed_drop_cnt"] == 3
    assert m["reasm_drop_cnt"] == 1          # the empty datagram
    assert m["sig_rows_cnt"] == 3
    assert [n for _, n in ctx.frames] == [3]


def test_row_stamp_at_mtu_width():
    """Rows at ml 1180 (msg_maxlen 1167, the longest message a 1232-byte
    packet holds): a full-packet single-signature txn and a full-packet
    12-signature txn stamp every field, no kernel involved."""
    ml = packed_row_ml(1167)
    assert ml == 1180 and ml + PACKED_ROW_EXTRA == 1280
    wires = _build(9, [(1, 1167, None, 0), (12, 1232, None, 0)])
    assert [len(w) for w in wires] == [1232, 1232]
    ctx, stamped = _frames(wires, rows=16, ml=ml)
    assert stamped == wires
    (rows, n), = ctx.frames
    assert n == 13
    r = 0
    for w in wires:
        k, msg, sigs, pubs = _wire_row(w, ml)
        sigs_ref, pubs_ref, msg_ref = ref_txn.parse(w)
        assert (msg, sigs, pubs) == (msg_ref, b"".join(sigs_ref),
                                     b"".join(pubs_ref))
        for i in range(k):
            row = rows[r + i]
            assert bytes(row[:len(msg)]) == msg
            assert not row[len(msg):ml].any()
            assert bytes(row[ml:ml + 64]) == sigs_ref[i]
            assert bytes(row[ml + 64:ml + 96]) == pubs_ref[i]
            word = int.from_bytes(bytes(row[ml + 96:ml + 100]), "little")
            assert word == len(msg) | i << 16 | (k - 1) << 24
        r += k
    assert _wire_row(wires[0], 1166) == "long"


def test_packed_topology_fits_its_workspace_at_mtu_width():
    """The fdtpu topology on the packed path at msg_maxlen 1167 (stride
    1280, batch 2048) lays out inside its workspace."""
    import uuid

    from firedancer_tpu.app import config as config_mod
    from firedancer_tpu.disco import topo as topo_mod

    cfg = config_mod.load(None, environ={})
    cfg["name"] = "mtu" + uuid.uuid4().hex[:12]
    cfg["quic"]["packed_publish"] = 1
    cfg.setdefault("ingest", {})["egress_packed"] = 1
    cfg["layout"]["verify_tile_count"] = 1
    cfg["tiles"]["verify"].update(batch=2048, msg_maxlen=1167)
    spec = config_mod.build_topology(cfg)
    links = {ls.name: ls for ls in spec.links}
    assert links["quic_verify"].mtu == 2048 * 1280
    jt = topo_mod.create(spec)
    try:
        assert jt.ws._top <= spec.wksp_mb << 20
    finally:
        jt.close()
        jt.unlink()
