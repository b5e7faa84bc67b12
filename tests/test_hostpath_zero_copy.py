"""Zero-copy wire->device host path (round 8 tentpole).

Five falsifiable contracts, all CPU:

  1. NO MATERIALIZATION — ring tx of a large frag never builds an
     intermediate bytes copy (the old ctypes.c_char_p(bytes(buf))), and
     dcache views share memory with the shm mapping.
  2. ONE COPY, EARLY CREDIT — on the bulk lane the blob handed to
     dispatch_blob by submit_packed_rows is the pipeline's own call
     buffer (the frame's rows copied once, no repack), and the frame's
     credit is released at that copy, before its verdict lands.  On the
     low-latency lane the blob IS the dcache shm region
     (np.shares_memory), its credit held until the verdict.
  3. NO TORN BUFFER — an overrun between rx and the seq re-check drops
     the batch whole (torn_drop) and still releases the credit.
  4. BIT IDENTITY — verdicts through the zero-repack submit_rows path
     equal the legacy _pack_into path on a mixed valid/tampered batch,
     fixed seed.
  5. WIRE RECONSTRUCTION — passing rows rebuild the exact single-sig
     wire form (0x01 | sig | msg) from the pinned view, with tags
     inserted into the tcache only after verify passes.
"""

import secrets
import tracemalloc

import numpy as np
import pytest

from firedancer_tpu.ballet import txn as txn_lib
from firedancer_tpu.disco.pipeline import VerifyPipeline
from firedancer_tpu.disco.topo import LinkSpec, TileSpec, TopoSpec, \
    assign_affinity
from firedancer_tpu.ops import ed25519 as ed
from firedancer_tpu.tango.ring import (
    Dcache,
    MCache,
    PACKED_ROW_EXTRA,
    Workspace,
    packed_row_ml,
    tx_burst,
)

ML = packed_row_ml(256)          # 284: stride 384 == 6 chunks exactly
STRIDE = ML + PACKED_ROW_EXTRA


def test_packed_row_ml_chunk_aligned():
    for maxlen in (1, 64, 96, 256, 1232):
        ml = packed_row_ml(maxlen)
        assert ml >= maxlen
        assert (ml + PACKED_ROW_EXTRA) % 64 == 0
    assert packed_row_ml(256) == 284
    with pytest.raises(ValueError):
        packed_row_ml(0)


@pytest.fixture
def ring():
    ws = Workspace("fdtpu_test_hostpath", 32 << 20, create=True)
    try:
        mc = MCache.new(ws, 4)
        dc = Dcache.new(ws, 4 << 20, 2, 1)
        yield ws, mc, dc
    finally:
        # test-held views export pointers into the mapping; the mapping
        # dies with the process if one survives gc (same stance as
        # JoinedTopology.close)
        mc = dc = None
        import gc
        gc.collect()
        try:
            ws.close()
        except BufferError:
            pass
        ws.unlink()


def test_dcache_views_share_shm(ring):
    ws, mc, dc = ring
    w = dc.write_view(dc.chunk0, 3 * STRIDE)
    assert np.shares_memory(w, dc._arr)
    w[:] = 7
    rows = dc.rows(dc.chunk0, 3, STRIDE)
    assert rows.shape == (3, STRIDE)
    assert np.shares_memory(rows, dc._arr)
    assert (rows == 7).all()
    # advance lands on the next chunk boundary, never splitting a frag
    nxt = dc.advance(dc.chunk0, 3 * STRIDE)
    assert nxt == dc.chunk0 + 3 * STRIDE // dc.chunk_sz
    with pytest.raises(ValueError):
        dc.view(dc.chunk0, dc.data_sz + 64)


def test_tx_burst_no_bytes_materialization(ring):
    """Satellite 1: a 4 MB frag through tx_burst must not materialize an
    intermediate bytes copy of the payload (numpy routes allocations
    through tracemalloc, so a bytes(buf) or asarray copy would show as a
    ~4 MB peak; the zero-copy path allocates only scratch)."""
    ws, mc, dc = ring
    frag = np.arange(4 << 20, dtype=np.uint8)  # wraps mod 256; fine
    starts = np.zeros(1, np.int64)
    lens = np.array([frag.nbytes], np.int32)
    sigs = np.array([1], np.uint64)
    tx_burst(mc, dc, dc.chunk0, frag, starts, lens, sigs)  # warm scratch
    tracemalloc.start()
    tx_burst(mc, dc, dc.chunk0, frag, starts, lens, sigs)
    _, peak = tracemalloc.get_traced_memory()
    tracemalloc.stop()
    assert peak < frag.nbytes // 4, \
        f"tx materialized ~{peak} bytes for a {frag.nbytes} B frag"
    # and the memoryview/bytes entry points wrap zero-copy too
    mv = memoryview(bytes(frag))
    tracemalloc.start()
    tx_burst(mc, dc, dc.chunk0, mv, starts, lens, sigs)
    _, peak = tracemalloc.get_traced_memory()
    tracemalloc.stop()
    assert peak < frag.nbytes // 4


class _FakeBlobFn:
    """Captures the exact array object handed to dispatch; all-pass."""

    def __init__(self):
        self.blobs = []

    def __call__(self, m, ln, s, p):
        return np.ones(m.shape[0], bool)

    def dispatch_blob(self, blob, maxlen=None):
        self.blobs.append(blob)
        return np.ones(blob.shape[0], bool)


def _stamp_rows(view, wires, pubs, ml=ML):
    """Producer-side packed-row stamp: wire i = 0x01 | sig | msg."""
    for i, (w, pub) in enumerate(zip(wires, pubs)):
        msg = w[65:]
        view[i, :len(msg)] = np.frombuffer(msg, np.uint8)
        view[i, ml:ml + 64] = np.frombuffer(w[1:65], np.uint8)
        view[i, ml + 64:ml + 96] = np.frombuffer(pub, np.uint8)
        view[i, ml + 96:ml + 100] = np.frombuffer(
            len(msg).to_bytes(4, "little"), np.uint8)


def _signed_txn(seed: bytes, nonce: int) -> tuple[bytes, bytes]:
    pub = ed.keypair_from_seed(seed)[0]
    msg = txn_lib.build_unsigned(
        [pub], secrets.token_bytes(32),
        [(1, b"\x00", nonce.to_bytes(8, "little"))],
        [secrets.token_bytes(32)])
    return txn_lib.assemble([ed.sign(seed, msg)], msg), pub


def test_dispatch_receives_shm_view_not_copy(ring):
    """The low-latency lane dispatches in place: ZERO payload copies
    between ring rx and device dispatch — the blob at dispatch_blob IS
    dcache memory — and the credit is held until the verdict."""
    ws, mc, dc = ring
    fn = _FakeBlobFn()
    pipe = VerifyPipeline(fn, buckets=[(4, ML)], tcache_depth=64,
                          max_inflight=0, lat_shapes=(4,))
    rows = dc.rows(dc.chunk0, 4, STRIDE)
    wires_pubs = [_signed_txn(bytes([i + 1]) * 32, i) for i in range(4)]
    _stamp_rows(rows, [w for w, _ in wires_pubs],
                [p for _, p in wires_pubs])
    mc.publish(sig=1, chunk=dc.chunk0, sz=4)
    released = []
    passed = pipe.submit_packed_rows(
        rows, n=4, guard=(mc, 0), lat=True,
        release_cb=lambda: released.append(len(fn.blobs)))
    assert len(fn.blobs) == 1
    assert np.shares_memory(fn.blobs[0], dc._arr), \
        "dispatch got a copy, not the dcache view"
    assert [p for p, _ in passed] == [w for w, _ in wires_pubs]
    assert released == [1], "released once, after the dispatch"
    assert pipe.metrics.lat_batches == 1
    assert pipe.metrics.torn_drop == 0


class _HeldVerdict:
    """A verdict that stays not-ready until the test lets it go."""

    def __init__(self, n):
        self.n, self.ready = n, False

    def is_ready(self):
        return self.ready

    def __array__(self, dtype=None, copy=None):
        return np.ones(self.n, bool)


def test_bulk_dispatch_is_call_buffer_and_credit_returns_at_copy(ring):
    """The bulk lane copies the frame's rows once into the pipeline's call
    buffer, dispatches that buffer (not the dcache view), and returns the
    frame's credit at the copy: before its verdict has landed."""
    ws, mc, dc = ring
    verdicts = []

    class _Fn(_FakeBlobFn):
        def dispatch_blob(self, blob, maxlen=None):
            self.blobs.append(blob)
            verdicts.append(_HeldVerdict(blob.shape[0]))
            return verdicts[-1]

    fn = _Fn()
    pipe = VerifyPipeline(fn, buckets=[(4, ML)], tcache_depth=64,
                          max_inflight=2)
    rows = dc.rows(dc.chunk0, 4, STRIDE)
    wires_pubs = [_signed_txn(bytes([i + 70]) * 32, 500 + i)
                  for i in range(3)]
    _stamp_rows(rows, [w for w, _ in wires_pubs],
                [p for _, p in wires_pubs])
    mc.publish(sig=1, chunk=dc.chunk0, sz=3)
    released = []
    passed = pipe.submit_packed_rows(rows, n=3, guard=(mc, 0),
                                     release_cb=lambda: released.append(1))
    assert passed == [] and released == [1]
    assert len(fn.blobs) == 1 and len(pipe.inflight) == 1
    blob = fn.blobs[0]
    assert not np.shares_memory(blob, dc._arr)
    assert blob is pipe.inflight[0].buf[0], "dispatch got the call buffer"
    np.testing.assert_array_equal(blob[:3], rows[:3])
    assert not blob[3:].any(), "rows past the fill read as dead lanes"
    # the producer may now overwrite the frame: the verdict still
    # rebuilds the wires from the call buffer
    rows[:] = 0
    verdicts[0].ready = True
    passed = pipe.harvest()
    assert [p for p, _ in passed] == [w for w, _ in wires_pubs]
    assert released == [1]


def test_torn_upload_detected_and_dropped(ring):
    """Satellite 3: producer laps the mcache between rx and the
    post-dispatch re-check -> batch dropped whole, credit released."""
    ws, mc, dc = ring
    fn = _FakeBlobFn()
    pipe = VerifyPipeline(fn, buckets=[(4, ML)], tcache_depth=64,
                          max_inflight=0)
    rows = dc.rows(dc.chunk0, 4, STRIDE)
    wires_pubs = [_signed_txn(bytes([i + 9]) * 32, 100 + i)
                  for i in range(4)]
    _stamp_rows(rows, [w for w, _ in wires_pubs],
                [p for _, p in wires_pubs])
    # depth-4 mcache: seq 0 published, then lapped by 4 more publishes
    for s in range(5):
        mc.publish(sig=s + 1, chunk=dc.chunk0, sz=4)
    released = []
    passed = pipe.submit_packed_rows(rows, n=4, guard=(mc, 0),
                                     release_cb=lambda: released.append(1))
    assert passed == []
    assert pipe.metrics.torn_drop == 1
    assert released == [1], "credit must release exactly once on torn drop"
    assert pipe.metrics.verify_pass == 0


def test_release_fires_once_on_clean_path(ring):
    ws, mc, dc = ring
    fn = _FakeBlobFn()
    pipe = VerifyPipeline(fn, buckets=[(4, ML)], tcache_depth=64,
                          max_inflight=0)
    rows = dc.rows(dc.chunk0, 4, STRIDE)
    wires_pubs = [_signed_txn(bytes([i + 20]) * 32, 200 + i)
                  for i in range(4)]
    _stamp_rows(rows, [w for w, _ in wires_pubs],
                [p for _, p in wires_pubs])
    mc.publish(sig=1, chunk=dc.chunk0, sz=4)
    released = []
    pipe.submit_packed_rows(rows, n=4, guard=(mc, 0),
                            release_cb=lambda: released.append(1))
    assert released == [1]


def test_wire_reconstruction_and_harvest_dedup():
    """Contract 5 with a REAL verifier: mixed valid/tampered rows, n <
    batch (zero padding), tags inserted only after verify passes."""
    import jax
    from firedancer_tpu.disco.tiles import _jit_blob_fn

    fn = _jit_blob_fn(jax.jit(ed.verify_batch))
    pipe = VerifyPipeline(fn, buckets=[(8, ML)], tcache_depth=64,
                          max_inflight=0)
    rows = np.zeros((8, STRIDE), np.uint8)
    wires_pubs = [_signed_txn(bytes([i + 40]) * 32, 300 + i)
                  for i in range(5)]
    _stamp_rows(rows, [w for w, _ in wires_pubs],
                [p for _, p in wires_pubs])
    rows[1, ML + 5] ^= 1          # tamper row 1's signature
    passed = pipe.submit_packed_rows(rows, n=5)
    assert sorted(p for p, _ in passed) == sorted(
        w for i, (w, _) in enumerate(wires_pubs) if i != 1)
    assert pipe.metrics.verify_pass == 4
    assert pipe.metrics.verify_fail == 1
    # resubmit: tags are in the tcache now -> all pre-dedup'd out
    rows[1, ML + 5] ^= 1          # untamper
    before = pipe.metrics.dedup_drop
    passed2 = pipe.submit_packed_rows(rows, n=5)
    assert [p for p, _ in passed2] == [wires_pubs[1][0]]  # only the fixed row
    assert pipe.metrics.dedup_drop == before + 4


def test_kernel_reads_multisig_row_markers():
    """The device graph masks each row's len word to its length: rows of
    a 3-, a 2- (one signature damaged) and a 1-signature txn, stamped by
    the quic publisher with their markers, verify row for row as the host
    verifier does, and only the damaged txn is refused."""
    import jax

    from benchmark import gen
    from firedancer_tpu.disco.tiles import _jit_blob_fn, _PackedWirePublisher
    from firedancer_tpu.models.verifier import host_verify_blob

    pool = gen.Pool(
        5, np.array([2, 2, 0], np.int8), np.array([3, 2, 1], np.int16),
        np.array([200, 180, 0], np.int32), np.array([0, 1, 2], np.int32),
        np.array([-1, 0, -1], np.int8), np.array([0, 1, 0], np.int16),
        np.array([0, 3, 5], np.int64))
    buf, lens, _, _ = gen.build_slice((pool, gen.key_pubs(5, 4)))
    offs = np.r_[0, np.cumsum(lens)]
    wires = [buf[offs[i]:offs[i + 1]] for i in range(3)]

    class _Ctx:
        trace, tsorig = None, 0

        class metrics:
            add = staticmethod(lambda k, v=1: None)

        def out_reserve(self, nbytes):
            self.blk = np.zeros(nbytes, np.uint8)
            return 0, self.blk

        def out_commit(self, chunk, nbytes, sig=0, sz=None, tsorig=0):
            self.n = sz

    ctx = _Ctx()
    pub = _PackedWirePublisher(ctx, rows=8, ml=ML)
    assert all(pub.add(w) for w in wires)
    pub.flush()
    rows = ctx.blk.reshape(8, STRIDE)
    assert ctx.n == 6
    assert rows[:6, ML + 98:ML + 100].any()      # markers are set
    fn = _jit_blob_fn(jax.jit(ed.verify_batch))
    got = np.asarray(fn.dispatch_blob(rows))
    want = np.array([1, 1, 1, 1, 0, 1, 0, 0], bool)
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(host_verify_blob(rows), want)
    pipe = VerifyPipeline(fn, buckets=[(8, ML)], tcache_depth=64,
                          max_inflight=0)
    passed = pipe.submit_packed_rows(rows, n=6)
    assert [p for p, _ in passed] == [wires[0], wires[2]]


def test_bit_identity_rows_vs_legacy_pack():
    """Satellite 4: zero-repack submit_rows verdicts == legacy _pack_into
    verdicts, mixed valid/tampered batch, fixed seed, CPU."""
    from firedancer_tpu.models.verifier import (
        SigVerifier,
        VerifierConfig,
        make_example_batch,
        use_legacy_pack,
    )

    B, ml = 64, 96
    sv = SigVerifier(VerifierConfig(batch=B, msg_maxlen=ml))
    msgs, lens, sigs, pubs = (np.asarray(a) for a in make_example_batch(
        B, ml, valid=True, sign_pool=8, seed=7))
    sigs = sigs.copy()
    sigs[3, 0] ^= 0xFF            # tampered lanes
    sigs[11, 63] ^= 0x01

    eng = sv.make_ingest(ml=ml, nbuf=2, depth=1)
    eng.submit(msgs, lens, sigs, pubs)
    (ref,) = eng.drain()
    assert ref.any() and not ref.all()

    rows = np.zeros((B, ml + PACKED_ROW_EXTRA), np.uint8)
    rows[:, :ml] = msgs
    rows[:, ml:ml + 64] = sigs
    rows[:, ml + 64:ml + 96] = pubs
    rows[:, ml + 96:ml + 100] = (
        lens.astype(np.int32).view(np.uint8).reshape(B, 4))
    eng2 = sv.make_ingest(ml=ml, nbuf=2, depth=1)
    eng2.submit_rows(rows)
    (got,) = eng2.drain()
    np.testing.assert_array_equal(got, ref)

    # the knob that routes ingest through the legacy path
    import os
    old = os.environ.pop("FDTPU_INGEST_LEGACY_PACK", None)
    try:
        assert not use_legacy_pack()
        os.environ["FDTPU_INGEST_LEGACY_PACK"] = "1"
        assert use_legacy_pack()
    finally:
        if old is None:
            os.environ.pop("FDTPU_INGEST_LEGACY_PACK", None)
        else:
            os.environ["FDTPU_INGEST_LEGACY_PACK"] = old


def test_assign_affinity():
    spec = TopoSpec("afftest", (LinkSpec("l", 4, 64),), (
        TileSpec("a", "source", (), ("l",)),
        TileSpec("b", "sink", (), (), {"cpu_idx": 9}),
        TileSpec("c", "sink", (), ()),
    ))
    # explicit list wraps in topology order; explicit cfg pins win
    out = assign_affinity(spec, "3,5")
    assert [t.cfg.get("cpu_idx") for t in out.tiles] == [3, 9, 3]
    # "" / None = untouched (same spec object)
    assert assign_affinity(spec, "") is spec
    assert assign_affinity(spec, None) is spec
    auto = assign_affinity(spec, "auto")
    assert all(t.cfg.get("cpu_idx") is not None for t in auto.tiles)


@pytest.mark.slow
def test_packed_topology_smoke():
    """2-verify-tile packed-wire topology boots, moves packed frags
    end-to-end with zero torn drops, and both tiles take work (the
    round-robin burst splitter deals across them)."""
    from firedancer_tpu.app import config as app_config
    from firedancer_tpu.disco.run import TopoRun
    from firedancer_tpu.utils import aot

    # AOT-first boot: spawn-context children must never cold-compile
    # (minutes on a contended core vs ~1 s deserialize)
    aot_dir = "/tmp/fdtpu_aot_test"
    if aot.ensure_verify_packed(aot_dir, 64, ML) is None:
        pytest.skip("AOT unusable on this backend")

    cfg = app_config.load()
    cfg["topology"] = "verify-bench"
    cfg["layout"]["verify_tile_count"] = 2
    cfg["development"]["packed_wire"] = 1
    cfg["development"]["source_count"] = 2048
    cfg["tiles"]["verify"]["batch"] = 64
    cfg["tiles"]["verify"]["aot_dir"] = aot_dir
    cfg["tiles"]["verify"]["aot_require"] = 1
    spec = app_config.build_topology(cfg)
    with TopoRun(spec) as run:
        run.wait_ready(timeout=300)
        import time
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            got = sum(run.metrics(f"verify:{v}")["txn_in_cnt"]
                      for v in range(2))
            if got >= 2048:
                break
            time.sleep(0.2)
        m0 = run.metrics("verify:0")
        m1 = run.metrics("verify:1")
        assert m0["txn_in_cnt"] + m1["txn_in_cnt"] >= 2048
        assert m0["txn_in_cnt"] > 0 and m1["txn_in_cnt"] > 0
        assert m0["torn_drop_cnt"] == 0 and m1["torn_drop_cnt"] == 0


def test_torn_rows_excluded_from_txn_accounting(ring):
    """Round-11 satellite: torn rows land in their OWN counter
    (torn_txns), never in txns_in/dedup_drop — pass/fail rates derived
    from txns_in stay honest — and a clean frag afterwards counts
    normally."""
    ws, mc, dc = ring
    fn = _FakeBlobFn()
    pipe = VerifyPipeline(fn, buckets=[(4, ML)], tcache_depth=64,
                          max_inflight=0)
    rows = dc.rows(dc.chunk0, 4, STRIDE)
    wires_pubs = [_signed_txn(bytes([i + 60]) * 32, 400 + i)
                  for i in range(4)]
    _stamp_rows(rows, [w for w, _ in wires_pubs],
                [p for _, p in wires_pubs])
    for s in range(5):                   # depth-4 mcache: seq 0 lapped
        mc.publish(sig=s + 1, chunk=dc.chunk0, sz=4)
    pipe.submit_packed_rows(rows, n=4, guard=(mc, 0))
    assert pipe.metrics.torn_drop == 1
    assert pipe.metrics.torn_txns == 4
    assert pipe.metrics.txns_in == 0, \
        "torn rows must not count as ingested"
    assert pipe.metrics.dedup_drop == 0
    # the snapshot carries the new field for _sync_metrics
    assert dict(pipe.metrics.snapshot())["torn_txns"] == 4
    # clean frag: normal accounting, torn counters untouched
    seq = mc.seq_query()
    mc.publish(sig=9, chunk=dc.chunk0, sz=4)
    passed = pipe.submit_packed_rows(rows, n=4, guard=(mc, seq))
    assert len(passed) == 4
    assert pipe.metrics.txns_in == 4
    assert pipe.metrics.torn_txns == 4


class _DedupCtx:
    """Minimal tile ctx for DedupTile.on_burst_view: metrics counters,
    the in-link mcache, and a publish_burst recorder."""

    def __init__(self, mc, cfg):
        self.cfg = cfg
        self._mc = mc
        self.metrics = self
        self.counts = {}
        self.published = []

    def add(self, name, n=1):
        self.counts[name] = self.counts.get(name, 0) + n

    def in_mcache(self, iidx):
        return self._mc

    def publish_burst(self, buf, starts, lens, sigs, tsorig=0):
        b = np.asarray(buf)
        self.published += [
            (bytes(b[int(s):int(s) + int(ln)]), int(sig))
            for s, ln, sig in zip(starts, lens, sigs)]


def _packed_verdict_frag(dc, chunk, wires):
    """Stamp one round-11 arena frag (u32 offs[k+1] | wires) into the
    dcache the way VerifyTile._publish_packed_verdicts does."""
    k = len(wires)
    offs = np.zeros(k + 1, np.uint32)
    np.cumsum([len(w) for w in wires], out=offs[1:])
    hdr = 4 * (k + 1)
    nb = hdr + int(offs[k])
    blk = dc.write_view(chunk, nb)
    blk[:hdr].view(np.uint32)[:] = offs
    blk[hdr:nb] = np.frombuffer(b"".join(wires), np.uint8)
    return nb


def test_packed_egress_dedup_consumer(ring):
    """Round-11 egress, consumer half: the DedupTile unpacks one arena
    frag into exactly the per-txn wires (ragged lengths), keys dedup on
    wire[1:9], drops a resubmitted frag whole as dups, and drops a torn
    frag before anything derived from it is published."""
    from firedancer_tpu.disco.tiles import DedupTile

    ws, mc, dc = ring
    rng = np.random.default_rng(5)
    wires = [b"\x01" + bytes(rng.integers(0, 256, 64, dtype=np.uint8))
             + bytes(rng.integers(0, 256, int(L), dtype=np.uint8))
             for L in (100, 7, 256, 0, 31)]
    _packed_verdict_frag(dc, dc.chunk0, wires)
    mc.publish(sig=1, chunk=dc.chunk0, sz=len(wires))
    ctx = _DedupCtx(mc, {"packed_egress": 1, "tcache_depth": 4096})
    dt = DedupTile()
    dt.init(ctx)
    assert dt.on_burst is None, \
        "packed egress must hide on_burst (rx-scratch sizing)"
    metas, _ = mc.consume_burst(0, 8)
    dt.on_burst_view(ctx, 0, metas, dc)
    want = [(w, int.from_bytes(w[1:9], "little")) for w in wires]
    assert ctx.published == want
    assert ctx.counts.get("uniq_cnt") == len(wires)
    assert ctx.counts.get("dup_drop_cnt") is None
    # same frag again: every tag already inserted -> all dup, no publish
    seq = mc.seq_query()
    mc.publish(sig=1, chunk=dc.chunk0, sz=len(wires))
    metas, _ = mc.consume_burst(seq, 8)
    dt.on_burst_view(ctx, 0, metas, dc)
    assert ctx.published == want
    assert ctx.counts.get("dup_drop_cnt") == len(wires)
    # torn: consume the meta, then lap the depth-4 mcache before the
    # consumer reads the payload -> dropped whole, nothing published
    seq = mc.seq_query()
    mc.publish(sig=1, chunk=dc.chunk0, sz=len(wires))
    metas, _ = mc.consume_burst(seq, 8)
    for s in range(4):
        mc.publish(sig=2 + s, chunk=dc.chunk0, sz=len(wires))
    before = len(ctx.published)
    dt.on_burst_view(ctx, 0, metas, dc)
    assert len(ctx.published) == before
    assert ctx.counts.get("torn_drop_cnt") == 1
