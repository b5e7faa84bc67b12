"""Run-loop accounting and the packed path's span chain.

  * the mux's five regime counters partition each tile's wall clock, with
    credit stalls out of busy_ns;
  * in_wait_ns/in_wait_cnt sum consume time minus tspub exactly, on the
    scalar, burst and view rx paths;
  * a packed quic -> verify -> dedup chain keeps the frame's oldest row as
    its origin, and the verify spans of one frame share its seq;
  * the verify tile's device-trace capture names host states
    (`fdtpu.*`), and a tile with no capture never imports jax;
  * the benchmark's readers of these counters.

Topologies run their Mux loops as threads over one created workspace
(the test_observability pattern).
"""

import os
import subprocess
import sys
import threading
import time
from types import SimpleNamespace

import numpy as np
import pytest

from firedancer_tpu.disco import mux as mux_mod
from firedancer_tpu.disco import topo as topo_mod
from firedancer_tpu.disco import trace as trace_mod
from firedancer_tpu.disco.mux import Mux
from firedancer_tpu.disco.topo import TopoBuilder
from firedancer_tpu.tango.ring import Cnc

REGIMES = ("busy_ns", "backp_ns", "house_ns", "idle_ns", "loop_ns")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)     # the benchmark package


def _wait(pred, timeout_s, what=""):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if pred():
            return
        time.sleep(0.01)
    raise TimeoutError(f"timed out waiting for {what}")


def _run(muxes):
    threads = [threading.Thread(target=m.run, daemon=True) for m in muxes]
    for t in threads:
        t.start()
    return threads


def _halt(jt, threads):
    for cnc in jt.cnc.values():
        cnc.signal(Cnc.SIGNAL_HALT)
    for t in threads:
        t.join(20)
        assert not t.is_alive()


# -- regime accounting -------------------------------------------------------

class _SrcVt:
    def after_credit(self, ctx):
        for _ in range(8):
            ctx.publish(b"\x01" * 32, sig=1)


class _FwdVt:
    def on_frag(self, ctx, iidx, meta, payload):
        ctx.publish(payload, sig=int(meta["sig"]))


class _SlowSinkVt:
    def on_frag(self, ctx, iidx, meta, payload):
        time.sleep(0.002)


def test_regimes_partition_wall_clock_and_busy_excludes_stalls():
    spec = (
        TopoBuilder(f"regime{os.getpid()}", wksp_mb=8)
        .link("a_b", depth=64, mtu=256)
        .link("b_c", depth=16, mtu=256)
        .tile("src", "sink", outs=["a_b"])
        .tile("mid", "sink", ins=["a_b"], outs=["b_c"])
        .tile("snk", "sink", ins=["b_c"])
        .build()
    )
    jt = topo_mod.create(spec)
    try:
        muxes = [Mux(jt, "src", _SrcVt()), Mux(jt, "mid", _FwdVt()),
                 Mux(jt, "snk", _SlowSinkVt())]
        # a loop's first flush point is its start, its last one its exit:
        # over the whole run the regimes must add up to the run's wall time
        ends = {}

        def timed(name, m):
            m.run()
            ends[name] = time.monotonic_ns()

        t0 = time.monotonic_ns()
        threads = [threading.Thread(target=timed, args=(n, m), daemon=True)
                   for n, m in zip(("src", "mid", "snk"), muxes)]
        for t in threads:
            t.start()
        _wait(lambda: jt.metrics["snk"].get("in_frag_cnt") >= 32, 30,
              "the slow sink to be mid-stream")
        time.sleep(1.0)
        _halt(jt, threads)
        for n in ("src", "mid", "snk"):
            wall = ends[n] - t0
            m = jt.metrics[n].snapshot()
            d = {r: m[r] for r in REGIMES}
            assert 0.98 * wall < sum(d.values()) <= wall, (n, d, wall)
            assert d["loop_ns"] > 0 and d["house_ns"] > 0, (n, d)
        # mid spends its time waiting for the sink's credits; that wait is
        # backp_ns and not also busy_ns
        wall = ends["mid"] - t0
        d = jt.metrics["mid"].snapshot()
        assert d["backp_ns"] > 0.5 * wall, d
        assert d["busy_ns"] < 0.25 * wall, d
    finally:
        jt.close()
        jt.unlink()


# -- queue wait at the inputs ------------------------------------------------

NOW = (5 << 32) + 1_000        # the consumer's clock, frozen


class _FrozenClock:
    """The mux's view of `time`: monotonic_ns frozen at NOW."""

    @staticmethod
    def monotonic_ns():
        return NOW

    sleep = staticmethod(time.sleep)


class _CountVt:
    """Counts frags through whichever rx path `path` names and halts the
    loop once `n` have arrived."""

    def __init__(self, path, n):
        self.n, self.got = n, 0
        if path != "scalar":
            self.on_frag = None
        if path != "burst":
            self.on_burst = None
        if path != "view":
            self.on_burst_view = None

    def _count(self, ctx, k):
        self.got += k
        if self.got >= self.n:
            ctx.halt()

    def on_frag(self, ctx, iidx, meta, payload):
        self._count(ctx, 1)

    def on_burst(self, ctx, iidx, metas, buf, offs, kept):
        self._count(ctx, kept)

    def on_burst_view(self, ctx, iidx, metas, dcache):
        self._count(ctx, len(metas))


@pytest.mark.parametrize("path", ["scalar", "burst", "view"])
def test_in_wait_counters_sum_consume_minus_tspub(monkeypatch, path):
    # waits across the u32 wrap of the stamps, and two stamps from after
    # the consumer's clock read (published later: they waited 0)
    waits = [0, 5, 999, 1_000, 1_001, 123_456, 7_000_000, 2_000_000_000]
    stamps = [(NOW - w) & 0xFFFFFFFF for w in waits]
    stamps += [(NOW + 10) & 0xFFFFFFFF, (NOW + 1_000_000) & 0xFFFFFFFF]
    spec = (
        TopoBuilder(f"wait{path}{os.getpid()}", wksp_mb=8)
        .link("a_b", depth=64, mtu=64)
        .tile("src", "sink", outs=["a_b"])
        .tile("dst", "sink", ins=["a_b"])
        .build()
    )
    jt = topo_mod.create(spec)
    try:
        link = jt.links["a_b"]
        chunk = link.dcache.chunk0
        link.dcache.write(chunk, b"\x07" * 16)
        for i, ts in enumerate(stamps):
            link.mcache.publish(i + 1, chunk, 16, tsorig=ts, tspub=ts)
        monkeypatch.setattr(mux_mod, "time", _FrozenClock)
        vt = _CountVt(path, len(stamps))
        Mux(jt, "dst", vt).run()
        assert vt.got == len(stamps)
        m = jt.metrics["dst"].snapshot()
        assert m["in_wait_cnt"] == len(stamps)
        assert m["in_wait_ns"] == sum(waits)
        assert m["in_frag_cnt"] == len(stamps)
    finally:
        jt.close()
        jt.unlink()


# -- the packed chain: quic -> verify -> dedup -------------------------------

ROWS = 8
HOLD_S = 0.05          # the fake device's verdict latency
MASK32 = 0xFFFFFFFF    # frag stamps are the low 32 bits of monotonic ns


class _SlowVerdict:
    """A dispatched verdict that is ready HOLD_S after dispatch."""

    def __init__(self, n):
        self.n, self.t = n, time.monotonic()

    def is_ready(self):
        return time.monotonic() - self.t >= HOLD_S

    def block_until_ready(self):
        return self

    def __array__(self, dtype=None, copy=None):
        return np.ones(self.n, bool)


class _FakeDevice:
    def __call__(self, msgs, lens, sigs, pubs):
        return np.ones(msgs.shape[0], bool)

    def dispatch_blob(self, blob, maxlen=None):
        return _SlowVerdict(blob.shape[0])


def _verify_vt():
    from firedancer_tpu.disco.tiles import VerifyTile

    class _Verify(VerifyTile):
        """The verify tile on a fake device (no jax graph)."""

        def init(self, ctx):
            self.rr_cnt, self.rr_idx = 1, 0
            self.flush_age_ns = 2_000_000
            self.dp_shards = 1
            self._init_pipeline(ctx, ctx.cfg, _FakeDevice(),
                                ctx.cfg["buckets"])

    return _Verify()


def _wires(n, seed):
    from firedancer_tpu.ballet import txn as txn_lib
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n):
        msg = txn_lib.build_unsigned(
            [rng.bytes(32)], rng.bytes(32),
            [(1, bytes([0]), i.to_bytes(8, "little"))],
            extra_accounts=[rng.bytes(32)])
        out.append(txn_lib.assemble([rng.bytes(64)], msg))
    return out


class _NetVt:
    """Publishes the datagrams of one frame from after_credit (a chain
    origin: tsorig = tspub), then a second batch later."""

    def __init__(self, wires):
        self.wires, self.sent = wires, 0
        self.t_pub = []

    def after_credit(self, ctx):
        if self.sent < len(self.wires):
            # t_pub brackets the publish of row 0
            self.t_pub.append(time.monotonic_ns())
            for w in self.wires:
                ctx.publish(w, sig=0)
                if len(self.t_pub) == 1:
                    self.t_pub.append(time.monotonic_ns())
            self.sent = len(self.wires)


class _NullVt:
    def on_frag(self, ctx, iidx, meta, payload):
        pass


def _packed_chain_spec(app):
    from firedancer_tpu.tango.ring import PACKED_ROW_EXTRA, packed_row_ml
    ml = packed_row_ml(256)
    return (
        TopoBuilder(app, wksp_mb=32)
        .link("net_quic", depth=64, mtu=1280)
        .link("quic_verify", depth=16, mtu=ROWS * (ml + PACKED_ROW_EXTRA))
        .link("verify_dedup", depth=16,
              mtu=ROWS * (65 + ml) + 4 * (ROWS + 1))
        .link("dedup_pack", depth=64, mtu=1280)
        .tile("net", "sink", outs=["net_quic"])
        .tile("quic", "quic", ins=["net_quic"], outs=["quic_verify"],
              packed_publish=1, packed_rows=ROWS, packed_ml=ml,
              packed_flush_age_ns=1_000_000)
        .tile("verify", "verify", ins=["quic_verify"],
              outs=["verify_dedup"], packed_wire=1, egress_packed=1,
              buckets=[[ROWS, ml]], max_inflight=4, native_hostpath=1)
        .tile("dedup", "dedup", ins=["verify_dedup"], outs=["dedup_pack"],
              packed_egress=1, tcache_depth=4096)
        .tile("pack", "sink", ins=["dedup_pack"])
        .build()
    )


def test_packed_chain_origin_is_oldest_row_and_frame_spans_share_seq():
    from firedancer_tpu.disco.tiles import DedupTile, QuicTile

    jt = topo_mod.create(_packed_chain_spec(f"chain{os.getpid()}"))
    try:
        # 3 rows: the frame closes on its 1 ms age, in quic's after_credit.
        # Net publishes all 3 before quic runs, so quic takes them in one
        # burst: a loaded host cannot stall net past that age mid-frame.
        net = _NetVt(_wires(3, seed=3))
        muxes = [Mux(jt, "net", net), Mux(jt, "quic", QuicTile()),
                 Mux(jt, "verify", _verify_vt()),
                 Mux(jt, "dedup", DedupTile()), Mux(jt, "pack", _NullVt())]
        threads = _run(muxes[:1])
        _wait(lambda: jt.metrics["net"].get("out_frag_cnt") >= 3, 60,
              "3 datagrams from net")
        threads += _run(muxes[1:])
        _wait(lambda: jt.metrics["pack"].get("in_frag_cnt") >= 3, 60,
              "3 verdicts at pack")
        _halt(jt, threads)

        def spans(tile, kind):
            _, recs = jt.trace[tile].snapshot()
            return recs[recs["kind"] == kind]

        # row 0's chain origin is its own publish stamp, taken inside
        # net's publish call
        mc = jt.links["net_quic"].mcache
        rc, row0 = mc.query(mc.seq0())
        assert rc == 0
        t_net = int(row0["tsorig"])
        t_lo, t_hi = net.t_pub
        assert (t_net - t_lo) & MASK32 <= (t_hi - t_lo) & MASK32
        # quic stamped one frame of 3 rows, opened on row 0
        co = spans("quic", trace_mod.KIND_COALESCE)
        assert len(co) == 1 and int(co["cnt"][0]) == 3
        frame_seq = int(co["seq"][0])
        # dedup's age counts from the net tile's publish of row 0, so it
        # spans the device's whole verdict latency; from the harvest it
        # would be a few ms
        db = spans("dedup", trace_mod.KIND_BURST)
        assert len(db) == 1
        age = int(db["age_ns"][0])
        assert age >= HOLD_S * 1e9, age
        since_net = (int(db["ts"][0]) - t_net) & MASK32
        assert since_net == age, (since_net, age)
        # and pack sees the same origin through dedup's burst publish
        pk = spans("pack", trace_mod.KIND_FRAG)
        assert len(pk) == 3
        assert np.all(pk["age_ns"].astype(np.int64) >= age)
        np.testing.assert_array_equal(
            (pk["ts"].astype(np.int64) - t_net) & MASK32, pk["age_ns"])
        # the frame is one device call of its 3 rows: the call's verify
        # spans carry the frame's quic_verify seq and count its rows, and
        # its coalesce span counts one frame
        for kind, cnt in ((trace_mod.KIND_COALESCE, 1),
                          (trace_mod.KIND_DISPATCH, 3),
                          (trace_mod.KIND_DEVICE, 3),
                          (trace_mod.KIND_HARVEST, 3),
                          (trace_mod.KIND_PUBLISH, 3)):
            got = spans("verify", kind)
            assert len(got) == 1, trace_mod.KIND_NAMES[kind]
            assert int(got["seq"][0]) == frame_seq, \
                trace_mod.KIND_NAMES[kind]
            assert int(got["cnt"][0]) == cnt, trace_mod.KIND_NAMES[kind]
        assert jt.metrics["verify"].get("batch_cnt") == 1
        assert jt.metrics["verify"].get("verdict_wait_ns") > 0
    finally:
        jt.close()
        jt.unlink()


# -- device-trace annotations ------------------------------------------------

def test_capture_names_host_states_and_anchors_the_clock(tmp_path):
    from jax.profiler import ProfileData

    from benchmark import reduce

    trace_dir = str(tmp_path / "trace")
    spec = (
        TopoBuilder(f"annot{os.getpid()}", wksp_mb=32)
        .link("quic_verify", depth=16, mtu=ROWS * 384)
        .link("verify_dedup", depth=16, mtu=ROWS * 349 + 4 * (ROWS + 1))
        .tile("quic", "sink", outs=["quic_verify"])
        .tile("verify", "verify", ins=["quic_verify"],
              outs=["verify_dedup"], packed_wire=1, egress_packed=1,
              buckets=[[ROWS, 284]], max_inflight=4,
              jax_trace_dir=trace_dir)
        .tile("dedup", "sink", ins=["verify_dedup"])
        .build()
    )
    jt = topo_mod.create(spec)
    try:
        m = Mux(jt, "verify", _verify_vt())
        # housekeeping at loop start, then not before the window is over
        m.HOUSE_NS = 1_000_000_000
        anchor_lo = time.monotonic_ns()
        th = threading.Thread(target=m.run, daemon=True)
        th.start()
        # the loop is up once the capture is on; then a planted idle
        # stretch: nothing inbound for 0.3 s
        _wait(lambda: jt.cnc["verify"].signal_query() == Cnc.SIGNAL_RUN,
              60, "the verify loop")
        time.sleep(0.05)
        w0 = time.time_ns()
        time.sleep(0.3)
        w1 = time.time_ns()
        _halt(jt, [th])      # fini stops the capture
        anchor_hi = time.monotonic_ns()
    finally:
        if trace_mod.annot is not None:
            trace_mod.stop_capture()
        jt.close()
        jt.unlink()

    tr = reduce.load(trace_dir)
    assert tr is not None
    names = set(tr.host.name)
    assert {"fdtpu.clock_anchor", "fdtpu.mux.idle",
            "fdtpu.mux.house"} <= names, sorted(
                n for n in names if n.startswith("fdtpu"))
    # the anchor's argument is the capturing process's CLOCK_MONOTONIC
    import glob
    path = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                  "*.xplane.pb"))[-1]
    args = [dict(e.stats) for p in ProfileData.from_file(path).planes
            for ln in p.lines for e in ln.events
            if e.name == "fdtpu.clock_anchor"]
    assert len(args) == 1
    assert anchor_lo <= int(args[0]["monotonic_ns"]) <= anchor_hi
    # the benchmark's reducer names the planted stretch
    gaps = reduce.idle_gaps(tr, w0, w1)
    assert gaps[0][0] == "fdtpu.mux.idle", gaps


def test_no_capture_no_jax_in_a_tile():
    """A tile loop with no capture, credit stalls and idle runs included,
    imports no jax."""
    code = f"""
import os, sys, threading, time
sys.path.insert(0, {ROOT!r})
from firedancer_tpu.disco import topo as topo_mod
from firedancer_tpu.disco.mux import Mux
from firedancer_tpu.disco.topo import TopoBuilder
from firedancer_tpu.tango.ring import Cnc

class Src:
    def __init__(self):
        self.n = 0
    def after_credit(self, ctx):
        if self.n < 64:
            ctx.publish(b"x" * 16, sig=1)
            self.n += 1

class Slow:
    def on_frag(self, ctx, iidx, meta, payload):
        time.sleep(0.001)

spec = (TopoBuilder("nojax%d" % os.getpid(), wksp_mb=8)
        .link("a_b", depth=4, mtu=64)
        .tile("src", "sink", outs=["a_b"])
        .tile("dst", "sink", ins=["a_b"]).build())
jt = topo_mod.create(spec)
ms = [Mux(jt, "src", Src()), Mux(jt, "dst", Slow())]
ts = [threading.Thread(target=m.run, daemon=True) for m in ms]
[t.start() for t in ts]
deadline = time.monotonic() + 30
while jt.metrics["dst"].get("in_frag_cnt") < 64 and time.monotonic() < deadline:
    time.sleep(0.01)
time.sleep(0.05)
for c in jt.cnc.values():
    c.signal(Cnc.SIGNAL_HALT)
[t.join(10) for t in ts]
got = jt.metrics["dst"].get("in_frag_cnt")
stalls = jt.metrics["src"].get("backp_ns")
jt.close(); jt.unlink()
print(got, stalls > 0, any(m == "jax" or m.startswith("jax.")
                           for m in sys.modules))
"""
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=120, cwd=ROOT)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.split() == ["64", "True", "False"], out.stdout


# -- the benchmark's readers -------------------------------------------------

def _run_view(w0, w1, window_ns=20_000_000_000):
    rec = SimpleNamespace(counters={"w0": w0, "w1": w1}, w0=0,
                          w1=window_ns)
    return SimpleNamespace(rec=rec)


FIXTURE = (
    {"pack": {"busy_ns": 1_000, "loop_ns": 2_000, "house_ns": 30,
              "idle_ns": 5, "in_frag_cnt": 10, "in_wait_ns": 4_000_000,
              "in_wait_cnt": 8},
     "verify:0": {"verdict_wait_ns": 1_000_000_000, "in_frag_cnt": 40,
                  "coalesced_frame_cnt": 4}},
    {"pack": {"busy_ns": 501_000, "loop_ns": 402_000, "house_ns": 1_030,
              "idle_ns": 5, "in_frag_cnt": 30, "in_wait_ns": 124_000_000,
              "in_wait_cnt": 18},
     "verify:0": {"verdict_wait_ns": 1_500_000_000, "in_frag_cnt": 60,
                  "coalesced_frame_cnt": 13}},
)


@pytest.mark.parametrize("name,want", [
    ("pack_us_per_txn", (500_000 + 400_000 + 1_000) / 20 / 1e3),
    ("pack_in_wait_ms", 120_000_000 / 10 / 1e6),
    ("device_wait_pct", 100 * 500_000_000 / 20_000_000_000),
    ("coalesced_frame_pct", 100 * 9 / 20),
])
def test_reader_on_fixture_counters(name, want):
    from benchmark.cells import reader
    got = reader(name)(_run_view(*FIXTURE))
    assert got == pytest.approx(want)


@pytest.mark.parametrize("name", ["pack_us_per_txn", "pack_in_wait_ms",
                                  "device_wait_pct", "coalesced_frame_pct"])
def test_reader_is_silent_on_a_parent_snapshot(name):
    """A program without the counters (the parent commit) reads None."""
    from benchmark.cells import reader
    old = {"pack": {"busy_ns": 9, "backp_ns": 1, "house_ns": 2,
                    "idle_ns": 3, "in_frag_cnt": 4},
           "verify:0": {"lanes_filled_cnt": 5}}
    assert reader(name)(_run_view(old, old)) is None
