"""fdtrace observability tests: span chains through a live 3-tile
pipeline, the /metrics + /healthz scrape round trip, Histf -> Prometheus
le-bucket invariants, and compile-event accounting on forced bucket
recompiles.

The pipeline test runs three Mux loops as THREADS over one created
topology (not spawned processes): the span/metrics machinery under test
is identical, and staying in-process keeps this module in the fast tier.
"""

import json
import os
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from firedancer_tpu.disco import metrics as metrics_mod
from firedancer_tpu.disco import topo as topo_mod
from firedancer_tpu.disco import trace as trace_mod
from firedancer_tpu.disco.mux import Mux
from firedancer_tpu.disco.topo import TopoBuilder
from firedancer_tpu.tango.ring import Cnc
from firedancer_tpu.utils.hist import Histf


def _wait(pred, timeout_s, what=""):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if pred():
            return
        time.sleep(0.01)
    raise TimeoutError(f"timed out waiting for {what}")


# -- span chain through a live pipeline -------------------------------------

class _SrcVt:
    """Publishes n frags from after_credit (outside frag context, so each
    frag STARTS a span chain: tsorig = its own tspub)."""

    def __init__(self, n):
        self.n = n
        self.sent = 0

    def after_credit(self, ctx):
        while self.sent < self.n:
            ctx.publish(bytes([self.sent]) * 32, sig=self.sent)
            self.sent += 1


class _FwdVt:
    def on_frag(self, ctx, iidx, meta, payload):
        ctx.publish(payload, sig=int(meta["sig"]))


class _SinkVt:
    def on_frag(self, ctx, iidx, meta, payload):
        pass


def test_span_chain_three_tiles():
    n = 8
    spec = (
        TopoBuilder(f"obs{os.getpid()}", wksp_mb=8)
        .link("a_b", depth=64, mtu=256)
        .link("b_c", depth=64, mtu=256)
        .tile("src", "sink", outs=["a_b"])
        .tile("mid", "sink", ins=["a_b"], outs=["b_c"])
        .tile("snk", "sink", ins=["b_c"])
        .build()
    )
    jt = topo_mod.create(spec)
    try:
        muxes = {"src": Mux(jt, "src", _SrcVt(n)),
                 "mid": Mux(jt, "mid", _FwdVt()),
                 "snk": Mux(jt, "snk", _SinkVt())}
        threads = [threading.Thread(target=m.run, daemon=True)
                   for m in muxes.values()]
        for t in threads:
            t.start()
        _wait(lambda: jt.metrics["snk"].get("in_frag_cnt") == n,
              30, f"{n} frags at the sink")
        for cnc in jt.cnc.values():
            cnc.signal(Cnc.SIGNAL_HALT)
        for t in threads:
            t.join(10)
            assert not t.is_alive()

        spans = {}
        for name in ("mid", "snk"):
            cur, recs = jt.trace[name].snapshot()
            frag = recs[recs["kind"] == trace_mod.KIND_FRAG]
            assert len(frag) == n, f"{name}: {len(frag)} frag spans"
            # single-writer monotonic clock: span starts never go backward
            assert np.all(np.diff(frag["ts"].astype(np.int64)) >= 0)
            spans[name] = recs

        # chain age: at the sink the frag is two hops old, so the
        # origin-relative age must be >= the last hop's latency
        snk = spans["snk"]
        assert np.all(snk["age_ns"].astype(np.int64)
                      >= snk["hop_ns"].astype(np.int64))
        # src -> mid is one hop: the chain originated at src's publish
        mid = spans["mid"]
        assert np.all(mid["age_ns"].astype(np.int64)
                      >= mid["hop_ns"].astype(np.int64))

        # the sink's shm in_hop_ns histogram is fed from the SAME hop
        # measurements the spans carry: rebuilding it from span hop_ns
        # must agree bucket-for-bucket (spans whose stamp raced the
        # consumer's clock capture record hop 0 and may be unsampled)
        edges, counts, hsum = jt.metrics["snk"].hist_snapshot("in_hop_ns")
        h = Histf(100, 10e9)
        for v in snk["hop_ns"]:
            if int(v):
                h.sample(int(v))
        zeros = int(np.sum(snk["hop_ns"] == 0))
        diff = counts.astype(np.int64) - h.counts.astype(np.int64)
        assert np.all(diff >= 0)
        assert int(diff.sum()) <= zeros

        # Chrome trace export is valid and loadable
        doc = trace_mod.chrome_trace(spans)
        blob = json.dumps(doc)
        back = json.loads(blob)
        xs = [e for e in back["traceEvents"] if e["ph"] == "X"]
        assert len(xs) == 2 * n
        assert all(e["dur"] > 0 and "frag" in e["name"] for e in xs)
        names = {e["args"]["name"] for e in back["traceEvents"]
                 if e["ph"] == "M"}
        assert {"mid", "snk"} <= names
        # and the terminal table renders
        table = trace_mod.hop_table(spans)
        assert "frag" in table and "mid" in table
    finally:
        jt.close()
        jt.unlink()


# -- /metrics + /healthz scrape round trip ----------------------------------

def _check_exposition(body: str):
    """Minimal Prometheus text-format checker: every sample line parses,
    every metric family was HELP+TYPE-declared exactly once with a valid
    kind (text-format conformance: one declaration per family even when
    the family spans many tiles/links)."""
    declared, helped = {}, set()
    for line in body.strip().splitlines():
        if line.startswith("# HELP "):
            name = line.split()[2]
            assert name not in helped, f"duplicate HELP for {name}"
            helped.add(name)
            continue
        if line.startswith("# TYPE "):
            _, _, name, kind = line.split()
            assert kind in ("counter", "gauge", "histogram"), line
            assert name not in declared, f"duplicate TYPE for {name}"
            declared[name] = kind
            continue
        assert not line.startswith("#"), line
        name = line.split("{", 1)[0].split(" ", 1)[0]
        float(line.rsplit(" ", 1)[1])  # value parses
        base = name
        for suf in ("_bucket", "_sum", "_count"):
            if name.endswith(suf) and name.removesuffix(suf) in declared:
                base = name.removesuffix(suf)
        assert base in declared, f"undeclared metric {name}"
        if base != name:
            assert declared[base] == "histogram", line
    return declared


def test_metrics_http_roundtrip():
    from firedancer_tpu.disco.run import MetricsHttpServer

    spec = (
        TopoBuilder(f"obsh{os.getpid()}", wksp_mb=8)
        .link("a_b", depth=64, mtu=256)
        .tile("src", "sink", outs=["a_b"])
        .tile("snk", "sink", ins=["a_b"])
        .build()
    )
    jt = topo_mod.create(spec)
    srv = MetricsHttpServer(jt, port=0)
    try:
        m = jt.metrics["snk"]
        m.add("in_frag_cnt", 7)
        m.set("out0_lag", 1234)
        m.add("in_wait_ns", 5_000)
        m.add("in_wait_cnt", 2)
        samples = [150, 1_000, 50_000, 2_000_000, 20e9]  # last overflows
        for v in samples:
            m.hist_sample("in_hop_ns", v)

        base = f"http://127.0.0.1:{srv.port}"
        r = urllib.request.urlopen(f"{base}/metrics", timeout=10)
        assert r.status == 200
        assert r.headers["Content-Type"].startswith("text/plain")
        body = r.read().decode()
        declared = _check_exposition(body)
        assert declared["fdtpu_in_frag_cnt"] == "counter"
        assert declared["fdtpu_out0_lag"] == "gauge"
        # the regime and queue-wait counters render beside the histogram
        for fam in ("fdtpu_loop_ns", "fdtpu_in_wait_ns",
                    "fdtpu_in_wait_cnt"):
            assert declared[fam] == "counter", fam
        assert 'fdtpu_in_wait_ns{tile="snk",kind="sink"} 5000' in body
        assert declared["fdtpu_in_hop_ns"] == "histogram"

        # le-bucket invariants for the snk tile's hop histogram
        buckets, total, hsum = [], None, None
        for line in body.splitlines():
            if line.startswith("fdtpu_in_hop_ns") and 'tile="snk"' in line:
                val = float(line.rsplit(" ", 1)[1])
                if "_bucket{" in line:
                    le = line.split(',le="', 1)[1].split('"', 1)[0]
                    buckets.append((le, val))
                elif line.startswith("fdtpu_in_hop_ns_count"):
                    total = val
                elif line.startswith("fdtpu_in_hop_ns_sum"):
                    hsum = val
        assert buckets and buckets[-1][0] == "+Inf"
        cum = [v for _, v in buckets]
        assert cum == sorted(cum), "cumulative buckets must be monotonic"
        assert cum[-1] == total == len(samples)
        # the overflow sample sits only in +Inf
        assert cum[-2] == len(samples) - 1
        assert hsum == sum(int(v) for v in samples)

        # healthz: BOOT tiles -> 503 with the offenders listed
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(f"{base}/healthz", timeout=10)
        assert ei.value.code == 503
        assert "src" in ei.value.read().decode()
        # all RUN with fresh heartbeats -> 200
        for cnc in jt.cnc.values():
            cnc.signal(Cnc.SIGNAL_RUN)
            cnc.heartbeat(time.monotonic_ns())
        r = urllib.request.urlopen(f"{base}/healthz", timeout=10)
        assert r.status == 200
        # unknown path -> 404
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(f"{base}/nope", timeout=10)
        assert ei.value.code == 404
    finally:
        srv.close()
        jt.close()
        jt.unlink()


def test_metrics_schema_lints():
    metrics_mod.lint_schema()


# -- compile events + occupancy on forced bucket recompile ------------------

def _make_payloads(n, extra_accounts, seed):
    from firedancer_tpu.ballet import txn as txn_lib
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n):
        msg = txn_lib.build_unsigned(
            [rng.bytes(32)], rng.bytes(32), [(1, bytes([0]), bytes(8))],
            extra_accounts=[rng.bytes(32) for _ in range(extra_accounts)])
        out.append(txn_lib.assemble([rng.bytes(64)], msg))
    return out


def test_compile_events_and_occupancy():
    from firedancer_tpu.ballet import txn as txn_lib
    from firedancer_tpu.disco.pipeline import VerifyPipeline

    small = _make_payloads(4, 1, seed=7)
    big = _make_payloads(4, 12, seed=8)
    len_s = len(txn_lib.parse(small[0]).message(small[0]))
    len_b = len(txn_lib.parse(big[0]).message(big[0]))
    assert len_s < len_b

    ring_buf = bytearray(trace_mod.footprint(depth=256))
    ring = trace_mod.TraceRing(memoryview(ring_buf), 0, create=True,
                               depth=256)

    def fake_verify(msgs, lens, sigs, pubs):
        return np.ones(msgs.shape[0], dtype=bool)

    pipe = VerifyPipeline(fake_verify,
                          buckets=[(4, len_s), (4, len_b)],
                          tracer=ring)
    for p in small + big:
        pipe.submit(p)
    pipe.flush()

    s = pipe.metrics
    # one compile event per (batch, maxlen) shape's first dispatch
    assert s.compile_cnt == 2
    assert s.compile_ns > 0
    # both buckets filled completely: no padding lanes
    assert s.lanes_filled == 8
    assert s.lanes_dispatched == 8
    assert s.last_fill_pct == 100
    # the process-wide registry saw the same two shapes
    evs = trace_mod.compile_events()
    assert evs[("verify", 4, len_s)]["cnt"] >= 1
    assert evs[("verify", 4, len_b)]["cnt"] >= 1

    _, recs = ring.snapshot()
    kinds = recs["kind"]
    assert int(np.sum(kinds == trace_mod.KIND_COMPILE)) == 2
    assert int(np.sum(kinds == trace_mod.KIND_COALESCE)) == 2
    assert int(np.sum(kinds == trace_mod.KIND_DEVICE)) == 2
    dev = recs[kinds == trace_mod.KIND_DEVICE]
    assert np.all(dev["cnt"] == 4)

    # a re-dispatch of an already-seen shape is NOT a compile event
    more = _make_payloads(4, 1, seed=9)
    for p in more:
        pipe.submit(p)
    pipe.flush()
    assert pipe.metrics.compile_cnt == 2


# -- trace ring + Histf unit invariants -------------------------------------

def test_trace_ring_lap_and_order():
    depth = 64
    buf = bytearray(trace_mod.footprint(depth=depth))
    ring = trace_mod.TraceRing(memoryview(buf), 0, create=True, depth=depth)
    for i in range(200):
        ring.record(trace_mod.KIND_FRAG, ts=1000 + i, dur=5, seq=i)
    cur, recs = ring.snapshot()
    assert cur == 200
    assert len(recs) == depth  # lapped: only the newest depth survive
    assert recs[0]["seq"] == 200 - depth and recs[-1]["seq"] == 199
    assert np.all(np.diff(recs["ts"].astype(np.int64)) > 0)
    # incremental drain: nothing new -> empty
    cur2, recs2 = ring.snapshot(since=cur)
    assert cur2 == cur and len(recs2) == 0
    # a joiner over the same memory sees the same records
    ring2 = trace_mod.TraceRing(memoryview(buf), 0)
    _, recs3 = ring2.snapshot()
    assert np.array_equal(recs3, recs)


def test_histf_percentile_and_overflow():
    h = Histf(100, 1e9)
    rng = np.random.default_rng(3)
    vals = rng.integers(100, 1_000_000, size=500)
    for v in vals:
        h.sample(int(v))
    for q in (0.25, 0.5, 0.9, 0.99, 1.0):
        # reference semantics: first edge whose cumulative count reaches
        # ceil(q * total)
        target = int(np.ceil(q * h.count()))
        acc = 0
        want = float(h.edges[-1])
        for i, c in enumerate(h.counts):
            acc += int(c)
            if acc >= target:
                want = float(h.edges[min(i, len(h.edges) - 1)])
                break
        assert h.percentile(q) == want
    assert h.overflow_cnt() == 0
    h.sample(5e9)  # beyond max -> clamped into the overflow bucket
    assert h.overflow_cnt() == 1
    assert h.percentile(1.0) == float(h.edges[-1])
    assert Histf(100, 1e9).percentile(0.99) == 0.0
