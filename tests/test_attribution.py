"""Bottleneck attribution + SLO engine + flight recorder tests.

The live test runs three Mux loops as THREADS over one created topology
(the test_observability pattern): an artificially slow sink consumer
must backpressure the middle tile, charge the sink's fseq slow diag,
and come out of `attrib.bottleneck` as THE named bottleneck link — the
tentpole's acceptance scenario, in the fast tier.
"""

import os
import threading
import time

import numpy as np

from firedancer_tpu.disco import attrib
from firedancer_tpu.disco import flightrec
from firedancer_tpu.disco import metrics as metrics_mod
from firedancer_tpu.disco import slo
from firedancer_tpu.disco import topo as topo_mod
from firedancer_tpu.disco import trace as trace_mod
from firedancer_tpu.disco.mux import Mux
from firedancer_tpu.disco.topo import TopoBuilder
from firedancer_tpu.tango.fctl import Fctl
from firedancer_tpu.tango.ring import Cnc, FSeq
from firedancer_tpu.utils.hist import Histf


def _wait(pred, timeout_s, what=""):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if pred():
            return
        time.sleep(0.01)
    raise TimeoutError(f"timed out waiting for {what}")


# -- Histf edge cases --------------------------------------------------------

def test_histf_empty_percentile_is_zero():
    h = Histf(100, 10e9)
    assert h.percentile(0.50) == 0.0
    assert h.percentile(0.99) == 0.0
    assert h.count() == 0 and h.overflow_cnt() == 0


def test_histf_overflow_only():
    h = Histf(100, 10e9)
    h.sample(1e12)          # way past max_val: lands in the overflow slot
    h.sample(2e12)
    assert h.count() == 2 and h.overflow_cnt() == 2
    # percentile clamps to the top finite edge: the histogram can only
    # say "at least max_val", never invent a value past its range
    assert h.percentile(0.50) == float(h.edges[-1])
    assert h.percentile(0.99) == float(h.edges[-1])


def test_histf_single_sample():
    h = Histf(100, 10e9)
    h.sample(5_000)
    # every quantile of a one-sample distribution is that sample's bucket
    edge = float(h.edges[np.searchsorted(h.edges, 5_000)])
    for q in (0.01, 0.50, 0.99, 1.0):
        assert h.percentile(q) == edge
    assert h.overflow_cnt() == 0


# -- Fctl stall accounting ---------------------------------------------------

def test_fctl_stall_attribution_counters():
    app = f"fctlat{os.getpid()}"
    spec = (
        TopoBuilder(app, wksp_mb=8)
        .link("a_b", depth=4, mtu=64)
        .tile("src", "sink", outs=["a_b"])
        .tile("dst", "sink", ins=["a_b"])
        .build()
    )
    jt = topo_mod.create(spec)
    try:
        mc = jt.links["a_b"].mcache
        fseq = jt.fseq[("dst", "a_b")]
        fctl = Fctl(cr_max=4).rx_add(fseq)
        seq = mc.seq0()
        fseq.update(seq)
        while fctl.consume(1):          # drain every credit
            mc.publish(0)
            seq += 1
            fctl.tx_cr_update(seq)
        assert fctl.backp_cnt == 1      # entered backpressure once
        assert fctl.backp_exit_cnt == 0
        time.sleep(0.002)               # measurable stall
        fseq.update(seq)                # consumer catches up
        assert fctl.tx_cr_update(seq) > 0
        assert fctl.backp_exit_cnt == 1
        assert fctl.stall_ns >= 2_000_000, \
            f"stall_ns lost the wait: {fctl.stall_ns}"
    finally:
        jt.close()
        jt.unlink()


# -- exposition conformance --------------------------------------------------

def test_prometheus_render_extra_families_and_escaping():
    app = f"expo{os.getpid()}"
    spec = (
        TopoBuilder(app, wksp_mb=8)
        .link("a_b", depth=64, mtu=256)
        .tile("src", "sink", outs=["a_b"])
        .tile("dst", "sink", ins=["a_b"])
        .build()
    )
    jt = topo_mod.create(spec)
    try:
        jt.metrics["src"].add("out_frag_cnt", 3)
        jt.metrics["dst"].add("in_frag_cnt", 3)
        extra = [
            ("fdtpu_link_lag", "gauge", "consumer seq lag",
             {"link": "a_b", "producer": "src", "consumer": "dst"}, 7),
            ("fdtpu_link_lag", "gauge", "consumer seq lag",
             {"link": "a_b", "producer": "src", "consumer": "dst2"}, 9),
            ("fdtpu_link_note", "counter", "label escaping probe",
             {"who": 'we"ird\\name\nnewline'}, 1),
        ]
        body = metrics_mod.prometheus_render(jt.metrics, extra=extra)
        # one HELP + one TYPE per family, even across tiles/links
        for fam in ("fdtpu_out_frag_cnt", "fdtpu_in_frag_cnt",
                    "fdtpu_link_lag"):
            assert body.count(f"# TYPE {fam} ") == 1, fam
            assert body.count(f"# HELP {fam} ") == 1, fam
        assert 'consumer="dst"} 7' in body
        assert 'consumer="dst2"} 9' in body
        # escaped per the text exposition format: \\ then \" then \n
        assert 'who="we\\"ird\\\\name\\nnewline"' in body
        assert "\nnewline" not in body.split('who="')[1].split("}")[0]
        # declarations precede their samples
        assert body.index("# TYPE fdtpu_link_lag ") \
            < body.index('fdtpu_link_lag{')
    finally:
        jt.close()
        jt.unlink()


# -- SLO engine over synthetic spans ----------------------------------------

def _spans(rows):
    recs = np.zeros(len(rows), dtype=trace_mod.TRACE_REC_DTYPE)
    for i, r in enumerate(rows):
        for k, v in r.items():
            recs[i][k] = v
    return recs


def test_slo_stage_stats_budgets_and_burn_trend():
    us = 1_000
    spans = {
        "q": _spans([{"kind": trace_mod.KIND_STAGE, "ts": t * us,
                      "dur": 20 * us} for t in range(10)]),
        "v": _spans(
            [{"kind": trace_mod.KIND_FRAG, "ts": t * us, "dur": 5 * us,
              "hop_ns": 30 * us} for t in range(10)]
            + [{"kind": trace_mod.KIND_DEVICE, "ts": t * us,
                "dur": 5_000 * us} for t in range(10)]),
        # sink ages: first half under the 2ms target, second half over
        "s": _spans(
            [{"kind": trace_mod.KIND_FRAG, "ts": t * us, "dur": us,
              "age_ns": 500 * us} for t in range(10)]
            + [{"kind": trace_mod.KIND_FRAG, "ts": (100 + t) * us,
                "dur": us, "age_ns": 9_000 * us} for t in range(10)]),
    }
    kind_of = {"q": "quic_server", "v": "verify", "s": "sink"}
    stats = {r["stage"]: r for r in slo.stage_stats(spans, kind_of, 2.0)}
    assert stats["wire"]["n"] == 10 and stats["wire"]["ok"], \
        "20us wire p99 fits the 100us wire budget"
    assert stats["ring-wait"]["n"] == 10 and stats["ring-wait"]["ok"]
    assert stats["device"]["n"] == 10 and not stats["device"]["ok"], \
        "5ms device p99 must bust the 0.7ms device budget"
    assert stats["publish"]["n"] == 0 and stats["publish"]["ok"], \
        "a stage with no samples cannot fail"

    b = slo.burn(spans, kind_of, 2.0)
    assert b["n"] == 20
    assert abs(b["rate"] - 0.5) < 1e-9
    assert b["trend"] == "up" and b["rate_second"] > b["rate_first"]

    table = slo.render_table(slo.stage_stats(spans, kind_of, 2.0), b, 2.0)
    assert "device" in table and "OVER" in table
    assert "burn rate: 50.0%" in table and "trend up" in table


def test_slo_burn_falls_back_to_verify_ages():
    # no terminal tile in the topology: the verify tile's own age stamps
    # still grade the chain up to dispatch admission
    us = 1_000
    spans = {"v": _spans([{"kind": trace_mod.KIND_BURST, "ts": t * us,
                           "dur": us, "age_ns": 9_000 * us}
                          for t in range(4)])}
    b = slo.burn(spans, {"v": "verify"}, 2.0)
    assert b["n"] == 4 and b["rate"] == 1.0


# -- the acceptance scenario: slow consumer -> named bottleneck --------------

class _SrcVt:
    """Publishes n frags from after_credit, a few per loop pass."""

    def __init__(self, n):
        self.n = n
        self.sent = 0

    def after_credit(self, ctx):
        for _ in range(min(8, self.n - self.sent)):
            ctx.publish(bytes([self.sent & 0xFF]) * 32, sig=self.sent)
            self.sent += 1


class _FwdVt:
    def on_frag(self, ctx, iidx, meta, payload):
        ctx.publish(payload, sig=int(meta["sig"]))


class _SlowSinkVt:
    """The artificially slow consumer: 2ms per frag."""

    def on_frag(self, ctx, iidx, meta, payload):
        time.sleep(0.002)


def test_bottleneck_names_slow_consumer_link():
    n = 400
    spec = (
        TopoBuilder(f"attr{os.getpid()}", wksp_mb=8)
        # wide first hop so src never stalls; narrow second hop so the
        # slow sink pins mid in _wait_credit
        .link("a_b", depth=1024, mtu=256)
        .link("b_c", depth=16, mtu=256)
        .tile("src", "sink", outs=["a_b"])
        .tile("mid", "sink", ins=["a_b"], outs=["b_c"])
        .tile("snk", "sink", ins=["b_c"])
        .build()
    )
    jt = topo_mod.create(spec)
    try:
        muxes = {"src": Mux(jt, "src", _SrcVt(n)),
                 "mid": Mux(jt, "mid", _FwdVt()),
                 "snk": Mux(jt, "snk", _SlowSinkVt())}
        threads = [threading.Thread(target=m.run, daemon=True)
                   for m in muxes.values()]
        for t in threads:
            t.start()
        _wait(lambda: jt.metrics["snk"].get("in_frag_cnt") >= 32,
              30, "the slow sink to be mid-stream")

        prev = attrib.link_sample(jt)
        time.sleep(0.6)
        cur = attrib.link_sample(jt)

        link, reason = attrib.bottleneck(prev, cur)
        assert link == "mid->snk (b_c)", f"verdict blamed {link}: {reason}"
        assert "slow consumer snk" in reason, reason

        # the producer charged the sink's fseq slow diag (the fd_fctl
        # receiver-diag contract)
        assert jt.fseq[("snk", "b_c")].diag(FSeq.DIAG_SLOW_CNT) > 0
        # mid spent real wall time backpressured; gauges flowed at
        # housekeeping
        assert cur["tiles"]["mid"]["backp_ns"] > 0
        assert cur["tiles"]["mid"]["out"]["b_c"]["occ_hwm"] > 0

        # the terminal frame renders, verdict line included
        frame = attrib.render_top(spec, prev, cur)
        assert any(ln.startswith("bottleneck: mid->snk (b_c)")
                   for ln in frame), frame[-1]
        assert any(ln.startswith("TILE") for ln in frame)

        # /metrics extra families carry the producer->consumer labels
        fams = attrib.link_families(jt)
        names = {f[0] for f in fams}
        assert {"fdtpu_link_lag", "fdtpu_link_slow_cnt",
                "fdtpu_link_occ_hwm", "fdtpu_link_frag_rate"} <= names
        slow = [f for f in fams if f[0] == "fdtpu_link_slow_cnt"
                and f[3]["consumer"] == "snk"]
        assert slow and slow[0][3]["producer"] == "mid"
        assert slow[0][4] > 0
        body = metrics_mod.prometheus_render(jt.metrics, extra=fams)
        assert body.count("# TYPE fdtpu_link_slow_cnt ") == 1
        assert 'fdtpu_link_slow_cnt{link="b_c",producer="mid",' \
               'consumer="snk"}' in body

        for cnc in jt.cnc.values():
            cnc.signal(Cnc.SIGNAL_HALT)
        for t in threads:
            t.join(10)
            assert not t.is_alive()
        # regime accounting closes the books: all four regimes flushed
        msnap = jt.metrics["mid"].snapshot()
        assert msnap["busy_ns"] > 0 and msnap["backp_ns"] > 0
        assert msnap["house_ns"] > 0
    finally:
        jt.close()
        jt.unlink()


# -- flight recorder ---------------------------------------------------------

def test_flight_bundle_roundtrip_and_render(tmp_path):
    spec = (
        TopoBuilder(f"fltr{os.getpid()}", wksp_mb=8)
        .link("a_b", depth=64, mtu=256)
        .tile("src", "sink", outs=["a_b"])
        .tile("mid", "verify", ins=["a_b"])
        .build()
    )
    jt = topo_mod.create(spec)
    try:
        t0 = time.monotonic_ns()
        for i in range(5):
            jt.trace["mid"].record(trace_mod.KIND_FRAG, t0 + i, 1_000,
                                   hop_ns=2_000, age_ns=3_000, seq=i)
        jt.trace["mid"].record(trace_mod.KIND_DEVICE, t0 + 9, 400_000)
        jt.metrics["mid"].add("in_frag_cnt", 5)
        jt.metrics["mid"].hist_sample("in_hop_ns", 2_000)
        jt.cnc["mid"].signal(Cnc.SIGNAL_FAIL)
        jt.fseq[("mid", "a_b")].diag_add(FSeq.DIAG_SLOW_CNT, 3)

        cfg = {"observability": {"slo_target_ms": 2.0},
               "secret": object()}   # default=str must absorb this
        path = flightrec.write_bundle(
            str(tmp_path), jt, reason="crash", tile="mid",
            restarts={"mid": 2}, config=cfg,
            events=["00:00:01 spawn mid gen=0 pid=1",
                    "00:00:02 tile mid failed (restarts=2)"])

        b = flightrec.load_bundle(path)
        assert b["manifest"]["reason"] == "crash"
        assert b["manifest"]["tile"] == "mid"
        assert b["manifest"]["tiles"]["mid"]["cnc"] == "FAIL"
        assert b["manifest"]["tiles"]["mid"]["restarts"] == 2
        assert len(b["spans"]["mid"]) == 6
        assert b["spans"]["mid"].dtype == trace_mod.TRACE_REC_DTYPE
        assert b["metrics"]["mid"]["slots"]["in_frag_cnt"] == 5
        assert b["links"]["links"]["a_b|mid"]["slow"] == 3
        assert b["links"]["links"]["a_b|mid"]["producer"] == "src"
        assert len(b["events"]) == 2

        out = flightrec.render_bundle(path)
        assert "reason crash" in out and "tile mid" in out
        assert "bottleneck at death:" in out
        assert "slow consumer mid" in out   # the bundled diag drove it
        assert "final spans of mid:" in out
        assert "device" in out              # the final span listing
        assert "stage budget vs 2 ms" in out
        # a second bundle in the same second gets a disambiguated dir
        path2 = flightrec.write_bundle(str(tmp_path), jt, reason="crash",
                                       tile="mid")
        assert path2 != path and os.path.isdir(path2)
    finally:
        jt.close()
        jt.unlink()


# -- log context -------------------------------------------------------------

def test_log_context_tags_records(capsys):
    import logging

    from firedancer_tpu.utils import log as log_mod
    logger = logging.getLogger("firedancer_tpu")
    saved = (logger.level, list(logger.handlers))
    log_mod.boot(level="DEBUG")
    try:
        log_mod.set_context("verify:0", 0)
        log_mod.notice("hello")
        assert " verify:0 hello" in capsys.readouterr().err
        log_mod.set_context("verify:0", 3)   # post-respawn generation
        log_mod.notice("again")
        assert " verify:0#3 again" in capsys.readouterr().err
        log_mod.set_context("", 0)           # supervisor default
        log_mod.notice("sup")
        assert " - sup" in capsys.readouterr().err
    finally:
        # restore the logger as it was; logging.shutdown() here would
        # close the capture streams every later test on this worker uses
        log_mod.set_context("", 0)
        logger.setLevel(saved[0])
        logger.handlers[:] = saved[1]
