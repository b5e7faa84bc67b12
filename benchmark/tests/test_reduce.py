"""The trace reduction and the counter readers, each on a small recorded
fixture: a verify tile's device and host events cut from a chip trace
(fixtures/trace_window.json), and counter records shaped as the program
writes them."""

import json
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

from benchmark import reduce
from benchmark.cells import reader
from firedancer_tpu.disco import trace as trace_mod

FIXTURES = Path(__file__).parent / "fixtures"


def events(rows):
    return reduce.Events([r[0] for r in rows],
                         np.array([r[1] for r in rows], np.int64),
                         np.array([r[2] for r in rows], np.int64),
                         [r[3] for r in rows])


def hand_trace():
    # two overlapping ops, a gap, one op running past the window's end
    dev = events([("sha512", 100, 50, "/device:TPU:0"),
                  ("verify_tail", 120, 60, "/device:TPU:0"),
                  ("fusion", 400, 300, "/device:TPU:0")])
    host = events([("Execute", 150, 300, "python"),
                   ("Wait", 250, 50, "python")])
    return reduce.Trace(dev, host, 1)


def test_busy_is_the_union_inside_the_window():
    tr = hand_trace()
    # [100, 180) and [400, 600) inside [0, 600)
    assert reduce.busy_s(tr, 0, 600) == pytest.approx((80 + 200) / 1e9)


def test_op_seconds_clipped():
    ops = reduce.op_seconds(hand_trace(), 0, 500)
    assert ops == pytest.approx({"sha512": 50e-9, "verify_tail": 60e-9,
                                 "fusion": 100e-9})


def test_idle_gaps_named_by_host_event():
    gaps = reduce.idle_gaps(hand_trace(), 0, 600)
    assert gaps[0] == ["Wait", pytest.approx(220e-9)]     # [180, 400)
    assert gaps[1] == ["no host event", pytest.approx(100e-9)]  # [0, 100)


def recorded():
    fx = json.loads((FIXTURES / "trace_window.json").read_text())
    return fx, reduce.Trace(events(fx["device"]), events(fx["host"]),
                            fx["chips"])


def test_recorded_trace_reduces():
    fx, tr = recorded()
    w0, w1 = fx["w0"], fx["w1"]
    c0, c1 = reduce.covered(tr, w0, w1)
    assert c0 == w0 and w0 < c1 <= w1
    busy = reduce.busy_s(tr, c0, c1)
    assert 0 < busy <= (c1 - c0) / 1e9
    ops = reduce.op_seconds(tr, c0, c1)
    assert sum(ops.values()) >= busy * (1 - 1e-9)  # overlaps count twice
    assert reduce.idle_gaps(tr, c0, c1)
    spans = np.zeros(len(fx["dispatch_ts"]), trace_mod.TRACE_REC_DTYPE)
    spans["kind"] = trace_mod.KIND_DISPATCH
    spans["ts"] = fx["dispatch_ts"]
    view = SimpleNamespace(trace=tr, rec=SimpleNamespace(
        w0_real=w0, w1_real=w1, w0=w0, buckets=[2048],
        spans={"verify:0": spans}))
    idle = reader("device_idle_pct")(view)
    assert idle == pytest.approx(100 * (1 - busy / ((c1 - c0) / 1e9)))
    per_sig = reader("kernel_ns_per_sig")(view)
    assert per_sig == pytest.approx(fx["kernel_ns_per_sig"])


def test_covered_stops_with_the_trace():
    tr = hand_trace()
    assert reduce.covered(tr, 0, 10_000) == (0, 700)
    assert reduce.covered(tr, 0, 500) == (0, 500)


def test_device_metrics_need_most_of_the_window():
    kernel = 'k = custom-call(), custom_call_target="tpu_custom_call"'
    tr = reduce.Trace(events([(kernel, 100, 50, "/device:TPU:0"),
                              ("fusion", 400, 300, "/device:TPU:0")]),
                      events([]), 1)   # device events end at 700
    assert reduce.device_window(tr, 0, 700) == (0, 700)
    assert reduce.device_window(tr, 0, 10_000) is None
    assert reduce.device_window(None, 0, 700) is None
    spans = np.zeros(1, trace_mod.TRACE_REC_DTYPE)
    spans["kind"] = trace_mod.KIND_DISPATCH
    for w1, read in ((700, True), (10_000, False)):
        view = SimpleNamespace(trace=tr, rec=SimpleNamespace(
            w0_real=0, w1_real=w1, w0=0, buckets=[128],
            spans={"verify:0": spans}))
        assert (reader("device_idle_pct")(view) is not None) == read
        assert (reader("kernel_ns_per_sig")(view) is not None) == read


def test_counter_readers():
    c = {"w0": {"verify:0": {"lanes_filled_cnt": 100,
                             "lanes_dispatched_cnt": 1000}},
         "w1": {"verify:0": {"lanes_filled_cnt": 600,
                             "lanes_dispatched_cnt": 3000}},
         "end": {"verify:0": {"txn_in_cnt": 95}}}
    view = SimpleNamespace(rec=SimpleNamespace(
        counters=c, send_pool=np.zeros(100)))
    assert reader("batch_fill_pct")(view) == 25.0
    assert reader("ingest_loss_pct")(view) == 5.0
