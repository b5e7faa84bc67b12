"""Self-healing topology unit tests (fast tier, no device graphs):
SupervisionPolicy parsing/backoff, TopoRun poll + three-state /healthz,
tango dead-consumer eviction, deterministic fault injection, the
GuardedVerifier degradation state machine (fake verifier + fake clock),
pipeline heartbeats through device waits, and mux fseq-cursor resume.

Everything multi-process (real kill -> respawn -> unstall) lives in
tools/chaos_smoke.py (the `chaos` ci.sh tier)."""

import os
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from firedancer_tpu.disco import faultinject
from firedancer_tpu.disco import topo as topo_mod
from firedancer_tpu.disco.mux import Mux
from firedancer_tpu.disco.run import SupervisionPolicy, TopoRun
from firedancer_tpu.disco.topo import TopoBuilder
from firedancer_tpu.tango.fctl import Fctl
from firedancer_tpu.tango.ring import Cnc

# -- SupervisionPolicy -------------------------------------------------------


def test_policy_from_cfg_defaults():
    from firedancer_tpu.app import config as config_mod
    cfg = config_mod.load(None)
    p = SupervisionPolicy.from_cfg(cfg)
    assert p.restart_policy == "fail_fast"
    assert p.max_restarts == 5
    # per-kind staleness: verify overridden in [supervision.heartbeat_stale]
    assert p.stale_ns("verify") == int(120.0 * 1e9)
    assert p.stale_ns("net") == int(60.0 * 1e9)
    assert p.stale_ns(None) == int(60.0 * 1e9)


def test_policy_from_cfg_env_overlay_strings():
    # FDTPU_* env overlays arrive as strings; from_cfg must coerce
    p = SupervisionPolicy.from_cfg({"supervision": {
        "restart_policy": "respawn", "max_restarts": "2",
        "backoff_initial_s": "0.01", "heartbeat_stale_s": "1.5",
        "heartbeat_stale": {"verify": "3"}}})
    assert p.restart_policy == "respawn" and p.max_restarts == 2
    assert p.backoff_initial_s == 0.01
    assert p.stale_ns("verify") == int(3e9)
    assert p.stale_ns("dedup") == int(1.5e9)


def test_backoff_deterministic_and_bounded():
    p = SupervisionPolicy(backoff_initial_s=0.25, backoff_max_s=8.0,
                          backoff_jitter=0.2)
    for attempt in range(1, 12):
        d1 = p.backoff_s(attempt, "verify:0")
        d2 = p.backoff_s(attempt, "verify:0")
        assert d1 == d2, "jitter must be deterministic per (tile, attempt)"
        base = min(0.25 * 2 ** (attempt - 1), 8.0)
        assert base * 0.8 <= d1 <= base * 1.2
    # different tiles de-synchronize
    assert p.backoff_s(3, "verify:0") != p.backoff_s(3, "verify:1")
    # jitter off -> exact exponential
    p0 = SupervisionPolicy(backoff_initial_s=0.5, backoff_max_s=4.0,
                           backoff_jitter=0.0)
    assert [p0.backoff_s(a) for a in (1, 2, 3, 4, 5)] == \
        [0.5, 1.0, 2.0, 4.0, 4.0]


# -- TopoRun: wait_ready regression + poll + /healthz ------------------------


def _mini_spec(tag: str):
    return (
        TopoBuilder(f"sup{tag}{os.getpid()}", wksp_mb=8)
        .link("a_b", depth=64, mtu=256)
        .tile("src", "sink", outs=["a_b"])
        .tile("v:0", "verify", ins=["a_b"])
        .build()
    )


class _FakeProc:
    def __init__(self, alive=True):
        self._alive = alive

    def is_alive(self):
        return self._alive

    def join(self, *a):
        pass

    def terminate(self):
        self._alive = False

    def kill(self):
        self._alive = False


def test_wait_ready_unstarted_raises():
    # regression: start=False + wait_ready used to die with a bare
    # KeyError off the empty procs dict
    run = TopoRun(_mini_spec("wr"), start=False)
    try:
        with pytest.raises(RuntimeError, match="not started"):
            run.wait_ready(timeout=0.1)
    finally:
        run.close()


def test_poll_states_and_healthz_three_way():
    policy = SupervisionPolicy(heartbeat_stale_s=0.05,
                               heartbeat_stale_by_kind={"verify": 30.0})
    run = TopoRun(_mini_spec("hz"), start=False, metrics_port=0,
                  policy=policy)
    try:
        run.procs = {"src": _FakeProc(), "v:0": _FakeProc()}
        base = f"http://127.0.0.1:{run.metrics_port}"

        # tiles still in BOOT within grace -> poll() holds fire, /healthz 503
        assert run.poll() is None
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(f"{base}/healthz", timeout=10)
        assert ei.value.code == 503
        assert "unhealthy" in ei.value.read().decode()

        # everything RUN + fresh heartbeats -> healthy
        for cnc in run.jt.cnc.values():
            cnc.signal(Cnc.SIGNAL_RUN)
            cnc.heartbeat(time.monotonic_ns())
        assert run.poll() is None
        r = urllib.request.urlopen(f"{base}/healthz", timeout=10)
        body = r.read().decode()
        assert r.status == 200 and body.startswith("ok\n")
        assert "slo " in body  # healthz carries the SLO one-liner now

        # degraded verify tile: still 200, but flagged (load balancers keep
        # routing; operators get a distinct state)
        run.jt.metrics["v:0"].set("degraded_mode", 1)
        r = urllib.request.urlopen(f"{base}/healthz", timeout=10)
        assert r.status == 200
        body = r.read().decode()
        assert body.startswith("degraded\n") and "v:0" in body
        run.jt.metrics["v:0"].set("degraded_mode", 0)

        # per-KIND staleness: age both heartbeats past the 50ms default;
        # the verify tile's 30s override keeps it healthy, src flags
        old = time.monotonic_ns() - int(0.2 * 1e9)
        for cnc in run.jt.cnc.values():
            cnc.heartbeat(old)
        assert run.poll() == "src"
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(f"{base}/healthz", timeout=10)
        body = ei.value.read().decode()
        assert "src" in body and "v:0" not in body

        # dead process beats everything
        run.jt.cnc["src"].heartbeat(time.monotonic_ns())
        run.procs["src"]._alive = False
        assert run.poll() == "src"

        # a tile wedged in BOOT past its grace window is a failure too
        run.procs["src"]._alive = True
        run.jt.cnc["src"].signal(Cnc.SIGNAL_BOOT)
        run._boot_deadline["src"] = time.monotonic() - 1.0
        assert run.poll() == "src"
    finally:
        run.procs = {}
        run.close()


# -- tango dead-consumer eviction --------------------------------------------


class _FakeFSeq:
    def __init__(self, seq=0):
        self.seq = seq

    def update(self, seq):
        self.seq = seq

    def query(self):
        return self.seq

    def diag_add(self, idx, delta=1):
        pass


class _FakeMcache:
    def __init__(self, seq):
        self._seq = seq

    def seq_query(self):
        return self._seq


def test_fctl_rx_evict_unblocks_producer():
    fs_dead, fs_live = _FakeFSeq(0), _FakeFSeq(90)
    f = Fctl(cr_max=64).rx_add(fs_dead).rx_add(fs_live)
    assert f.cr_query(100) == 0          # dead consumer pins credits
    assert f.rx_evict(fs_dead) is True
    assert f.rx_cnt == 1
    assert f.cr_query(100) == 64 - 10    # only the live consumer counts
    assert f.rx_evict(fs_dead) is False  # already gone


def test_evict_dead_consumer_fast_forwards():
    fs = _FakeFSeq(3)
    cur = Fctl.evict_dead_consumer(fs, _FakeMcache(777))
    assert cur == 777 and fs.query() == 777
    # and again in real shm: FSeq.reset is the supervisor-side store
    spec = _mini_spec("ev")
    jt = topo_mod.create(spec)
    try:
        fseq = jt.fseq[("v:0", "a_b")]
        mc = jt.links["a_b"].mcache
        fseq.update(1)
        # produce a few frags so the producer cursor moves ahead
        for i in range(5):
            mc.publish(i)
        assert Fctl.evict_dead_consumer(fseq, mc) == mc.seq_query()
        assert fseq.query() == mc.seq_query()
    finally:
        jt.close()
        jt.unlink()


# -- fault injection ---------------------------------------------------------


def test_faultinject_parse_and_overlay():
    plans = faultinject.parse_plan(
        "verify=delay_frag_us:50,seed:9; verify:1=kill_after_frags:10,boot:0"
        ";source=drop_frag_p:0.25")
    assert plans["verify"] == {"delay_frag_us": 50, "seed": 9}
    assert plans["source"] == {"drop_frag_p": 0.25}
    # kind entry applies to every instance; exact entry overlays knob-wise
    assert faultinject.plan_for("verify:0", plans) == \
        {"delay_frag_us": 50, "seed": 9}
    assert faultinject.plan_for("verify:1", plans) == \
        {"delay_frag_us": 50, "seed": 9, "kill_after_frags": 10, "boot": 0}
    assert faultinject.plan_for("dedup", plans) is None


def test_faultinject_for_tile_gating():
    env = {"FDTPU_FAULTS": "verify:0=kill_after_frags:5,boot:0"}
    # no plan names the tile -> None (the zero-overhead contract)
    assert faultinject.for_tile("dedup", environ=env) is None
    assert faultinject.for_tile("verify:0", environ={}) is None
    f = faultinject.for_tile("verify:0", environ=env)
    assert f is not None and f._kill_after == 5
    # boot-generation gate: the respawned incarnation runs fault-free
    assert faultinject.for_tile("verify:0", restart_cnt=1, environ=env) is None
    # cfg string plan merges over env; cfg dict applies directly
    f = faultinject.for_tile(
        "verify:0", cfg={"faults": "verify:0=delay_frag_us:7"}, environ=env)
    assert f._kill_after == 5 and f._delay_s == pytest.approx(7e-6)
    f = faultinject.for_tile("x", cfg={"faults": {"drop_frag_p": 0.5}},
                             environ={})
    assert f._drop_p == 0.5


def test_faultinject_deterministic_streams():
    mk = lambda: faultinject.FaultInjector(  # noqa: E731
        "verify:0", {"drop_frag_p": 0.3, "corrupt_payload_p": 0.3, "seed": 4})
    a, b = mk(), mk()
    pay = bytes(range(64))
    seq_a = [a.frag(pay) for _ in range(64)]
    seq_b = [b.frag(pay) for _ in range(64)]
    assert seq_a == seq_b
    drops = sum(1 for _, d in seq_a if d)
    flips = sum(1 for p, d in seq_a if not d and p != pay)
    assert drops and flips  # both knobs actually fired
    # corrupted payloads differ by exactly one bit
    for p, d in seq_a:
        if not d and p != pay:
            diff = np.bitwise_xor(np.frombuffer(p, np.uint8),
                                  np.frombuffer(pay, np.uint8))
            assert int(np.unpackbits(diff).sum()) == 1
    # a different instance name diverges under the same plan seed
    c = faultinject.FaultInjector(
        "verify:1", {"drop_frag_p": 0.3, "corrupt_payload_p": 0.3, "seed": 4})
    assert [c.frag(pay) for _ in range(64)] != seq_a


def test_faultinject_kill_fires_before_nth_frag(monkeypatch):
    exits = []
    monkeypatch.setattr(faultinject.os, "_exit",
                        lambda code: exits.append(code))
    f = faultinject.FaultInjector("v", {"kill_after_frags": 3})
    f.frag(b"x")
    f.frag(b"x")
    assert not exits
    f.frag(b"x")  # the 3rd frag is never processed
    assert exits == [faultinject.KILL_EXIT_CODE]


def test_faultinject_batch_kill_defers_to_frag_boundary(monkeypatch):
    # vectorized rx paths: a kill threshold inside the batch trims it to
    # the allowed prefix (processed + span-recorded by the mux) and the
    # kill fires at the NEXT fault-point entry — the dead tile's flight
    # bundle keeps its final spans instead of losing the whole burst
    exits = []
    monkeypatch.setattr(faultinject.os, "_exit",
                        lambda code: exits.append(code))
    f = faultinject.FaultInjector("v", {"kill_after_frags": 150})
    assert f.burst(100, None, None) == 100   # wholly under threshold
    assert not exits
    assert f.burst(100, None, None) == 49    # trimmed to frags 101..149
    assert not exits                         # deferred past the batch
    f.house()                                # next entry: corpse drops
    assert exits == [faultinject.KILL_EXIT_CODE]


def test_faultinject_dispatch_fail_n_then_heals():
    f = faultinject.FaultInjector("v", {"fail_dispatch_n": 2})
    for _ in range(2):
        with pytest.raises(faultinject.InjectedDispatchError):
            f.dispatch()
    f.dispatch()  # healed
    assert f.dispatch_cnt == 3


# -- GuardedVerifier state machine -------------------------------------------


def _host_odd(msgs, lens, sigs, pubs):
    # deterministic fake host backend: odd lanes pass
    return np.arange(len(msgs)) % 2 == 1


class _FlakyFn:
    """Fake device verifier: scripted per-call behavior."""

    def __init__(self, script):
        self.script = list(script)  # "ok" | "raise" | "hang"
        self.calls = 0

    def __call__(self, msgs, lens, sigs, pubs):
        mode = self.script[min(self.calls, len(self.script) - 1)]
        self.calls += 1
        if mode == "raise":
            raise RuntimeError("injected device loss")
        if mode == "hang":
            return _Hung()
        return np.ones(len(msgs), dtype=bool)


class _Hung:
    def is_ready(self):
        return False

    def __array__(self, dtype=None, copy=None):
        raise RuntimeError("device gone")


def _gv(fn, **kw):
    from firedancer_tpu.disco.pipeline import GuardedVerifier
    t = [0.0]
    kw.setdefault("clock", lambda: t[0])
    kw.setdefault("host_arrays", _host_odd)
    g = GuardedVerifier(fn, **kw)
    return g, t


def _args(n=8):
    z = np.zeros((n, 4), np.uint8)
    return z, np.zeros(n, np.int32), z, z


def test_guarded_retry_masks_transient_failure():
    g, _ = _gv(_FlakyFn(["raise", "ok"]), retries=1, fail_threshold=3)
    ok = np.asarray(g(*_args()))
    assert ok.all() and not g.degraded
    assert g.device_fail_cnt == 0 and g.fallback_lanes == 0


def test_guarded_batch_fallback_then_degraded_then_recovery():
    g, t = _gv(_FlakyFn(["raise"] * 9 + ["ok"]), retries=0,
               fail_threshold=3, reprobe_s=5.0)
    expect = _host_odd(*_args())
    # failures 1..2: per-batch host fallback, still healthy
    for i in range(2):
        ok = np.asarray(g(*_args()))
        assert np.array_equal(ok, expect)
        assert not g.degraded and g.device_fail_cnt == i + 1
    # failure 3 crosses the consecutive threshold
    np.asarray(g(*_args()))
    assert g.degraded and g.device_fail_cnt == 3
    # degraded: dispatches short-circuit to host (device fn NOT called)
    calls0 = g.fn.calls
    np.asarray(g(*_args()))
    assert g.fn.calls == calls0
    assert g.fallback_vps() == 0  # clock frozen; just must not divide by 0
    # advance past the reprobe window: probe fails, re-arms the timer
    t[0] += 6.0
    np.asarray(g(*_args()))
    assert g.fn.calls == calls0 + 1 and g.degraded
    assert g.reprobe_cnt == 1
    # next window: the script heals, the probe materializes -> recovered
    g.fn.script = ["ok"]
    g.fn.calls = 0
    t[0] += 6.0
    ok = np.asarray(g(*_args()))
    assert ok.all()
    assert not g.degraded and g._consec == 0
    # healthy again: device path serves
    assert np.asarray(g(*_args())).all()


def test_guarded_harvest_deadline_counts_as_failure():
    # device accepts every dispatch but never completes: the dispatch-side
    # never raises, so only the harvest deadline can cross the threshold
    g, t = _gv(_FlakyFn(["hang"]), retries=0, fail_threshold=2,
               deadline_s=1.0)
    expect = _host_odd(*_args())
    v = g(*_args())
    assert not v.is_ready()
    t[0] += 2.0            # past deadline: harvest must not block forever
    assert v.is_ready()
    ok = np.asarray(v)
    assert np.array_equal(ok, expect)
    assert g.device_fail_cnt == 1 and not g.degraded
    v2 = g(*_args())
    t[0] += 2.0
    np.asarray(v2)
    assert g.degraded


def test_guarded_deadline_zero_disables_hang_watchdog():
    # deadline_s <= 0: a slow dispatch is never declared hung no matter
    # how much time passes (bench topologies on a contended CPU host
    # disable the watchdog this way); a verdict that eventually
    # materializes still counts as a clean device success
    g, t = _gv(_FlakyFn(["hang"]), retries=0, fail_threshold=2,
               deadline_s=0.0)
    v = g(*_args())
    t[0] += 1e6
    assert not v.is_ready()                 # poll-only, never force-ready
    # the "hung" device finally completes: swap in a real verdict
    v._dev = np.ones(8, dtype=bool)
    assert v.is_ready()
    assert np.asarray(v).all()
    assert g.device_fail_cnt == 0 and not g.degraded


def test_guarded_consec_clears_only_on_materialized_verdict():
    g, t = _gv(_FlakyFn(["raise", "ok", "raise", "raise"]), retries=0,
               fail_threshold=3)
    np.asarray(g(*_args()))        # fail #1
    assert g._consec == 1
    np.asarray(g(*_args()))        # a verdict MATERIALIZES -> consec clears
    assert g._consec == 0
    np.asarray(g(*_args()))
    np.asarray(g(*_args()))
    assert g._consec == 2 and not g.degraded


def test_guarded_fault_injection_drives_dispatch():
    fault = faultinject.FaultInjector("v", {"fail_dispatch_n": 2})
    g, t = _gv(_FlakyFn(["ok"]), retries=0, fail_threshold=2,
               reprobe_s=1.0, fault=fault)
    expect = _host_odd(*_args())
    assert np.array_equal(np.asarray(g(*_args())), expect)
    np.asarray(g(*_args()))
    assert g.degraded              # 2 consecutive injected failures
    t[0] += 2.0                    # fault healed (fail_dispatch_n spent)
    assert np.asarray(g(*_args())).all()
    assert not g.degraded


def test_guarded_surface_mirrors_wrapped_fn():
    # a plain 4-array fn must NOT grow dispatch_blob (pipeline packed
    # autodetect is hasattr-based)
    g, _ = _gv(_FlakyFn(["ok"]))
    assert not hasattr(g, "dispatch_blob")

    class _Packed:
        n_shards = 1

        def __call__(self, *a):
            return np.ones(4, bool)

        def dispatch_blob(self, blob, maxlen=None):
            return np.ones(len(blob), dtype=bool)

    from firedancer_tpu.disco.pipeline import GuardedVerifier
    g2 = GuardedVerifier(_Packed(), host_blob=lambda b, maxlen: np.ones(
        len(b), bool), host_arrays=_host_odd)
    assert hasattr(g2, "dispatch_blob")
    assert g2.n_shards == 1        # __getattr__ passthrough
    ok = np.asarray(g2.dispatch_blob(np.zeros((4, 8), np.uint8)))
    assert ok.shape == (4,)


# -- pipeline heartbeats through device waits --------------------------------


def test_pipeline_heartbeats_during_device_wait():
    from firedancer_tpu.ballet import txn as txn_lib
    from firedancer_tpu.disco.pipeline import VerifyPipeline

    class _SlowVerdict:
        def __init__(self, n, polls):
            self.n = n
            self.polls = polls

        def is_ready(self):
            self.polls -= 1
            return self.polls <= 0

        def __array__(self, dtype=None, copy=None):
            return np.ones(self.n, dtype=bool)

    def slow_fn(msgs, lens, sigs, pubs):
        return _SlowVerdict(len(msgs), polls=5)

    beats = []
    rng = np.random.default_rng(11)
    payloads = []
    for _ in range(4):
        msg = txn_lib.build_unsigned([rng.bytes(32)], rng.bytes(32),
                                     [(1, bytes([0]), bytes(8))],
                                     extra_accounts=[rng.bytes(32)])
        payloads.append(txn_lib.assemble([rng.bytes(64)], msg))
    mlen = len(txn_lib.parse(payloads[0]).message(payloads[0]))
    pipe = VerifyPipeline(slow_fn, buckets=[(4, mlen)], max_inflight=0,
                          heartbeat_cb=lambda: beats.append(1))
    out = []
    for p in payloads:
        out += pipe.submit(p)
    out += pipe.flush()
    assert len(out) == 4
    # ~4 not-ready polls each heartbeat once before the verdict lands
    assert len(beats) >= 3


# -- degraded verify under respawn -------------------------------------------


class _DeadDevice:
    """Device graph that is permanently down: every verdict is served by
    the GuardedVerifier's host fallback."""

    def __call__(self, msgs, lens, sigs, pubs):
        raise RuntimeError("injected device loss")


def _sign_batch(n: int, seed: int = 33):
    """n real (msg, sig, pub) triples; odd lanes corrupted -> mixed
    verdicts, so a fallback that fails open (or closed) is caught."""
    from firedancer_tpu.ops import ed25519 as ed
    rng = np.random.default_rng(seed)
    msgs, sigs, pubs = [], [], []
    for i in range(n):
        seed_b = rng.bytes(32)
        pub, _, _ = ed.keypair_from_seed(seed_b)
        msg = rng.bytes(32)
        sig = bytearray(ed.sign(seed_b, msg))
        if i % 2:
            sig[10] ^= 0x40
        msgs.append(msg)
        sigs.append(bytes(sig))
        pubs.append(pub)
    return msgs, sigs, pubs


class _DegradedVerifyVt:
    """Fast-tier stand-in for a degraded verify tile: verdicts come from a
    GuardedVerifier whose device graph is dead (so every verdict is
    served by the strict host twin), and the tile 'dies' (halts
    mid-stream) after `die_after` frags."""

    def __init__(self, die_after=None):
        self.die_after = die_after
        self.seqs = []
        self.g = None

    def init(self, ctx):
        from firedancer_tpu.disco.pipeline import GuardedVerifier
        self.g = GuardedVerifier(_DeadDevice(), retries=0, fail_threshold=1,
                                 reprobe_s=1e9, clock=lambda: 0.0)

    def on_frag(self, ctx, iidx, meta, payload):
        pub, sig, msg = payload[:32], payload[32:96], payload[96:]
        ok = np.asarray(self.g(
            np.frombuffer(msg, np.uint8)[None, :],
            np.array([len(msg)], np.int32),
            np.frombuffer(sig, np.uint8)[None, :],
            np.frombuffer(pub, np.uint8)[None, :]))
        self.seqs.append(int(meta["seq"]))
        ctx.publish(b"", sig=int(bool(ok[0])), out=0)
        if self.die_after is not None and len(self.seqs) >= self.die_after:
            ctx.halt()


def test_degraded_verify_resumes_across_respawn_no_dup_verdicts():
    """Kill -> respawn of a degraded verify tile: the respawned
    incarnation picks up from the dead tile's fseq cursor so ZERO
    verdicts are duplicated, and its GuardedVerifier fallback serves the
    strict host twin (models.verifier.host_verify_arrays)."""
    from firedancer_tpu.models.verifier import host_verify_arrays

    n = 12
    spec = (
        TopoBuilder(f"degraded{os.getpid()}", wksp_mb=8)
        .link("src_verify", depth=64, mtu=256)
        .link("verify_dedup", depth=64, mtu=64)
        .tile("source", "sink", outs=["src_verify"])
        .tile("verify:0", "verify", ins=["src_verify"],
              outs=["verify_dedup"])
        .tile("dedup", "sink", ins=["verify_dedup"])
        .build()
    )
    jt = topo_mod.create(spec)
    try:
        msgs, sigs, pubs = _sign_batch(n)
        lnk = jt.links["src_verify"]
        chunk = 0
        for i in range(n):
            payload = pubs[i] + sigs[i] + msgs[i]
            nxt = lnk.dcache.write(chunk, payload)
            lnk.mcache.publish(0, chunk, len(payload))
            chunk = nxt
        # keep the dedup consumer from pinning verdict-link credits
        jt.fseq[("dedup", "verify_dedup")].update(
            jt.links["verify_dedup"].mcache.seq0() + n)

        # incarnation 0 dies after 5 verdicts (mid-stream halt)
        vt0 = _DegradedVerifyVt(die_after=5)
        m0 = Mux(jt, "verify:0", vt0)
        m0.run()
        assert vt0.g.degraded
        assert len(vt0.seqs) == 5
        cursor = jt.fseq[("verify:0", "src_verify")].query()
        assert cursor == vt0.seqs[-1] + 1, "cursor must persist the ack"

        # respawn: restart_cnt=1 resumes from the cursor, same spec cfg
        vt1 = _DegradedVerifyVt(die_after=n - 5)
        m1 = Mux(jt, "verify:0", vt1, restart_cnt=1)
        m1.run()
        assert vt1.g.degraded
        assert vt1.g._host_arrays is host_verify_arrays, \
            "respawned fallback is not the strict host twin"
        assert vt1.g.fallback_lanes == n - 5

        # zero duplicate verdicts: the two incarnations' frag seqs are
        # disjoint and together cover the full stream
        assert not (set(vt0.seqs) & set(vt1.seqs)), "duplicate verdicts"
        assert sorted(vt0.seqs + vt1.seqs) == sorted(
            set(vt0.seqs) | set(vt1.seqs))
        assert len(vt0.seqs) + len(vt1.seqs) == n

        # and the verdict stream downstream carries the mixed host-twin
        # verdicts (odd lanes corrupted at signing time), equal to the
        # strict host verify of the same stream
        mc = jt.links["verify_dedup"].mcache
        verdicts = []
        seq = mc.seq0()
        for _ in range(n):
            rc, meta = mc.query(seq)
            assert rc == 0
            verdicts.append(int(meta["sig"]))
            seq += 1
        assert verdicts == [1, 0] * (n // 2)
        expect = host_verify_arrays(
            np.frombuffer(b"".join(msgs), np.uint8).reshape(n, 32),
            np.full(n, 32, np.int32),
            np.frombuffer(b"".join(sigs), np.uint8).reshape(n, 64),
            np.frombuffer(b"".join(pubs), np.uint8).reshape(n, 32))
        assert verdicts == [int(v) for v in expect]
        # drop every shm view (mux dcaches, link handles, the last meta
        # record) before the workspace unmaps
        m0 = m1 = meta = lnk = mc = None  # noqa: F841
        import gc
        gc.collect()
    finally:
        jt.close()
        jt.unlink()


# -- drain protocol: DRAIN/DRAINED state machine -----------------------------


def test_policy_from_cfg_drain_knobs():
    from firedancer_tpu.app import config as config_mod
    cfg = config_mod.load(None)
    p = SupervisionPolicy.from_cfg(cfg)
    # unconfigured: drain off, behavior identical to pre-drain trees
    assert p.drain_timeout_s == 0.0 and p.drain_manifest_dir == ""
    p = SupervisionPolicy.from_cfg({"supervision": {
        "drain_timeout_s": "2.5", "drain_manifest_dir": "/tmp/dm"}})
    assert p.drain_timeout_s == 2.5 and p.drain_manifest_dir == "/tmp/dm"


def test_dependency_order_producers_first():
    from firedancer_tpu.disco.run import dependency_order
    spec = (
        TopoBuilder(f"dep{os.getpid()}", wksp_mb=8)
        .link("s_v", depth=64, mtu=256)
        .link("v_d", depth=64, mtu=64)
        .tile("dedup", "sink", ins=["v_d"])          # declared consumer-first
        .tile("verify:0", "verify", ins=["s_v"], outs=["v_d"])
        .tile("source", "sink", outs=["s_v"])
        .build()
    )
    order = dependency_order(spec)
    assert sorted(order) == sorted(t.name for t in spec.tiles)
    assert order.index("source") < order.index("verify:0")
    assert order.index("verify:0") < order.index("dedup")


def test_fctl_evict_then_rejoin_no_double_credit_no_redelivery():
    """Eviction -> re-join race: after the supervisor fast-forwards a dead
    consumer's fseq, the respawned incarnation must resume FROM the
    evicted cursor (mux restart_cnt>0 resume), so its first fseq publish
    can never rewind the line (double-crediting the producer with lag it
    already acked) and no frag below the cursor is ever re-delivered."""
    spec = _mini_spec("rj")
    jt = topo_mod.create(spec)
    try:
        mc = jt.links["a_b"].mcache
        for i in range(10):
            mc.publish(i)
        fseq = jt.fseq[("v:0", "a_b")]
        fseq.update(mc.seq0() + 3)   # consumer died 7 frags behind

        # producer side: the dead line pins credits until evicted
        f = Fctl(cr_max=8).rx_add(fseq)
        assert f.cr_query(mc.seq_query()) == 1  # 8 - 7 lag
        cursor = Fctl.evict_dead_consumer(fseq, mc)
        assert cursor == mc.seq_query()
        assert f.cr_query(mc.seq_query()) == 8  # fully refilled

        # re-join: the respawned mux resumes from the evicted cursor,
        # not its corpse's last position
        class _Vt:
            pass

        m1 = Mux(jt, "v:0", _Vt(), restart_cnt=1)
        assert m1.ins[0].seq == cursor, "respawn would re-deliver frags"
        # its first housekeeping-style ack writes the same cursor: the
        # producer's credit view never rewinds
        m1.ins[0].fseq.update(m1.ins[0].seq)
        assert f.cr_query(mc.seq_query()) == 8
        m1 = None  # noqa: F841
        import gc
        gc.collect()
    finally:
        jt.close()
        jt.unlink()


class _DrainVt:
    """Records delivered frag seqs; optional drain hook that reports dry
    only after `wet` polls (an in-flight device batch flushing)."""

    def __init__(self, die_after=None, wet=0):
        self.seqs = []
        self.die_after = die_after
        self.wet = wet
        self.drain_polls = 0

    def on_frag(self, ctx, iidx, meta, payload):
        self.seqs.append(int(meta["seq"]))
        if self.die_after is not None and len(self.seqs) >= self.die_after:
            ctx.halt()

    def drain(self, ctx) -> bool:
        self.drain_polls += 1
        return self.drain_polls > self.wet


def _run_mux_thread(m):
    import threading
    t = threading.Thread(target=m.run, daemon=True)
    t.start()
    return t


def _wait(pred, timeout=10.0, what="condition"):
    deadline = time.monotonic() + timeout
    while not pred():
        if time.monotonic() > deadline:
            raise TimeoutError(f"timed out waiting for {what}")
        time.sleep(0.002)


def test_mux_drain_flushes_parks_and_manifests(tmp_path):
    spec = (
        TopoBuilder(f"dr{os.getpid()}", wksp_mb=8)
        .link("a_b", depth=64, mtu=256)
        .tile("src", "sink", outs=["a_b"])
        .tile("v:0", "verify", ins=["a_b"],
              supervision={"drain_manifest_dir": str(tmp_path)})
        .build()
    )
    jt = topo_mod.create(spec)
    try:
        mc = jt.links["a_b"].mcache
        for i in range(6):
            mc.publish(i)
        vt = _DrainVt(wet=3)
        m = Mux(jt, "v:0", vt)
        m.HOUSE_NS = 1_000_000  # 1ms housekeeping: fast DRAIN pickup
        cnc = jt.cnc["v:0"]
        th = _run_mux_thread(m)
        try:
            _wait(lambda: cnc.signal_query() == Cnc.SIGNAL_RUN, what="RUN")
            _wait(lambda: len(vt.seqs) == 6, what="frag consumption")
            cnc.signal(Cnc.SIGNAL_DRAIN)
            _wait(lambda: cnc.signal_query() == Cnc.SIGNAL_DRAINED,
                  what="DRAINED ack")
            # the drain hook was polled until it reported dry
            assert vt.drain_polls >= 4
            # frozen cursor covers everything consumed
            assert jt.fseq[("v:0", "a_b")].query() == mc.seq0() + 6
            snap = jt.metrics["v:0"].snapshot()
            assert snap["drain_cnt"] == 1
            assert snap["drain_flush_ns"] >= 0
            # cursor manifest persisted for the successor / audit
            import json
            man_path = tmp_path / "v_0.manifest.json"
            assert man_path.exists()
            man = json.loads(man_path.read_text())
            assert man["tile"] == "v:0" and man["kind"] == "verify"
            assert man["cursors"]["a_b"] == mc.seq0() + 6
            assert man["restart_cnt"] == 0 and man["knob_gen"] == 0
            # park holds DRAINED (the finally's BOOT must not clobber it)
            time.sleep(0.05)
            assert cnc.signal_query() == Cnc.SIGNAL_DRAINED
            hb0 = cnc.heartbeat_query()
            _wait(lambda: cnc.heartbeat_query() > hb0, what="park heartbeat")
        finally:
            cnc.signal(Cnc.SIGNAL_HALT)
            th.join(10.0)
        assert not th.is_alive()
        m = None  # noqa: F841
        import gc
        gc.collect()
    finally:
        jt.close()
        jt.unlink()


def test_mux_drain_restart_zero_loss_zero_dup():
    """Rolling-restart data-plane contract, in process: incarnation 0 is
    DRAINed mid-stream (not killed), incarnation 1 resumes from the
    drained cursor — the two seq sets are disjoint and cover the whole
    stream (zero loss, zero duplicate verdicts)."""
    spec = _mini_spec("dz")
    jt = topo_mod.create(spec)
    try:
        mc = jt.links["a_b"].mcache
        for i in range(12):
            mc.publish(i)
        vt0 = _DrainVt()
        m0 = Mux(jt, "v:0", vt0)
        m0.HOUSE_NS = 1_000_000
        cnc = jt.cnc["v:0"]
        th = _run_mux_thread(m0)
        try:
            _wait(lambda: len(vt0.seqs) == 12, what="pre-drain consumption")
            cnc.signal(Cnc.SIGNAL_DRAIN)
            _wait(lambda: cnc.signal_query() == Cnc.SIGNAL_DRAINED,
                  what="DRAINED ack")
        finally:
            cnc.signal(Cnc.SIGNAL_HALT)
            th.join(10.0)
        assert not th.is_alive()

        # frags published after the drain belong to the successor
        for i in range(6):
            mc.publish(100 + i)
        vt1 = _DrainVt(die_after=6)
        m1 = Mux(jt, "v:0", vt1, restart_cnt=1)
        assert m1.ins[0].seq == mc.seq0() + 12, "successor must resume " \
            "from the drained cursor"
        m1.run()
        assert not (set(vt0.seqs) & set(vt1.seqs)), "duplicate delivery"
        assert len(vt0.seqs) + len(vt1.seqs) == 18, "lost frags"
        m0 = m1 = None  # noqa: F841
        import gc
        gc.collect()
    finally:
        jt.close()
        jt.unlink()


def test_drain_tile_acks_and_times_out():
    import threading
    run = TopoRun(_mini_spec("dt"), start=False, metrics_port=0,
                  policy=SupervisionPolicy(drain_timeout_s=5.0))
    try:
        run.procs = {"src": _FakeProc(), "v:0": _FakeProc()}
        cnc = run.jt.cnc["v:0"]
        cnc.signal(Cnc.SIGNAL_RUN)

        def _ack():
            while cnc.signal_query() != Cnc.SIGNAL_DRAIN:
                time.sleep(0.002)
            cnc.heartbeat(time.monotonic_ns())
            cnc.signal(Cnc.SIGNAL_DRAINED)

        t = threading.Thread(target=_ack, daemon=True)
        t.start()
        assert run.drain_tile("v:0", 5.0) is True
        t.join(5.0)
        # nobody acks src: bounded False, never a hang
        t0 = time.monotonic()
        assert run.drain_tile("src", 0.2) is False
        assert time.monotonic() - t0 < 2.0
        # death mid-drain is a False too (crash-respawn fallback)
        run.procs["v:0"]._alive = False
        cnc.signal(Cnc.SIGNAL_RUN)
        assert run.drain_tile("v:0", 5.0) is False
    finally:
        run.procs = {}
        run.close()


def test_drain_tile_reasserts_over_boot_stamp():
    # a tile respawned an instant before drain_tile stamps RUN on loop
    # entry, overwriting a DRAIN raised during its boot — the supervisor
    # must re-assert the lost request instead of timing out
    import threading
    run = TopoRun(_mini_spec("db"), start=False, metrics_port=0,
                  policy=SupervisionPolicy(drain_timeout_s=5.0))
    try:
        run.procs = {"src": _FakeProc(), "v:0": _FakeProc()}
        cnc = run.jt.cnc["v:0"]
        cnc.signal(Cnc.SIGNAL_BOOT)

        def _booting_tile():
            while cnc.signal_query() != Cnc.SIGNAL_DRAIN:
                time.sleep(0.002)          # supervisor raises DRAIN...
            cnc.signal(Cnc.SIGNAL_RUN)     # ...boot stamp loses it
            while cnc.signal_query() != Cnc.SIGNAL_DRAIN:
                time.sleep(0.002)          # re-asserted by drain_tile
            cnc.heartbeat(time.monotonic_ns())
            cnc.signal(Cnc.SIGNAL_DRAINED)

        t = threading.Thread(target=_booting_tile, daemon=True)
        t.start()
        assert run.drain_tile("v:0", 5.0) is True
        t.join(5.0)
    finally:
        run.procs = {}
        run.close()


def test_retile_swaps_restart_required_cfg():
    run = TopoRun(_mini_spec("rt"), start=False)
    try:
        src_cfg = dict(run.jt.tile_spec("src").cfg)
        run._retile("v:0", {"n_buffers": 5, "max_inflight": 2})
        # supervisor-side lookups (jt.tile_spec) follow the new spec
        assert run.jt.spec is run.spec
        ts = run.jt.tile_spec("v:0")
        assert ts.cfg["n_buffers"] == 5 and ts.cfg["max_inflight"] == 2
        # only the named tile's cfg changed; topology shape is intact
        assert ts.kind == "verify"
        assert [il.link for il in ts.in_links] == ["a_b"]
        assert dict(run.jt.tile_spec("src").cfg) == src_cfg
    finally:
        run.close()


def test_poll_and_healthz_report_draining():
    policy = SupervisionPolicy(heartbeat_stale_s=30.0)
    run = TopoRun(_mini_spec("dh"), start=False, metrics_port=0,
                  policy=policy)
    try:
        run.procs = {"src": _FakeProc(), "v:0": _FakeProc()}
        base = f"http://127.0.0.1:{run.metrics_port}"
        for cnc in run.jt.cnc.values():
            cnc.signal(Cnc.SIGNAL_RUN)
            cnc.heartbeat(time.monotonic_ns())

        # a DRAINing tile with a live heartbeat is an operational event,
        # not a failure: poll holds fire, healthz serves 200 "draining"
        run.jt.cnc["v:0"].signal(Cnc.SIGNAL_DRAIN)
        assert run.poll() is None
        r = urllib.request.urlopen(f"{base}/healthz", timeout=10)
        body = r.read().decode()
        assert r.status == 200
        assert body.startswith("draining\n") and "v:0" in body

        run.jt.cnc["v:0"].signal(Cnc.SIGNAL_DRAINED)
        assert run.poll() is None
        r = urllib.request.urlopen(f"{base}/healthz", timeout=10)
        assert r.read().decode().startswith("draining\n")

        # but a WEDGED drain (stale heartbeat) is still a failure.  The
        # verify kind's staleness bound shrinks rather than the heartbeat
        # being back-dated: a back-dated monotonic stamp goes below zero
        # on a host that has been up for less than the bound
        run.jt.cnc["v:0"].signal(Cnc.SIGNAL_DRAIN)
        policy.heartbeat_stale_by_kind["verify"] = 0.05
        run.jt.cnc["v:0"].heartbeat(time.monotonic_ns())
        time.sleep(0.1)
        assert run.poll() == "v:0"
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(f"{base}/healthz", timeout=10)
        assert ei.value.code == 503 and "v:0" in ei.value.read().decode()

        # a tile mid rolling-restart is exempt from poll entirely (the
        # drain path owns its lifecycle, even through the reaped window)
        run._draining.add("v:0")
        run.procs["v:0"]._alive = False
        assert run.poll() is None
        run._draining.discard("v:0")
        assert run.poll() == "v:0"
    finally:
        run.procs = {}
        run.close()


# -- mux: fseq-cursor resume + zero-overhead fault default -------------------


def test_mux_respawn_resumes_from_fseq_cursor():
    spec = _mini_spec("rs")
    jt = topo_mod.create(spec)
    try:
        mc = jt.links["a_b"].mcache
        for i in range(10):
            mc.publish(i)
        fseq = jt.fseq[("v:0", "a_b")]
        cursor = mc.seq_query() - 3
        fseq.update(cursor)

        class _Vt:
            pass

        m0 = Mux(jt, "v:0", _Vt())              # first boot: from seq0
        assert m0.ins[0].seq == mc.seq0()
        assert m0.fault is None                 # no plan -> zero overhead
        m1 = Mux(jt, "v:0", _Vt(), restart_cnt=1)
        assert m1.ins[0].seq == cursor          # respawn: from the cursor
        assert m1.restart_cnt == 1
        # heartbeat_poke stamps the cnc and honors HALT
        hb0 = jt.cnc["v:0"].heartbeat_query()
        m1.heartbeat_poke()
        assert jt.cnc["v:0"].heartbeat_query() >= hb0
        jt.cnc["v:0"].signal(Cnc.SIGNAL_HALT)
        m1._next_poke = 0
        m1.heartbeat_poke()
        assert m1.ctx.halted
        # drop the muxes' dcache views before the workspace unmaps
        m0 = m1 = None  # noqa: F841
        import gc
        gc.collect()
    finally:
        jt.close()
        jt.unlink()
