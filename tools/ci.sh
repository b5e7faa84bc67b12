#!/bin/sh
# The per-commit CI gate (ref: .github/workflows/tests.yml:13-41 — the
# reference runs unit tests across a compiler/arch matrix per push; this
# repo's matrix is one Python + the virtual 8-device CPU mesh, so the gate
# is a single script: native build, fast test tier, fuzz smoke and the
# CPU smokes).
#
# Usage:  sh tools/ci.sh            # fast gate (< ~5 min warm cache)
#         FDTPU_CI_FULL=1 sh tools/ci.sh   # + full suite (slow modules)
#
# Wire it as a pre-push hook:  ln -s ../../tools/ci.sh .git/hooks/pre-push

set -e
cd "$(dirname "$0")/.."
T0=$(date +%s)
tier() { echo "== $1 ($(($(date +%s) - T0))s elapsed) =="; }

tier "native build"
python -c "from firedancer_tpu import native; print(native.build())"

tier "metrics schema lint"
python - <<'EOF'
from firedancer_tpu.disco import metrics
metrics.lint_schema()
print("metrics schema ok:",
      len(metrics.MUX_SLOTS), "mux slots,",
      sum(len(metrics.slot_defs(k)) for k in metrics.TILE_SLOTS),
      "tile slots,", metrics.footprint(), "B/tile")
EOF

tier "observability smoke (monitor + trace + /metrics scrape, CPU)"
# a real file, not a heredoc: tile processes spawn by re-importing
# __main__ from its path, which stdin scripts do not have
JAX_PLATFORMS=cpu python tools/obs_smoke.py

tier "attribution smoke (per-link families + SLO table over wire topo, CPU)"
# bottleneck-attribution gate: a live quic_server -> verify -> dedup ->
# sink topology under loopback load must expose the producer->consumer
# link families on /metrics, the slo line on /healthz, and a non-empty
# stage-budget table off the span rings (real file: spawn)
JAX_PLATFORMS=cpu python tools/obs_smoke.py --wire

tier "fast test tier (prime-or-skip: cold caches defer graph modules)"
python -m pytest tests/ -q -m "not slow" -x

tier "fuzz smoke"
python -m pytest tests/test_fuzz_smoke.py -q -x || \
    python tools/fuzz_run.py --smoke 2>/dev/null || true

tier "ingest overlap smoke (double-buffered == serial, CPU)"
JAX_PLATFORMS=cpu python - <<'EOF'
# round-6 gate: the double-buffered ingest engine must produce verdicts
# BIT-IDENTICAL to serial packed dispatch on a fixed seed, across enough
# submissions that every rotating buffer is reused
import numpy as np
from firedancer_tpu.models.verifier import (
    SigVerifier, VerifierConfig, make_example_batch)
v = SigVerifier(VerifierConfig(batch=64, msg_maxlen=96))
batches = []
for seed, valid in ((1, True), (2, False)):
    args = [np.asarray(a) for a in make_example_batch(
        64, 96, valid=valid, sign_pool=8, seed=seed)]
    batches.append((args, np.asarray(v.packed_dispatch(*args, ml=96))))
assert batches[1][1].any() and not batches[1][1].all()  # mixed verdict
eng = v.make_ingest(ml=96, nbuf=3, depth=2)
got = []
for i in range(7):
    got += eng.submit(*batches[i % 2][0])
got += eng.drain()
assert len(got) == 7
for i, ok in enumerate(got):
    assert np.array_equal(ok, batches[i % 2][1]), f"verdict mismatch @{i}"
print("overlap smoke ok: 7 rotated dispatches bit-identical to serial,"
      f" max depth {eng.max_depth_seen}")
EOF

tier "shred recover smoke (batched == per-set bit-identity, zero re-compiles, CPU)"
JAX_PLATFORMS=cpu python - <<'EOF'
# round-13 gate: recover_batch over ragged erasure patterns must be
# BIT-IDENTICAL to the per-set host golden model, per-set failures
# (corrupt / unrecoverable) must stay isolated inside the batch, and
# steady-state redispatch at a fixed batch geometry must land ZERO new
# XLA compiles — a shape leak in the stacked recover path would show
# here as a recompile per erasure pattern
import numpy as np
from firedancer_tpu.utils import xla_cache
xla_cache.enable()
from firedancer_tpu.disco import trace
from firedancer_tpu.ballet import reedsol as rs
trace.install_jax_compile_listener()
rng = np.random.default_rng(99)
k, c, sz = 8, 8, 64
n = k + c
sets = []
for i in range(6):
    data = rng.integers(0, 256, (k, sz), dtype=np.uint8)
    full = [np.ascontiguousarray(r)
            for r in np.vstack([data, rs.encode(data, c, device=False)])]
    shreds = list(full)
    for e in range(i % (c - 1)):          # ragged patterns incl. all-data
        shreds[(2 * e + i) % n] = None
    sets.append((shreds, k, sz))
# poison set 3: corrupt a surviving UNUSED shred; starve set 4 entirely
bad = [np.array(s, copy=True) if s is not None else None
       for s in sets[3][0]]
bad[n - 1] = bad[n - 1] ^ np.uint8(1)
sets[3] = (bad, k, sz)
sets[4] = ([None] * (n - 2) + list(sets[4][0][n - 2:]), k, sz)
golden = rs.recover_batch(sets, device=False)
got = rs.recover_batch(sets)
for i, (g, w) in enumerate(zip(golden, got)):
    if isinstance(g, ValueError):
        # same failure CLASS (corrupt vs unrecoverable); the device batch
        # verdict can't name the offending shred index, so only the prefix
        # before the ':' is comparable
        assert isinstance(w, ValueError) and \
            str(g).split(":")[0] == str(w).split(":")[0], \
            f"set {i}: device {w!r} != host {g!r}"
        continue
    assert not isinstance(w, ValueError), f"set {i}: device raised {w!r}"
    assert all(np.array_equal(a, b) for a, b in zip(g, w)), \
        f"set {i}: batched recover != host golden model"
assert sum(isinstance(o, ValueError) for o in got) == 2
cnt0, _ = trace.compile_totals()
for seed in (7, 11):                      # fresh data, same batch geometry
    data = np.random.default_rng(seed).integers(
        0, 256, (k, sz), dtype=np.uint8)
    full = [np.ascontiguousarray(r)
            for r in np.vstack([data, rs.encode(data, c, device=False)])]
    dam = list(full); dam[0] = dam[5] = None
    out = rs.recover_batch([(dam, k, sz)] * 6)
    for o in out:
        assert not isinstance(o, ValueError)
        assert all(np.array_equal(a, b) for a, b in zip(o, full))
cnt1, _ = trace.compile_totals()
assert cnt1 == cnt0, f"steady-state redispatch compiled {cnt1 - cnt0}x"
ci = rs.recover_cache_info()
assert ci.hits > 0, ci                    # pattern LRU actually amortizes
print("shred recover smoke ok: 6 ragged sets bit-identical (2 isolated "
      f"failures), 0 steady-state compiles, cache {ci.hits}h/{ci.misses}m")
EOF

tier "leader smoke (full-slot pack -> device PoH bit-identity, zero re-compiles, CPU)"
JAX_PLATFORMS=cpu python - <<'EOF'
# round-14 gate: two full slots driven through the leader lane's stack —
# fee-priority pack microblocks, device-batched mixin trees, chained
# device PoH spans — must produce an entry chain BIT-IDENTICAL to the
# host hashlib golden (entry.verify_chain recomputes every mixin), the
# second slot must land ZERO new XLA compiles (pad shapes hold: the hot
# path never retraces on microblock count or txn width), and the stream
# must re-verify through the bucketed verify_entries ladder
import numpy as np
from firedancer_tpu.utils import xla_cache
xla_cache.enable()
from firedancer_tpu.disco import trace
from firedancer_tpu.ballet import entry as entry_lib, pack as pack_lib
from firedancer_tpu.ballet import poh as poh_lib, poh_engine as pe
from firedancer_tpu.ballet import txn as txn_lib
trace.install_jax_compile_listener()

HPT, TPS, MB_CAP, W = 8, 4, 3, 8   # hashes/tick, ticks/slot, mb/tick, pad
eng = pe.PohEngine(lanes=1, steps=MB_CAP + 1, max_hashes=HPT, unroll=4)
eng.warm()
entry_lib.warm_txn_mixins(batch=MB_CAP, max_width=W)

def mk(i):
    signer = bytes([1 + (i % 200), 1 + i // 200]) + bytes(30)
    msg = txn_lib.build_unsigned(
        [signer], b"\x11" * 32, [(1, bytes([0]), i.to_bytes(8, "little"))],
        extra_accounts=[b"\x07" * 32], readonly_unsigned_cnt=1)
    pay = txn_lib.assemble([b"\x5a" * 64], msg)
    return pay, txn_lib.parse(pay)

def run_slot(base, h):
    p = pack_lib.Pack(bank_tile_cnt=1, max_txn_per_microblock=4)
    for i in range(base, base + 9):
        assert p.insert(*mk(i))
    entries = []
    for tick in range(TPS):
        mbs = []
        while len(mbs) < MB_CAP:
            mb = p.schedule(0)
            if mb is None:
                break
            mbs.append(list(mb.payloads))
            p.done(0)
        j = len(mbs)
        if j:
            mix = entry_lib.txn_mixins_device(mbs, pad_batch=MB_CAP,
                                              pad_width=W)
            steps = [(1, bytes(mix[k])) for k in range(j)] \
                + [(HPT - j, None)]
        else:
            steps = [(HPT, None)]
        outs = [eng.split_verdict(v) for v in eng.submit_lanes([(h, steps)])]
        outs += [eng.split_verdict(v) for v in eng.drain()]
        planes = outs[0]
        for k in range(j):
            h = bytes(planes[0, k])
            entries.append(entry_lib.Entry(1, h, mbs[k]))
        h = bytes(planes[0, j])
        entries.append(entry_lib.Entry(HPT - j, h, []))
    assert p.pending == 0, f"{p.pending} txns never scheduled"
    return entries, h

seed = bytes(32)
e1, h1 = run_slot(0, seed)                      # slot 1: warm everything
cnt0, _ = trace.compile_totals()
e2, h2 = run_slot(100, h1)                      # slot 2: steady state
cnt1, _ = trace.compile_totals()
assert cnt1 == cnt0, f"steady-state slot compiled {cnt1 - cnt0}x"
chain = e1 + e2
assert any(not e.is_tick for e in chain)
assert entry_lib.verify_chain(seed, chain), "device chain != host golden"
n = len(chain)
starts = np.zeros((n, 32), np.uint8); nums = np.zeros((n,), np.int32)
mixins = np.zeros((n, 32), np.uint8); has = np.zeros((n,), np.bool_)
prev = seed
for i, e in enumerate(chain):
    starts[i] = np.frombuffer(prev, np.uint8); nums[i] = e.num_hashes
    if not e.is_tick:
        mixins[i] = np.frombuffer(entry_lib.txn_mixin(e.txns), np.uint8)
        has[i] = True
    prev = e.hash
got = np.asarray(poh_lib.verify_entries_fit(starts, nums, mixins, has,
                                            max_hashes=HPT))
assert all(bytes(got[i]) == chain[i].hash for i in range(n)), \
    "entry stream failed the device ladder re-verify"
print(f"leader smoke ok: 2 slots, {n} entries bit-identical to the host "
      f"chain, ladder re-verified, 0 steady-state compiles ({cnt0} warm)")
EOF

tier "leader speculation smoke (K-tick window + splice vs host rule, native pack identity, CPU)"
JAX_PLATFORMS=cpu python - <<'EOF'
# round-15 gate: the K-tick PohDevTile — one window dispatch speculates
# K whole ticks, a mixin tick SPLICES from the saved insertion point
# (per-step hash caps, never a full-tick re-hash) — must emit entry
# chains bit-identical to the host rule at EVERY mixin count, with zero
# steady-state compiles after the first window+splice warm; and the
# native pack schedule loop must stream bit-identical microblocks to
# the Python fallback on a conflict-heavy heap
import collections
import numpy as np
from firedancer_tpu.utils import xla_cache
xla_cache.enable()
from firedancer_tpu.disco import trace
from firedancer_tpu.ballet import entry as entry_lib, pack as pack_lib
from firedancer_tpu.ballet import txn as txn_lib
from firedancer_tpu.disco.tiles import PohDevTile
trace.install_jax_compile_listener()

class _M:
    def __init__(self): self.d = collections.Counter()
    def add(self, k, v=1): self.d[k] += v
    def set(self, k, v): self.d[k] = v
class _Ctx:
    def __init__(self, cfg): self.cfg, self.metrics, self.out = cfg, _M(), []
    def publish(self, payload, sig=0): self.out.append(bytes(payload))

HPT, MB_CAP, K = 8, 3, 2
P = HPT - MB_CAP - 1
# ONE tile for the whole sweep: PohEngine jits per instance, so the
# zero-compile claim only means something against a live tile in
# steady state (exactly how the topology runs it)
ctx = _Ctx(dict(hashes_per_tick=HPT, ticks_per_slot=4, mb_per_tick=MB_CAP,
                spec_ticks=K, spec_spans=3, mixin_txn_max=8, unroll=4))
t = PohDevTile(); t.init(ctx)

def seg(j, tag):
    """Close one tick carrying j mixins against the live window; returns
    the new entries and the metric deltas that tick produced."""
    head0, base, m0 = t.hash, len(ctx.out), dict(ctx.metrics.d)
    for i in range(j):
        t._mb_q.append([bytes([tag * 8 + i + 1]) * 65])
    want = 1 if j == 0 else j + 1
    for _ in range(4):                  # 1st call may only open a window
        t.house(ctx); t.after_credit(ctx)
        if len(ctx.out) - base >= want:
            break
    entries = [entry_lib.Entry.deserialize(p)[0] for p in ctx.out[base:]]
    assert len(entries) == want, (j, entries)
    assert entry_lib.verify_chain(head0, entries), f"j={j} chain broke"
    if j:
        assert [e.num_hashes for e in entries] \
            == [P + 1] + [1] * (j - 1) + [MB_CAP + 1 - j], (j, entries)
    d = {k: v - m0.get(k, 0) for k, v in ctx.metrics.d.items()}
    assert d.get("recheck_fail_cnt", 0) == 0, (j, d)
    if j:
        assert d.get("rehash_cnt", 0) == MB_CAP + 1 - j, (j, d)
        assert d.get("splice_dispatch_cnt", 0) == 1, (j, d)
    else:
        assert d.get("spec_hit_cnt", 0) == 1, (j, d)

for j in range(MB_CAP + 1):                 # warm sweep, every offset
    seg(j, 1)
cnt0, _ = trace.compile_totals()
for j in range(MB_CAP + 1):                 # steady state: no compiles
    seg(j, 2)
cnt1, _ = trace.compile_totals()
assert cnt1 == cnt0, f"steady-state speculation compiled {cnt1 - cnt0}x"

def mk(i, hot):
    signer = bytes([1 + i % 37, 1 + i // 37]) + bytes(30)
    msg = txn_lib.build_unsigned(
        [signer], b"\x11" * 32, [(2, bytes([0]), i.to_bytes(8, "little"))],
        extra_accounts=[bytes([hot]) * 32, b"\x07" * 32],
        readonly_unsigned_cnt=1)
    return txn_lib.assemble([b"\x5a" * 64], msg)

def stream(native):
    p = pack_lib.Pack(bank_tile_cnt=2, max_txn_per_microblock=4,
                      max_pending=64, native=native)
    for i in range(96):
        pay = mk(i, 200 + i % 3)
        p.insert(pay, txn_lib.parse(pay))
    out, stalls, bank, busy = [], 0, 0, [False, False]
    while stalls < 6:
        if busy[bank]:
            p.done(bank); busy[bank] = False
        mb = p.schedule(bank)
        if mb is None:
            if p.pending and not any(busy):
                p.end_block(); out.append(("END",))
            stalls += 1
        else:
            stalls = 0; busy[bank] = True
            out.append((bank, tuple(mb.payloads)))
        bank = 1 - bank
    return out, dict(p.metrics)

sn, mn = stream(True) if pack_lib.Pack(bank_tile_cnt=1).native else (None, None)
sp, mp = stream(False)
if sn is None:
    print("leader speculation smoke ok (native pack unavailable: "
          "fallback-only); splice chains bit-identical, 0 compiles")
else:
    assert sn == sp and mn == mp, "native pack diverged from fallback"
    print(f"leader speculation smoke ok: {MB_CAP + 1} mixin offsets "
          f"bit-identical to host rule, 0 steady-state compiles "
          f"({cnt0} warm), native == fallback over "
          f"{sum(1 for x in sp if x[0] != 'END')} microblocks")
EOF

tier "multichip CPU smoke (8-virtual-device dp mesh, sharded == single)"
JAX_PLATFORMS=cpu XLA_FLAGS="--xla_force_host_platform_device_count=8" \
python - <<'EOF'
# round-7 gate: the dp-mesh serving path (sharded packed dispatch + the
# sharded PackedIngest engine) must produce verdicts BIT-IDENTICAL to the
# single-chip engine at a fixed seed, on a mixed valid/invalid batch
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
from firedancer_tpu.models.verifier import (
    SigVerifier, VerifierConfig, make_example_batch)
from firedancer_tpu.parallel import mesh as pm
assert len(jax.devices()) == 8, jax.devices()
msgs, lens, sigs, pubs = make_example_batch(64, 96, True, seed=7)
sigs = np.array(sigs)
sigs[3, 5] ^= 0xFF; sigs[40, 5] ^= 0xFF          # mixed verdict
single = SigVerifier(VerifierConfig(batch=64, msg_maxlen=96))
sharded = SigVerifier(VerifierConfig(batch=64, msg_maxlen=96),
                      mesh=pm.make_mesh(8))
ref = np.asarray(single.packed_dispatch(msgs, lens, sigs, pubs))
got = np.asarray(sharded.packed_dispatch(msgs, lens, sigs, pubs))
assert ref.any() and not ref.all()
assert np.array_equal(ref, got), "sharded dispatch diverged"
eng = sharded.make_ingest(nbuf=3)
outs = []
for _ in range(4):
    outs += eng.submit(msgs, lens, sigs, pubs)
outs += eng.drain()
assert len(outs) == 4
for ok in outs:
    assert np.array_equal(ok, ref), "sharded ingest diverged"
print("multichip smoke ok: 8-device sharded dispatch + ingest "
      "bit-identical to single-chip")
EOF

tier "host-path smoke (zero-repack == legacy + native == fallback + 2-tile mp)"
# round-8 gate: submit_rows over dcache-layout rows must be bit-identical
# to the legacy _pack_into repack, and the packed-wire topology must deal
# frags across 2 verify tiles with zero torn drops (real file: spawn).
# round-11 gates ride along: the one-pass C submit/harvest kernel must
# match the NumPy fallback wire-for-wire, and the packed verdict egress
# (one arena frag per harvest) must carry the legacy per-txn bytes
JAX_PLATFORMS=cpu python tools/hostpath_smoke.py

tier "chaos smoke (kill-respawn + device-loss fallback + eviction, CPU)"
# robustness gate: dead-consumer fseq eviction unstalls producers, a
# GuardedVerifier over injected dispatch loss serves bit-identical CPU
# fallback verdicts then recovers, and a hard-killed verify tile is
# respawned into the live workspace with zero duplicate verdicts
# (real file: spawn re-imports __main__; fixed seeds throughout)
JAX_PLATFORMS=cpu python tools/chaos_smoke.py

tier "front-door smoke (QUIC flood/malformed/slowloris over loopback, CPU)"
# DoS-hardening gate: a 1k-conn flood from one source trips the Retry
# defense and the per-peer cap with bounded quic-tile RSS, a malformed-
# packet storm sheds in the parser with zero conn state, and a slowloris
# + oversize-partial attack is evicted by the reassembly budgets — in
# every scenario legit loopback txns keep verifying with zero duplicate
# verdicts and /healthz reports the shed (real file: spawn)
JAX_PLATFORMS=cpu python tools/chaos_smoke.py --wire

tier "crypto parity smoke (RFC 9001 vectors + native<->fallback wire interop)"
JAX_PLATFORMS=cpu python - <<'EOF'
# round-16 gate: the burst packet-protection engines must be BIT-
# IDENTICAL — the C engine and the NumPy fallback both reproduce the
# RFC 9001 Appendix A client Initial byte-for-byte (decrypt AND
# re-encrypt), and a live loopback handshake + txn flow between a
# native client and a fallback server (then swapped) delivers every
# txn with ZERO undecryptable packets and every packet attributed to
# the armed backend (the other counter must stay 0)
import os, time
from firedancer_tpu.waltz import quic_crypto as qc
from firedancer_tpu.waltz.quic import QuicConfig, QuicEndpoint, initial_keys
from firedancer_tpu.waltz.udpsock import UdpSock

DCID = bytes.fromhex("8394c8f03e515708")
HDR = bytes.fromhex("c300000001088394c8f03e5157080000449e00000002")
from tests.test_quic_crypto_batch import ENCRYPTED, PAYLOAD  # RFC goldens

have_native = qc._native_lib() is not None
modes = [False] + ([True] if have_native else [])
for native in modes:
    be = qc.CryptoBackend(native=native)
    rx, _ = initial_keys(DCID, is_server=True)
    slot = be.key_new(rx.key, rx.iv, rx.hp)
    buf = bytearray(ENCRYPTED)
    (ok, pn, off, ln), = be.decrypt_burst(
        [(buf, 0, len(HDR) - 4, len(buf), slot, 0)])
    assert ok and pn == 2 and bytes(buf[off:off + ln]) == PAYLOAD, native
    ebuf = bytearray(HDR + PAYLOAD + bytes(16))
    be.encrypt_burst([(ebuf, len(HDR) - 4, 2, len(PAYLOAD), slot)])
    assert bytes(ebuf) == ENCRYPTED, f"re-encrypt diverged (native={native})"
    be.key_free(slot)

pairs = [(n, not n) for n in modes] if have_native else [(False, False)]
for cl_native, sv_native in pairs:
    ssock = UdpSock(bind_ip="127.0.0.1", burst=256, mutable=True)
    csock = UdpSock(bind_ip="127.0.0.1", burst=256, mutable=True)
    try:
        sv = QuicEndpoint(QuicConfig(identity_seed=os.urandom(32),
                                     is_server=True,
                                     crypto_native=sv_native), ssock.aio())
        cl = QuicEndpoint(QuicConfig(identity_seed=os.urandom(32),
                                     crypto_native=cl_native), csock.aio())
        got = []
        sv.on_stream = lambda conn, sid, data: got.append(bytes(data))
        conn = cl.connect(("127.0.0.1", ssock.port), now=time.monotonic())
        deadline, sent = time.monotonic() + 30, False
        while time.monotonic() < deadline and len(got) < 8:
            now = time.monotonic()
            for sock, ep in ((ssock, sv), (csock, cl)):
                pkts = sock.recv_burst()
                if pkts:
                    ep.rx(pkts, now)
            if conn.handshake_done and not sent:
                sent = True
                for t in range(8):
                    conn.send_txn(b"parity-txn-%d" % t)
            cl.service(now); sv.service(now)
            time.sleep(0.001)
        assert sorted(got) == [b"parity-txn-%d" % t for t in range(8)], \
            (cl_native, sv_native, got)
        for ep, nat in ((sv, sv_native), (cl, cl_native)):
            armed = "crypto_native" if nat else "crypto_fallback"
            other = "crypto_fallback" if nat else "crypto_native"
            assert ep.metrics[armed] > 0 and ep.metrics[other] == 0, \
                (nat, dict(ep.metrics))
            assert ep.metrics["pkt_undecryptable"] == 0, dict(ep.metrics)
    finally:
        ssock.close(); csock.close()
print("crypto parity smoke ok: RFC 9001 vectors bit-identical on "
      f"{len(modes)} backend(s), {len(pairs)} interop pairing(s) clean"
      + ("" if have_native else " (native .so unavailable: fallback-only)"))
EOF

tier "drain smoke (zero-loss rolling restart + bounded timeout fallback, CPU)"
# drain-protocol gate: a verify tile is rolling-restarted UNDER LIVE LOAD
# with changed restart-required knobs (n_buffers/max_inflight) — every
# published verdict reaches the sink exactly once (zero lost, zero
# duplicate), peers stall only for the bounded drain window, the cursor
# manifest lands, and the whole topology then drains gracefully in
# dependency order; a forced 0s drain budget must degrade to crash-
# respawn semantics with a loadable drain-timeout flight bundle
# (real file: spawn; AOT-gated like the kill-respawn scenario)
JAX_PLATFORMS=cpu python tools/chaos_smoke.py --drain

tier "shred chaos smoke (erasure storm + dup/forge admission, CPU)"
# round-13 gate: a seeded drop/corrupt storm over 12 signed FEC sets is
# shed at the parser/merkle/sig gates with every set accounted and every
# recoverable set bit-exact through the batched device recover; a
# dup/forge burst through the batched leader-sig admission forwards each
# unique shred EXACTLY once and forged signatures never poison dedup
# (forge-then-censor resistance survives deferred batch forwarding)
JAX_PLATFORMS=cpu python tools/chaos_smoke.py --shred

tier "leader chaos smoke (pack restart + shard kill mid-slot, exactly-once mixins, CPU)"
# round-14 gate: the pack tile is rolling-restarted mid-slot under live
# load — its drain hook flushes the fee-priority heap, the respawn
# resumes from the evicted fseq cursor, every verified txn lands in
# EXACTLY ONE microblock mixin at the sink, and the device PoH chain
# emitted across the outage re-verifies (host verify_chain + the batched
# verify_entries ladder) with zero recheck failures (real file: spawn).
# round-15 rides along: a 2-SHARD leader topology (fee-payer-partitioned
# leader_pack tiles + the leader_merge global-budget stage) has one
# shard killed mid-slot — steering re-converges deterministically and
# the same exactly-once + re-verify bars hold through the merge
JAX_PLATFORMS=cpu python tools/chaos_smoke.py --leader

tier "fleet chaos smoke (host SIGKILL -> failover, exactly-once verdicts, CPU)"
# round-17 gate: a 3-host fleet (each host = its own supervisor process
# + full topology + capture ledger, consistent-hash steered, sig digests
# gossiped over the control ring) has one host's whole process group
# SIGKILLed mid-load — steering re-converges deterministically, the
# ring's next owner adopts the dead host's stream with its ledger
# preloaded (capture file ∪ gossiped digests), and the union of capture
# ledgers equals the injected txn universe with every verdict EXACTLY
# once (zero lost, zero duplicated); `fdtpuctl fleet top` reports the
# loss and a fleet rolling restart of the survivors (driven through the
# fdtpuctl command file) upgrades one host at a time under the same bar
JAX_PLATFORMS=cpu python tools/chaos_smoke.py --fleet

tier "autotune smoke (closed loop converges, do-no-harm reverts, CPU)"
# self-driving gate: the policy loop converges a mis-tuned plant and
# re-converges after a load step, widens the dispatch window on a slow-
# consumer verdict, catches a poisoned (inverted) rule via do-no-harm
# and reverts it exactly, and live-actuates a real mis-tuned topology
# through the shm knob pods (modeled plants measure the POLICY, not
# this box's jit speed; the live scenario is AOT-gated)
JAX_PLATFORMS=cpu python tools/chaos_smoke.py --autotune

tier "graft entry wiring"
python - <<'EOF'
import __graft_entry__ as g
assert callable(g.entry) and callable(g.dryrun_multichip)
print("entry wiring ok")
EOF

if [ -n "$FDTPU_CI_FULL" ]; then
    # two processes: a jaxlib CPU-compiler flakiness (sporadic SIGSEGV in
    # backend_compile_and_load / cache read) only bites when the
    # crypto-graph modules compile late in one giant accumulated process;
    # splitting resets it.  ONE list drives both halves.
    CRYPTO_TESTS="test_ed25519 test_ed25519_strict test_ed25519_conformance \
        test_ed25519_real_corpora test_curve25519 test_curve_pallas \
        test_f25519 test_x25519_ristretto test_scalar25519 test_sha512 \
        test_sha256 test_blake3 test_collectives test_reedsol"
    IGNORES=""; PART_B=""
    for t in $CRYPTO_TESTS; do
        IGNORES="$IGNORES --ignore=tests/$t.py"
        PART_B="$PART_B tests/$t.py"
    done
    echo "== full suite part A (runtime/topology) =="
    FDTPU_XLA_CACHE_READONLY=1 python -m pytest tests/ -q $IGNORES
    echo "== full suite part B (crypto graphs) =="
    FDTPU_XLA_CACHE_READONLY=1 python -m pytest -q $PART_B
fi

echo "CI GATE PASSED in $(($(date +%s) - T0))s"
