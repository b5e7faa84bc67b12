"""The minimum end-to-end verify slice (SURVEY.md §7.4): txn bytes in,
per-txn verdicts out.

Mirrors the verify tile's processing contract
(src/app/fdctl/run/tiles/fd_verify.c after_frag -> fd_txn_verify,
fd_verify.h:43-88): parse -> tcache pre-dedup on the first 64 sig bits ->
batched ed25519 verify -> per-txn accept iff every signature passes.

The TPU twist vs the reference's synchronous in-tile loop: signatures from
many txns are coalesced into fixed-shape device batches (wiredancer's
async-offload insertion point, SURVEY.md §3.2), so per-batch latency is
device round-trip + coalescing window, amortized over thousands of lanes.

Message-length buckets: XLA graphs are fixed-shape, so the pipeline keeps
several compiled (batch, msg_maxlen) buckets and routes each txn to the
smallest bucket that fits its message — small transfers fill the wide
fast bucket while full-MTU txns (wire MTU 1232, ref
src/ballet/txn/fd_txn.h:92-103) go to a narrower full-width bucket instead
of being dropped.  This is the same compile-time-batch-specialization game
the reference plays with SIMD widths (fd_sha512.h:266-361).
"""

from collections import deque
import ctypes
from dataclasses import dataclass, field
import os
import time

import numpy as np

from .. import native as native_mod
from ..ballet import txn as txn_lib
from ..tango.ring import (PACKED_LEN_MASK, PACKED_SIG_IDX_SHIFT,
                          PACKED_SIG_MORE_SHIFT)
from ..tango.tcache import NativeTCache, TCache
from ..utils import log
from ..utils.hist import Histf
from . import trace as trace_mod


def _is_ready(dev) -> bool:
    """Non-blocking completion poll on a dispatched device array (jax
    arrays grew .is_ready() long ago; anything without it is host data
    and trivially ready)."""
    fn = getattr(dev, "is_ready", None)
    return True if fn is None else bool(fn())

# default bucket ladder: (lanes, msg_maxlen); covers through the wire MTU
DEFAULT_BUCKETS = ((2048, 256), (256, 768), (64, 1232))

# priority admission (round 9): ingest links thread a per-frag
# latency-class bit through the tango frag meta `sig` field — the same
# meta-field threading round 8 used for packed row counts in `meta.sz`.
# Producers that participate in priority tagging keep their app sigs
# below bit 63 (the source tile draws tags in [1, 2^63)); untagged wire
# ingest (quic) masks the bit off so random signature bytes can never
# alias a txn into the low-latency lane.
LAT_PRIO_BIT = 1 << 63

# default low-latency lane shape ladder (lanes per pre-warmed shape)
DEFAULT_LAT_SHAPES = (16, 64, 256)


class _GuardedVerdict:
    """Verdict future with a harvest-side deadline (GuardedVerifier's
    async half).  Implements exactly the surface the pipeline touches on
    a dispatched verdict: is_ready() polls, np.asarray materializes,
    copy_to_host_async passes through.  A future that is still not ready
    past the deadline — or whose materialization raises — counts as a
    device failure and the verdict is recomputed on the host from the
    still-pinned inputs (the pipeline pins packed blobs/row views until
    _finish, so the bytes are guaranteed live here)."""

    __slots__ = ("_g", "_dev", "_host_call", "_t0")

    def __init__(self, g, dev, host_call, t0):
        self._g = g
        self._dev = dev
        self._host_call = host_call
        self._t0 = t0

    def is_ready(self) -> bool:
        if _is_ready(self._dev):
            return True
        if self._g.deadline_s <= 0:     # deadline disabled: poll only
            return False
        # a hung dispatch becomes "ready" at the deadline so harvest()
        # reaches __array__ and the host fallback fires
        return self._g._clock() - self._t0 > self._g.deadline_s

    def copy_to_host_async(self):
        fn = getattr(self._dev, "copy_to_host_async", None)
        if fn is not None:
            fn()

    def __array__(self, dtype=None, copy=None):
        g = self._g
        if (_is_ready(self._dev) or g.deadline_s <= 0
                or g._clock() - self._t0 <= g.deadline_s):
            try:
                ok = np.asarray(self._dev)
                g._consec = 0
                return ok if dtype is None else ok.astype(dtype)
            except Exception as e:  # noqa: BLE001 — any materialization
                log.warning("device verdict fetch failed: %s", str(e))
        else:
            log.warning("device verdict hung past %.1fs deadline",
                        g.deadline_s)
        ok = g._device_failed(self._host_call)
        return ok if dtype is None else ok.astype(dtype)


class GuardedVerifier:
    """Self-healing wrapper around a device verifier (the graceful-
    degradation half of the supervision tentpole).

    Wraps the two dispatch surfaces the pipeline uses — __call__ over
    (msgs, lens, sigs, pubs) and, when the wrapped fn has one,
    dispatch_blob over packed rows — preserving the duck-typing
    VerifyPipeline autodetects on (dispatch_blob presence, .mode,
    .n_shards pass through).  Behavior:

      * every device dispatch gets `retries` bounded retries; a dispatch
        that still raises falls back to the host ed25519 backend for THAT
        batch (verdicts keep flowing, `device_fail_cnt` counts)
      * a dispatched verdict that never materializes within `deadline_s`
        is also a failure (caught at harvest via _GuardedVerdict) and is
        recomputed on the host from the still-pinned inputs; set
        deadline_s <= 0 to disable the hang watchdog (benchmarks on a
        contended 1-core CPU host legitimately outlast any sane deadline)
      * `fail_threshold` CONSECUTIVE failures flip `degraded` on: all
        dispatches go straight to the host backend, and every `reprobe_s`
        seconds one live batch probes the device — a probe that
        materializes in time clears degraded and restores the device path

    Host verdicts are bit-identical to device verdicts: both paths
    implement the same acceptance rules, conformance-tested against
    ops.ed25519.verify_one_host."""

    def __init__(self, fn, fail_threshold: int = 3, retries: int = 1,
                 deadline_s: float = 30.0, reprobe_s: float = 5.0,
                 fault=None, clock=time.monotonic,
                 host_blob=None, host_arrays=None):
        self.fn = fn
        self.fail_threshold = max(1, int(fail_threshold))
        self.retries = max(0, int(retries))
        self.deadline_s = float(deadline_s)
        self.reprobe_s = float(reprobe_s)
        self.fault = fault          # FaultInjector or None
        self._clock = clock
        self._host_blob = host_blob
        self._host_arrays = host_arrays
        self.degraded = False
        self.device_fail_cnt = 0
        self.fallback_lanes = 0
        self.reprobe_cnt = 0
        self._consec = 0
        self._next_probe = 0.0
        self._fb_t0 = None          # fallback-rate window origin
        self._fb_lanes0 = 0
        # expose dispatch_blob ONLY if the wrapped fn has it — pipeline
        # packed autodetect is hasattr-based, so a phantom method here
        # would flip a 4-array verifier into packed mode
        if hasattr(fn, "dispatch_blob"):
            self.dispatch_blob = self._guarded_dispatch_blob

    def __getattr__(self, name):
        # .mode / .n_shards / anything else the pipeline introspects
        return getattr(self.__dict__["fn"], name)

    # -- dispatch surfaces -------------------------------------------------
    def __call__(self, msgs, lens, sigs, pubs):
        return self._dispatch(
            lambda: self.fn(msgs, lens, sigs, pubs),
            lambda: self._host_4(msgs, lens, sigs, pubs))

    def _guarded_dispatch_blob(self, blob, maxlen=None):
        return self._dispatch(
            lambda: self.fn.dispatch_blob(blob, maxlen=maxlen),
            lambda: self._host_b(blob, maxlen))

    # -- host backend ------------------------------------------------------
    # The default backends follow the wrapped verifier's mode: an
    # antipa-mode device graph degrades to the antipa host verify
    # (torsion laxity included), so fallback verdicts stay bit-identical
    # to what the device would have produced.  Injected host_blob /
    # host_arrays (tests, custom backends) are used as given.
    def _fn_mode(self) -> str:
        return getattr(self.__dict__["fn"], "mode", "strict")

    def _host_4(self, msgs, lens, sigs, pubs):
        if self._host_arrays is None:
            from functools import partial

            from ..models.verifier import host_verify_arrays
            self._host_arrays = partial(host_verify_arrays,
                                        mode=self._fn_mode())
        return self._host_arrays(msgs, lens, sigs, pubs)

    def _host_b(self, blob, maxlen):
        if self._host_blob is None:
            from functools import partial

            from ..models.verifier import host_verify_blob
            self._host_blob = partial(host_verify_blob,
                                      mode=self._fn_mode())
        return self._host_blob(blob, maxlen=maxlen)

    def _host(self, host_call):
        ok = np.asarray(host_call()).astype(bool)
        self.fallback_lanes += len(ok)
        return ok

    def fallback_vps(self) -> int:
        """CPU-fallback verify rate (lanes/s) over the current degraded
        window; 0 when healthy."""
        if self._fb_t0 is None:
            return 0
        dt = self._clock() - self._fb_t0
        if dt <= 0:
            return 0
        return int((self.fallback_lanes - self._fb_lanes0) / dt)

    # -- state machine -----------------------------------------------------
    def _enter_degraded(self):
        self.degraded = True
        self._next_probe = self._clock() + self.reprobe_s
        self._fb_t0 = self._clock()
        self._fb_lanes0 = self.fallback_lanes
        log.warning("verify device path degraded after %d consecutive "
                    "failures: serving off the CPU ed25519 fallback "
                    "(reprobe every %.1fs)", self._consec, self.reprobe_s)

    def _recover(self):
        self.degraded = False
        self._consec = 0
        self._fb_t0 = None
        log.warning("verify device path recovered; leaving degraded mode")

    def _device_failed(self, host_call):
        """Shared failure accounting (dispatch raise or harvest timeout)
        + host fallback for the affected batch."""
        self.device_fail_cnt += 1
        self._consec += 1
        if self.degraded:
            self._next_probe = self._clock() + self.reprobe_s
        elif self._consec >= self.fail_threshold:
            self._enter_degraded()
        return self._host(host_call)

    def _try_materialize(self, dev):
        """Degraded-mode probe: block (bounded by deadline_s) on a live
        dispatch; returns the verdict array or None on hang/raise."""
        deadline = self._clock() + self.deadline_s
        while not _is_ready(dev):
            if self._clock() > deadline:
                return None
            time.sleep(0.001)
        try:
            return np.asarray(dev)
        except Exception as e:  # noqa: BLE001
            log.warning("device probe materialization failed: %s", e)
            return None

    def _dispatch(self, dev_call, host_call):
        now = self._clock()
        if self.degraded and now < self._next_probe:
            return self._host(host_call)
        probing = self.degraded
        if probing:
            self.reprobe_cnt += 1
        last = None
        for _ in range(self.retries + 1):
            try:
                if self.fault is not None:
                    self.fault.dispatch()
                dev = dev_call()
            except Exception as e:  # noqa: BLE001 — a dispatch-time raise
                last = str(e)       # of ANY kind means the device path is
                continue            # not producing verdicts right now
                # (stringified: keeping the exception would pin the whole
                # frag-loop stack through its traceback if a log handler
                # retains the record)
            if probing:
                # degraded-mode probe: this live batch decides recovery,
                # so (unlike the healthy path) we block on it
                ok = self._try_materialize(dev)
                if ok is None:
                    break
                self._recover()
                return ok.astype(bool)
            # NOTE: _consec is NOT reset here — only a verdict that
            # actually materializes clears it (_GuardedVerdict.__array__);
            # a device that accepts dispatches but never completes them
            # must still cross the threshold
            return _GuardedVerdict(self, dev, host_call, now)
        if last is not None:
            log.warning("device dispatch failed (consec=%d): %s",
                        self._consec + 1, last)
        return self._device_failed(host_call)


@dataclass
class VerifyMetrics:
    """Counter block, the shape of the reference's per-tile metrics region
    (src/disco/metrics/metrics.xml verify tile)."""

    txns_in: int = 0
    parse_fail: int = 0
    dedup_drop: int = 0
    too_long_drop: int = 0
    sig_overflow_drop: int = 0
    verify_fail: int = 0
    verify_pass: int = 0
    batches: int = 0
    # zero-copy packed-wire path: frags whose seqlock re-check failed
    # AFTER the device dispatch (producer lapped the dcache mid-upload);
    # the whole frag is dropped rather than risking torn verdicts
    torn_drop: int = 0
    # rows riding those torn frags.  Counted SEPARATELY from txns_in so
    # pass/fail rates derived from txns_in (fdtpuctl top) exclude rows
    # that never reached harvest — a torn frag bumps neither txns_in nor
    # dedup_drop
    torn_txns: int = 0
    # TPU hooks (fdtrace): first-dispatch-per-shape events (the XLA
    # trace+compile cost a cold (batch, maxlen) bucket pays) and lane
    # occupancy (filled vs dispatched — padding waste per age-flush)
    compile_cnt: int = 0
    compile_ns: int = 0
    lanes_filled: int = 0
    lanes_dispatched: int = 0
    last_fill_pct: int = 0
    # dual-lane dispatch (round 9): low-latency lane accounting.
    # lat_spill counts lat-class txns shed to the throughput lane
    # (inflight budget / queue age / capacity) — shed txns are still
    # verified, never dropped, so spill is a latency signal not a loss.
    lat_txns: int = 0
    lat_spill: int = 0
    lat_batches: int = 0
    lat_deadline_closes: int = 0
    # host wall ns blocked on the device in _finish: the is_ready poll
    # loop plus the verdict fetch (np.asarray)
    verdict_wait_ns: int = 0
    # packed rows (submit_packed_rows): message bytes of the rows
    # dispatched, and txns of two or more signature rows taken in
    msg_bytes: int = 0
    multisig_txns: int = 0
    # packed frames copied into a bulk-lane call that already held
    # another frame's rows (the device queue was full when they came)
    coalesced_frames: int = 0
    batch_ns: Histf = field(default_factory=lambda: Histf(1_000, 60_000_000_000))
    # batch-latency decomposition (round 4): coalesce = first submit ->
    # dispatch (the batching window's cost), batch_ns = dispatch ->
    # verdict harvested (device + queue + device->host copy)
    coalesce_ns: Histf = field(
        default_factory=lambda: Histf(1_000, 60_000_000_000))
    # end-to-end arrival->verdict per lane (round 9): e2e_ns samples the
    # throughput lane (oldest txn of each bucket batch), lat_e2e_ns the
    # low-latency lane — the per-lane p99s the dual-lane bench reports,
    # measured with the SAME ruler on both sides
    e2e_ns: Histf = field(
        default_factory=lambda: Histf(1_000, 60_000_000_000))
    lat_e2e_ns: Histf = field(
        default_factory=lambda: Histf(1_000, 60_000_000_000))

    def snapshot(self) -> dict:
        d = {k: getattr(self, k) for k in (
            "txns_in", "parse_fail", "dedup_drop", "too_long_drop",
            "sig_overflow_drop", "verify_fail", "verify_pass", "batches",
            "torn_drop", "torn_txns", "compile_cnt", "compile_ns",
            "lanes_filled",
            "lanes_dispatched", "last_fill_pct", "lat_txns", "lat_spill",
            "lat_batches", "lat_deadline_closes", "verdict_wait_ns",
            "msg_bytes", "multisig_txns", "coalesced_frames")}
        d["batch_ns_p50"] = self.batch_ns.percentile(0.50)
        d["batch_ns_p99"] = self.batch_ns.percentile(0.99)
        d["coalesce_ns_p50"] = self.coalesce_ns.percentile(0.50)
        d["coalesce_ns_p99"] = self.coalesce_ns.percentile(0.99)
        d["e2e_ns_p50"] = self.e2e_ns.percentile(0.50)
        d["e2e_ns_p99"] = self.e2e_ns.percentile(0.99)
        d["lat_e2e_ns_p50"] = self.lat_e2e_ns.percentile(0.50)
        d["lat_e2e_ns_p99"] = self.lat_e2e_ns.percentile(0.99)
        return d


def _len_words(rows, n: int, ml: int) -> np.ndarray:
    """The len-le32 word of each of the first n packed rows: the message
    length and the row's signature marker (tango/ring.py)."""
    return np.ascontiguousarray(rows[:n, ml + 96:ml + 100]).view(
        "<u4").ravel()


@dataclass
class _Pending:
    payload: bytes
    parsed: txn_lib.Txn
    lanes: list[int]  # indices into the bucket's open batch
    tag: int  # dedup tag (low 64 bits of first sig), computed once in submit()


@dataclass
class _BurstPending:
    """A whole accepted burst as one pending record (submit_burst): per-txn
    bookkeeping stays in numpy so harvest is vectorized too.  Lanes of the
    burst's txns are CONTIGUOUS in the bucket (the native parser allocates
    sequentially).  Payload bytes live as ONE copied region (the rx
    scratch buffer is reused next poll) with per-txn (start, len) into it;
    per-txn bytes objects are materialized only for PASSING txns at
    harvest."""

    buf: bytes              # copy of this round's payload region
    start: object           # (k,) int64 payload start per accepted txn
    plen: object            # (k,) int32 payload length per accepted txn
    lane0: object           # (k,) int32 first lane per txn
    nsig: object            # (k,) int32 sig lanes per txn
    tag: object             # (k,) uint64 dedup tags


@dataclass
class _RowsPending:
    """The packed rows of one device call (submit_packed_rows).  On the
    bulk lane they are the call buffer the call's frames were copied into
    (release_cb None: each frame's credit went back at its copy).  On the
    low-latency lane they are a live view over the shm dcache, pinned by a
    held consumer credit until the verdict materializes, and release_cb
    returns that credit once the frag retires.  Either way the rows stay
    put until harvest rebuilds the passing wires from them."""

    rows: object            # (batch, ml+100) uint8 call buffer or shm view
    tag: object             # (n,) uint64 dedup tag of each row's txn: its
                            # first row's row[ml:ml+8]
    dup: object             # (n,) bool pre-dedup verdict of each row's txn
                            # (query-only)
    n: int                  # true row count; rows beyond are zero padding
    ml: int
    release_cb: object = None


@dataclass
class PackedVerdicts:
    """One harvested frag's passing txns as a packed wire arena (round 11
    egress form): wire j = arena[offs[j]:offs[j+1]] = k | sig_0[64] ..
    sig_k-1[64] | msg — the same bytes the legacy per-txn list would
    carry, back to back.  The arena is OWNED (copied out of the harvest
    scratch), so a PackedVerdicts outlives the pipeline's next finish;
    the verify tile burst-stamps it downstream as ONE frag instead of
    k."""

    arena: object           # (nbytes,) uint8, owned
    offs: object            # (k+1,) int64 wire boundaries, offs[0] = 0
    tags: object            # (k,) uint64 dedup tags of the survivors
    k: int                  # survivor count
    seq: int = 0            # the packed frame's in-link frag seq
    tsorig: int = 0         # the frame's span-chain origin (u32 stamp)

    def wires(self) -> list[bytes]:
        """Materialize per-txn wire bytes (legacy egress / parity).  One
        arena tobytes + bytes slicing — ~2x cheaper per txn than slicing
        the ndarray per wire (no per-txn view objects)."""
        buf = self.arena.tobytes() if isinstance(
            self.arena, np.ndarray) else bytes(self.arena)
        ol = np.asarray(self.offs).tolist()
        return [buf[a:b] for a, b in zip(ol, ol[1:])]


@dataclass
class _Inflight:
    """A dispatched-but-unharvested device batch (wiredancer's in-flight
    request set, src/wiredancer/c/wd_f1.h:85-113: results come back
    asynchronously and are matched to requests on completion)."""

    ok_dev: object            # jax array future of per-lane pass bits
    pending: list             # the _Pending txns of that batch
    t0: int                   # dispatch timestamp (ns)
    buf: object = None        # packed blob pinned under this dispatch
    owner: object = None      # the _Bucket / _FrameCall whose pool gets
                              # buf back once the batch is retired
    lane: int = 0             # 0 = throughput lane, 1 = low-latency lane
    t_first: int = 0          # arrival ns of the batch's oldest txn
    seq: int = 0              # packed call: its first frame's in-link frag
                              # seq, carried by the dispatch/device/harvest
                              # spans
    tsorig: int = 0           # packed call: span-chain origin (u32 stamp)


class _Bucket:
    """One compiled (batch, msg_maxlen) shape with its open batch.

    packed=True lays the bucket out as ONE row-interleaved uint8 array
    (msgs | sigs | pubs | lens-le32 per row): the native burst parser
    fills it in place and the device dispatch uploads it as a single
    blob (wiredancer's DMA push shape: one host->device transfer per
    batch instead of four).  msgs/sigs/pubs remain live numpy
    VIEWS into the array, so the scalar submit() path and test fakes
    work unchanged.

    Packed buckets rotate over a small pool of `n_buffers` blobs
    (upload/compute double buffering, VERDICT r5 Next #4): a flushed
    blob stays pinned under its _Inflight dispatch and returns to the
    pool only after its verdict materializes in _finish() — it is never
    repacked while the device may still read it — while reset() swaps
    in a free (zeroed) blob so the next batch packs during the previous
    batch's upload + verify."""

    def __init__(self, batch: int, maxlen: int, packed: bool = False,
                 n_buffers: int = 2, bidx: int = 0, lane: int = 0):
        self.batch = batch
        self.maxlen = maxlen
        self.packed = packed
        self.n_buffers = max(1, n_buffers)
        # position in the pipeline's ladder, stamped at creation — the
        # dispatch trace span's iidx (a list.index() per flush before)
        self.bidx = bidx
        self.lane = lane            # 0 = throughput, 1 = low-latency
        self._pool: deque = deque()
        self.reset()

    # packed row tail width; must equal ops.ed25519.PACKED_EXTRA (the
    # layout's single definition — cross-checked in tests) without
    # importing jax at pipeline-module import time
    PACKED_EXTRA = 100

    def release(self, arr) -> None:
        """Return a no-longer-inflight packed blob to the rotation."""
        if self.packed and len(self._pool) < self.n_buffers:
            self._pool.append(arr)

    def reset(self):
        if self.packed:
            ml = self.maxlen
            if self._pool:
                self.arr = self._pool.popleft()
                # zero the reused blob: the verify contract wants
                # zero-padded message columns, and partial (age-flush)
                # fills would otherwise see the previous batch's bytes
                self.arr.fill(0)
            else:
                self.arr = np.zeros((self.batch, ml + self.PACKED_EXTRA),
                                    dtype=np.uint8)
            self.msgs = self.arr[:, :ml]
            self.sigs = self.arr[:, ml:ml + 64]
            self.pubs = self.arr[:, ml + 64:ml + 96]
        else:
            self.arr = None
            self.msgs = np.zeros((self.batch, self.maxlen), dtype=np.uint8)
            self.sigs = np.zeros((self.batch, 64), dtype=np.uint8)
            self.pubs = np.zeros((self.batch, 32), dtype=np.uint8)
        self.lens = np.zeros((self.batch,), dtype=np.int32)
        self.used = 0
        self.t_first = 0  # ns stamp of the first txn in the open batch
        self.pending: list[_Pending] = []

    def set_len(self, lane: int, n: int):
        self.lens[lane] = n
        if self.packed:
            self.arr[lane, self.maxlen + 96:self.maxlen + 100] = (
                np.int32(n).tobytes())


class _FrameCall:
    """The bulk lane's open device call for packed frames
    (submit_packed_rows): a packed blob of the frames' (batch, ml) shape
    that each frame's live rows are copied into at row `used`, whole and
    in order, so the call's transactions stay contiguous rows.  A frame
    starts on a transaction's first row (its producer never splits one).

    Blobs rotate like a packed _Bucket's: the dispatched blob stays pinned
    under its _Inflight and comes back to the pool once harvest has
    rebuilt its wires, so the steady state holds max_inflight + 1 blobs
    and allocates nothing.  Each blob keeps a high-water mark `hw`, the
    rows written since it was last zeroed: at dispatch only rows
    [used, hw) are zeroed, so rows past the fill read as dead lanes
    (tag 0) without a fill(0) of the whole blob per call."""

    def __init__(self, batch: int, maxlen: int, bidx: int = 0):
        self.batch = batch
        self.maxlen = maxlen
        self.bidx = bidx            # the matching bucket's trace iidx
        self._pool: deque = deque()
        self.arr = None             # taken from the pool at the first copy
        self.hw = 0
        self.reset()

    def reset(self):
        """Empty the open call (its blob, if any, went to a dispatch)."""
        self.used = 0
        self.frames = 0
        self.tags: list = []
        self.dups: list = []
        self.t_first = 0            # perf_counter ns of the first copy
        self.seq = 0                # the first frame's in-link seq
        self.tsorig = 0             # the first frame's chain origin

    def take(self) -> None:
        """Give the open call a blob: a pooled one, else a fresh one."""
        if self._pool:
            self.arr, self.hw = self._pool.popleft()
        else:
            self.arr = np.zeros(
                (self.batch, self.maxlen + _Bucket.PACKED_EXTRA), np.uint8)
            self.hw = 0

    def release(self, buf) -> None:
        """Return a retired (blob, hw) to the rotation."""
        self._pool.append(buf)


class VerifyPipeline:
    """Fixed-shape batching verify pipeline.

    Single-bucket form (tests, latency tiers):
        VerifyPipeline(fn, batch=B, msg_maxlen=L)
    Multi-bucket form (production: full-MTU coverage):
        VerifyPipeline(fn, buckets=[(2048, 256), (256, 768), (64, 1232)])

    verify_fn must be shape-polymorphic (a jitted ed.verify_batch / a
    SigVerifier recompiles per bucket shape on first use).
    tcache_depth: dedup window in distinct signatures (fd_dedup tile default
    is ~2M; tests use small windows).
    """

    def __init__(self, verify_fn, batch: int | None = None,
                 msg_maxlen: int | None = None, tcache_depth: int = 1 << 16,
                 buckets=None, max_inflight: int = 0,
                 packed_rows: bool | None = None, tracer=None,
                 n_buffers: int = 2, dp_shards: int = 1,
                 heartbeat_cb=None, lat_shapes=None, deadline_us: int = 2000,
                 lat_max_inflight: int = 2, lat_maxlen: int | None = None,
                 lat_spill_age_factor: float = 4.0,
                 native_hostpath: bool | None = None,
                 egress_packed: bool = False):
        if buckets is None:
            if batch is None or msg_maxlen is None:
                raise ValueError("need either (batch, msg_maxlen) or buckets")
            buckets = ((batch, msg_maxlen),)
        self.verify_fn = verify_fn
        # dp_shards: the data-parallel mesh width the verifier dispatches
        # over (round 7).  Bucket shapes must split the mesh evenly so the
        # hot path never pads (a padded dispatch compiles a second masked
        # graph per bucket); the verifier's own shard count must agree or
        # its dispatch would silently run a different SPMD program than
        # the topology declares.
        self.dp_shards = max(1, int(dp_shards))
        if self.dp_shards > 1:
            vshards = getattr(verify_fn, "n_shards", self.dp_shards)
            if vshards != self.dp_shards:
                raise ValueError(
                    f"dp_shards={self.dp_shards} but verify_fn shards "
                    f"{vshards} ways")
            for b, _m in buckets:
                if b % self.dp_shards:
                    raise ValueError(
                        f"bucket batch {b} not divisible by "
                        f"dp_shards {self.dp_shards}")
        # packed row-interleaved buckets + single-blob dispatch when the
        # verifier supports it (SigVerifier.dispatch_blob, per-sig modes
        # — the packed graph is the configured strict/antipa graph; rlc
        # has no packed form); explicit packed_rows overrides the
        # autodetect
        if packed_rows is None:
            packed_rows = (hasattr(verify_fn, "dispatch_blob")
                           and getattr(verify_fn, "mode", "strict")
                           in ("strict", "antipa"))
        self.packed_rows = packed_rows
        # n_buffers: packed-blob rotation depth per bucket (double
        # buffering by default; raise alongside max_inflight to keep a
        # free blob available at higher dispatch-ahead depths)
        self.n_buffers = n_buffers
        self.buckets = [
            _Bucket(b, m, packed=packed_rows, n_buffers=n_buffers, bidx=i)
            for i, (b, m) in enumerate(sorted(buckets, key=lambda t: t[1]))
        ]
        # legacy single-bucket attributes (tests introspect these)
        self.batch = self.buckets[0].batch
        self.msg_maxlen = self.buckets[-1].maxlen
        # native tcache preferred: the burst parse path queries it inline
        # from C (one call per burst instead of one dict op per txn)
        try:
            self.tcache = NativeTCache(tcache_depth)
        except Exception:
            self.tcache = TCache(tcache_depth)
        # one-pass native host path (round 11): submit-side tag gather +
        # dedup query and harvest-side verdict/insert/wire-build each run
        # as a single C call per frag (native/hostpath.cpp).  Requires the
        # native tcache (the C kernel queries/inserts it in-library); any
        # build/load failure falls back to the NumPy path, bit-identical.
        if native_hostpath is None:
            native_hostpath = os.environ.get(
                "FDTPU_INGEST_NATIVE_HOSTPATH", "1") != "0"
        self._hp = None
        if native_hostpath and isinstance(self.tcache, NativeTCache):
            try:
                self._hp = native_mod.lib()
            except Exception:
                self._hp = None
        # harvest scratch for the native finish, grown to the worst case
        # n*(65+ml) once per shape — steady state allocates nothing
        self._hp_arena = np.empty(0, np.uint8)
        self._hp_offs = np.empty(1, np.int64)
        self._hp_tags = np.empty(0, np.uint64)
        self._hp_cnt = np.zeros(3, np.int64)
        self._hp_sub = np.zeros(4, np.int64)
        # packed verdict egress: _finish_rows returns ONE PackedVerdicts
        # per frag instead of k (bytes, txn) tuples; the verify tile
        # stamps it downstream as a single arena frag
        self.egress_packed = bool(egress_packed)
        self.metrics = VerifyMetrics()
        # max_inflight > 0 enables the ASYNC data plane (wiredancer's
        # contract): a filled batch is dispatched without waiting, up to
        # max_inflight batches ride the device queue, and completed
        # batches are harvested in order by harvest() / submit().  0 =
        # synchronous (verdicts returned by the submit that fills a
        # batch — the simple form tests use).
        self.max_inflight = max_inflight
        # bulk batches retired per NON-blocking harvest poll (see
        # harvest()); the deadline lane is never quota'd.  2 measures
        # best on the modeled-latency smoke: 1 stretches the backlog
        # window (the grind runs longer), unbounded head-of-line-blocks
        # the deadline lane for tens of ms
        self.harvest_quota = 2
        self.inflight: deque[_Inflight] = deque()
        # bulk-lane packed frames (submit_packed_rows): one _FrameCall per
        # frame shape, and the one whose call is open (holds rows not yet
        # dispatched).  Frames that arrive while the device queue is at
        # max_inflight merge into that call.
        self._fcalls: dict[tuple[int, int], _FrameCall] = {}
        self._fcall: _FrameCall | None = None
        # fdtrace: optional span sink (a disco.trace.TraceRing — or any
        # object with its .record signature); coalesce/device/compile
        # spans are recorded alongside the mux's frag/burst spans so the
        # whole chain reconstructs in one timeline
        self.tracer = tracer
        self._seen_shapes: set[tuple[int, int]] = set()
        # called while blocked on a device verdict (TileCtx.heartbeat in
        # the verify tile): a long device wait must not read as a dead
        # tile to the supervisor, and must still honor HALT
        self.heartbeat_cb = heartbeat_cb
        # ---- low-latency lane (round 9) --------------------------------
        # A ladder of small pre-warmed shapes beside the throughput
        # buckets.  Admitted txns accumulate in ONE bucket shaped as the
        # LARGEST lat shape; at close — fill, or deadline_us on the
        # oldest admitted txn — the batch ships as the SMALLEST ladder
        # shape that holds the filled lanes (closest fit), so a
        # deadline close at 1% fill does not pay the full accumulator's
        # device time.  lat batches retire through their OWN inflight
        # queue: a 16-lane verdict must never wait behind a 2048-lane
        # throughput batch in the ordered harvest.
        self.lat_shapes = tuple(sorted(int(s) for s in (lat_shapes or ())))
        self.deadline_us = int(deadline_us)
        self.lat_max_inflight = max(1, int(lat_max_inflight))
        self.lat_spill_age_ns = int(
            float(lat_spill_age_factor) * self.deadline_us * 1_000)
        self.lat_inflight: deque[_Inflight] = deque()
        if self.lat_shapes:
            for s in self.lat_shapes:
                if self.dp_shards > 1 and s % self.dp_shards:
                    raise ValueError(
                        f"lat shape {s} not divisible by "
                        f"dp_shards {self.dp_shards}")
            ml = (min(m for _, m in buckets) if lat_maxlen is None
                  else int(lat_maxlen))
            self.lat_bucket = _Bucket(
                self.lat_shapes[-1], ml, packed=packed_rows,
                n_buffers=n_buffers, bidx=len(self.buckets), lane=1)
        else:
            self.lat_bucket = None

    @property
    def has_pending(self) -> bool:
        return (self.has_open or bool(self.inflight)
                or bool(self.lat_inflight))

    @property
    def has_open(self) -> bool:
        """True iff some bucket or the open frame call holds UNDISPATCHED
        txns — the age-flush predicate (in-flight batches need no
        flushing, only harvesting; gating the flush on has_pending made
        the tile re-fire a no-op dispatch_open every after_credit while
        batches were in flight)."""
        return (any(bk.pending for bk in self.buckets)
                or bool(self.lat_bucket and self.lat_bucket.pending)
                or self._fcall is not None)

    def _call_due(self) -> bool:
        """The open frame call may go out: the device queue has room."""
        return (self._fcall is not None
                and len(self.inflight) < max(1, self.max_inflight))

    def _bucket_for(self, msg_len: int) -> _Bucket | None:
        for bk in self.buckets:  # sorted by maxlen: smallest fitting bucket
            if msg_len <= bk.maxlen:
                return bk
        return None

    # ---- low-latency lane ----------------------------------------------
    def mark_warm(self, shapes) -> None:
        """Record (batch, maxlen) shapes as already compiled (the tile
        warms every bucket + lat ladder shape through the verifier BEFORE
        this pipeline exists): their first dispatch here then does not
        count as a compile, so a nonzero compile_cnt in steady state
        means a genuinely cold shape reached the hot path — the
        no-compile-storm signal the latency smoke gates on."""
        for b, ml in shapes:
            self._seen_shapes.add((int(b), int(ml)))

    def _lat_overloaded(self) -> bool:
        """Overload-shed predicate: the lane's dispatch-ahead depth is at
        budget, or its open queue has aged far past the deadline (device
        underwater) — either way new admissions spill to the throughput
        lane instead of queuing behind a lane that can't keep its
        promise."""
        if len(self.lat_inflight) >= self.lat_max_inflight:
            return True
        bk = self.lat_bucket
        return bool(
            bk.t_first and self.lat_spill_age_ns
            and time.perf_counter_ns() - bk.t_first > self.lat_spill_age_ns)

    def _fit_rows(self, used: int) -> int:
        """Closest-fit ladder shape: the smallest pre-warmed lat shape
        holding `used` filled lanes."""
        for s in self.lat_shapes:
            if s >= used:
                return s
        return self.lat_shapes[-1]

    def _flush_lat(self, deadline: bool = False) -> list:
        bk = self.lat_bucket
        if bk is None or not bk.pending:
            return []
        if deadline:
            self.metrics.lat_deadline_closes += 1
        return self._flush_bucket(bk, rows=self._fit_rows(bk.used))

    def lat_due(self, now_ns: int | None = None) -> bool:
        """True iff the open low-latency batch's OLDEST txn has aged past
        deadline_us — the batch-close-on-deadline predicate, cheap enough
        for every after_credit iteration."""
        bk = self.lat_bucket
        if bk is None or not bk.pending or self.deadline_us <= 0:
            return False
        now = time.perf_counter_ns() if now_ns is None else now_ns
        return now - bk.t_first >= self.deadline_us * 1_000

    def dispatch_due(self) -> list:
        """Deadline dispatch: close the open lat batch the moment its
        oldest txn hits deadline_us, even at 1% fill (closest-fit shape).
        Non-blocking in async mode; completed batches from either lane
        are returned."""
        out = self._flush_lat(deadline=True) if self.lat_due() else []
        if self.max_inflight > 0:
            out += self.harvest()
        return out

    def submit(self, payload: bytes,
               lat: bool = False) -> list[tuple[bytes, txn_lib.Txn]]:
        """Feed one serialized txn.  Returns verified txns flushed by this
        submit (empty unless an open batch filled and was dispatched).

        lat=True admits the txn to the low-latency lane (priority
        admission).  When the lane is overloaded — inflight depth at
        budget, or the open queue aged far past the deadline — or the
        txn doesn't fit the lane's shape, it SPILLS to the throughput
        lane (lat_spill counts it) rather than blowing the deadline
        silently or dropping."""
        self.metrics.txns_in += 1
        try:
            parsed = txn_lib.parse(payload)
        except txn_lib.TxnParseError:
            self.metrics.parse_fail += 1
            return []

        msg = parsed.message(payload)
        sigs = parsed.signatures(payload)
        bk = None
        if lat and self.lat_bucket is not None:
            lb = self.lat_bucket
            if (len(msg) <= lb.maxlen and len(sigs) <= lb.batch
                    and not self._lat_overloaded()):
                bk = lb
            else:
                self.metrics.lat_spill += 1
        if bk is None:
            bk = self._bucket_for(len(msg))
            if bk is None:
                self.metrics.too_long_drop += 1
                return []

        if len(sigs) > bk.batch:
            # a txn's sig lanes must fit one device batch; batch >= 12
            # (FD_TXN_ACTUAL_SIG_MAX) covers every wire-valid txn
            self.metrics.sig_overflow_drop += 1
            return []
        # pre-dedup on the low 64 bits of the first signature
        # (fd_verify.h:64-71; the full-sig dedup tile runs downstream).
        # Query-only here; the tag is inserted only after verify PASSES in
        # flush() — inserting pre-verify would let an attacker poison the
        # window with a mangled copy and block the valid retransmission.
        tag = int.from_bytes(sigs[0][:8], "little")
        if self.tcache.query(tag):
            self.metrics.dedup_drop += 1
            return []

        out = []
        if bk.used + len(sigs) > bk.batch:
            out = (self._flush_lat() if bk.lane
                   else self._flush_bucket(bk))
        pubs = parsed.signer_pubkeys(payload)
        lanes = []
        for s, p in zip(sigs, pubs):
            lane = bk.used
            bk.msgs[lane, : len(msg)] = np.frombuffer(msg, dtype=np.uint8)
            bk.set_len(lane, len(msg))
            bk.sigs[lane] = np.frombuffer(s, dtype=np.uint8)
            bk.pubs[lane] = np.frombuffer(p, dtype=np.uint8)
            lanes.append(lane)
            bk.used += 1
        if not bk.t_first:
            bk.t_first = time.perf_counter_ns()
        bk.pending.append(_Pending(payload, parsed, lanes, tag))
        if bk.lane:
            self.metrics.lat_txns += 1
        if bk.used == bk.batch:
            out += self._flush_lat() if bk.lane else self._flush_bucket(bk)
        return out

    def submit_burst(self, payloads=None, packed=None) -> list:
        """Feed many serialized txns with ONE native parse+dedup call per
        bucket fill (native/txnparse.cpp — the verify tile's burst data
        plane; the scalar submit() path cost ~110 us/txn of Python,
        3.6x the reference's whole per-core verify budget).

        Input: either payloads (list[bytes]) or packed=(buf, offs) — a
        flat buffer + int64 offsets (n+1), e.g. the ring rx scratch from
        fd_ring_rx_burst, consumed zero-copy.

        Returns verified txns flushed by this call as (payload, None)
        tuples: burst mode skips Txn descriptor construction (the verify
        tile forwards payload+tag only; downstream tiles re-parse).
        Callers that need the parsed descriptor use submit().

        Bursts fill the PRIMARY (widest-lane) bucket; txns whose message
        exceeds it reroute through the scalar path's bucket ladder."""
        from ..ballet import txn_native as tn

        if packed is None:
            handle = getattr(self.tcache, "handle", None)
            if handle is None:
                # no native tcache (lib unavailable): degrade to scalar
                out = []
                for p in payloads:
                    out += self.submit(p)
                return out
            packed = tn.pack_payloads(payloads)
        else:
            handle = getattr(self.tcache, "handle", None)
            if handle is None:
                out = []
                buf0, offs0 = packed
                for i in range(len(offs0) - 1):
                    out += self.submit(bytes(buf0[offs0[i]:offs0[i + 1]]))
                return out
        buf, offs = packed

        out = []
        bk = self.buckets[0]
        idx = 0
        n = len(offs) - 1
        while idx < n:
            if bk.packed:
                r = tn.parse_packed_bucket(buf, offs[idx:], bk.arr,
                                           bk.maxlen, bk.lens, bk.used,
                                           handle)
            else:
                r = tn.parse_packed(buf, offs[idx:], bk.msgs, bk.lens,
                                    bk.sigs, bk.pubs, bk.used, handle)
            errs = r.err
            too_long = np.nonzero(errs == tn.ERR_TOO_LONG)[0]
            reroute = len(self.buckets) > 1
            self.metrics.txns_in += r.consumed - (
                len(too_long) if reroute else 0)
            self.metrics.parse_fail += int((errs == tn.ERR_PARSE).sum())
            self.metrics.dedup_drop += int((errs == tn.ERR_DUP).sum())
            self.metrics.sig_overflow_drop += int(
                (errs == tn.ERR_SIG_CAP).sum())
            if reroute:
                for i in too_long:
                    j = idx + int(i)
                    out += self.submit(bytes(buf[offs[j]:offs[j + 1]]))
            else:
                self.metrics.too_long_drop += len(too_long)
            acc = np.nonzero(errs == tn.OK)[0]
            if len(acc):
                # one copy of this round's region; accepted txns address
                # into it by (start, len) — materialized per txn only on
                # verify pass at harvest
                base = int(offs[idx])
                region = bytes(
                    memoryview(buf)[base:int(offs[idx + r.consumed])])
                starts = (offs[idx:][acc] - base).astype(np.int64)
                plens = (offs[idx:][acc + 1] - offs[idx:][acc]).astype(
                    np.int32)
                if not bk.t_first:
                    bk.t_first = time.perf_counter_ns()
                bk.pending.append(_BurstPending(
                    region, starts, plens,
                    r.lane0[acc], r.nsig[acc], r.tag[acc]))
                bk.used += r.lanes_used
            pre_used = bk.used
            idx += r.consumed
            if idx >= n:
                break
            # reaching here means the parser stopped early: the next txn
            # needs more lanes than remain — flush and retry it against
            # the empty bucket
            out += self._flush_bucket(bk)
            if r.consumed == 0 and pre_used == 0:
                # even an empty bucket can't hold it (defensive;
                # kErrSigCap already rejects txns wider than capacity)
                self.metrics.txns_in += 1
                self.metrics.sig_overflow_drop += 1
                idx += 1
        if bk.used == bk.batch:
            out += self._flush_bucket(bk)
        return out

    def submit_packed_rows(self, rows, n: int | None = None, guard=None,
                           release_cb=None, lat: bool = False,
                           tsorig: int = 0) -> list:
        """Packed-wire submit (round 8): `rows` is a (batch, ml+100) uint8
        VIEW over the shm dcache, already laid out in the device-blob row
        format (msg | sig | pub | len-le32) by the producer.

        n: true row count (rows beyond are the producer's zero padding;
        their tag is 0 and they are excluded from dedup and counts).
        guard=(mcache, seq): the frag's seqlock, re-checked once the rows
        have been read; a torn frag (producer lapped the dcache) is
        dropped whole (torn_drop) — never verified.
        release_cb: fired exactly once when the pipeline is done with the
        view — the tile returns the frag's consumer credit there.
        tsorig: the frame's span-chain origin, handed back on its
        PackedVerdicts so the verdict frag continues the chain.

        Bulk lane: the frame's n rows are copied into the open call
        (_FrameCall) as one contiguous copy, the seq is re-checked, and
        release_cb fires at once, so no credit is held across a device
        call.  The open call is dispatched whenever the device queue has
        room (here and after every harvest): under light load a lone
        frame goes out at once as its own call, and frames that arrive
        while max_inflight calls are queued merge into the next one.  A
        frame that does not fit the open call closes it first; a frame is
        never split.

        lat=True routes the frag through the low-latency lane instead: the
        dispatch slices the view in place to the closest-fit ladder shape
        >= n, the credit stays held until the verdict retires via the lat
        inflight queue, and the frame's dispatch/device/harvest spans
        carry its seq.  An overloaded lane spills the whole frag to the
        bulk lane (lat_spill += its txns)."""
        if not hasattr(self.verify_fn, "dispatch_blob"):
            raise ValueError("submit_packed_rows needs a packed verifier "
                             "(dispatch_blob)")
        nrows = rows.shape[0]
        ml = rows.shape[1] - _Bucket.PACKED_EXTRA
        n = nrows if n is None else min(int(n), nrows)
        spill = False
        if lat and self.lat_shapes:
            if not self._lat_overloaded():
                return self._submit_lat_rows(rows, n, ml, guard, release_cb,
                                             tsorig)
            spill = True
        return self._submit_frame(rows, n, ml, guard, release_cb, tsorig,
                                  spill)

    def _submit_tags(self, rows, n: int, ml: int):
        """Dedup tags and pre-dedup verdicts of n packed rows: (per-row
        txn tag, per-row txn dup, txns, multi-signature txns, message
        bytes, dup txns).  Tags are the low 64 bits of the signature
        (row[ml:ml+8]); the 8B/row gather is metadata, not a payload
        copy.  Query-only — tags insert at harvest iff verify passes
        (fd_verify.h:64-71).  A txn of k signatures is k contiguous rows
        (the len word's marker, tango/ring.py): its first row's tag is
        every row's tag, queried once per txn.  Native path (round 11):
        strided gather + batched query as ONE C call."""
        if (self._hp is not None and rows.dtype == np.uint8
                and rows.strides[1] == 1):
            tag = np.empty(n, np.uint64)
            dup8 = np.empty(n, np.uint8)
            self._hp.fd_hostpath_submit_rows(
                ctypes.c_void_p(rows.ctypes.data),
                int(rows.strides[0]), n, ml,
                ctypes.c_void_p(self.tcache.handle),
                ctypes.c_void_p(tag.ctypes.data),
                ctypes.c_void_p(dup8.ctypes.data),
                ctypes.c_void_p(self._hp_sub.ctypes.data))
            ntxn, nmulti, nbytes, ndup = (int(c) for c in self._hp_sub)
            return tag, dup8.view(bool), ntxn, nmulti, nbytes, ndup
        return self._np_submit(rows, n, ml)

    def _torn(self, guard, rows, n: int, ml: int) -> bool:
        """The frag's seqlock re-check: True (and counted) when the
        producer lapped the dcache since rx, so the rows read may be torn.
        Torn rows never reach harvest: they count in their OWN counter,
        txns_in/dedup_drop untouched, so pass/fail rates derived from
        txns_in stay honest."""
        if guard is None:
            return False
        mcache, seq = guard
        rc, _ = mcache.query(seq)
        if rc == 0:
            return False
        self.metrics.torn_drop += 1
        # txns = first rows (signature index 0), as the submit counts them
        self.metrics.torn_txns += int(
            (rows[:n, ml + 98] == 0).sum())
        return True

    def _submit_frame(self, rows, n: int, ml: int, guard, release_cb,
                      tsorig: int, spill: bool) -> list:
        """Bulk lane: copy, check, release, then dispatch when the device
        queue has room (see submit_packed_rows)."""
        shape = (rows.shape[0], ml)
        out = []
        fc = self._fcall
        if fc is not None and ((fc.batch, fc.maxlen) != shape
                               or fc.used + n > fc.batch):
            out += self._close_call()
        fc = self._fcalls.get(shape)
        if fc is None:
            bidx = next((bk.bidx for bk in self.buckets
                         if (bk.batch, bk.maxlen) == shape), 0)
            fc = self._fcalls[shape] = _FrameCall(shape[0], ml, bidx)
        if fc.arr is None:
            fc.take()
        t0 = time.perf_counter_ns()
        u = fc.used
        dst = fc.arr[u:u + n]
        np.copyto(dst, rows[:n])
        fc.hw = max(fc.hw, u + n)
        if not self._torn(guard, dst, n, ml) and n:
            tag, dup, ntxn, nmulti, nbytes, ndup = self._submit_tags(
                dst, n, ml)
            m = self.metrics
            m.txns_in += ntxn
            m.multisig_txns += nmulti
            m.dedup_drop += ndup
            m.lanes_filled += n
            m.msg_bytes += nbytes
            if spill:
                m.lat_spill += ntxn
            if fc.frames:
                m.coalesced_frames += 1
            else:
                fc.t_first = t0
                fc.seq = guard[1] if guard is not None else 0
                fc.tsorig = tsorig
            fc.frames += 1
            fc.tags.append(tag)
            fc.dups.append(dup)
            fc.used = u + n
            self._fcall = fc
        if release_cb is not None:
            release_cb()
        if self._call_due():
            out += self._dispatch_call()
        if self.max_inflight <= 0:
            return out
        return out + self.harvest()

    def _close_call(self) -> list:
        """The next frame does not fit the open call: retire the oldest
        verdict while the device queue is full (blocking), then dispatch
        the call."""
        t0 = time.perf_counter_ns()
        out = []
        while self.inflight and not self._call_due():
            out += self._finish(self.inflight.popleft())
        return out + self._dispatch_call(t0)

    def _dispatch_call(self, t_span: int | None = None) -> list:
        """Dispatch the open frame call as one device call over its whole
        blob (the frames' (batch, ml) shape, warmed at boot), with ONE
        pending record over its `used` rows.  Sync mode (max_inflight 0)
        retires it at once; async mode queues it, the caller having made
        room.  Spans: KIND_COALESCE first copy -> dispatch (cnt = frames)
        and KIND_DISPATCH (cnt = rows), both with the first frame's seq;
        the dispatch span starts at t_span where a full queue was drained
        first (_close_call), the dispatch-queue pressure stage."""
        fc = self._fcall
        self._fcall = None
        t0 = time.perf_counter_ns()
        if t_span is None:
            t_span = t0
        m = self.metrics
        used, arr, ml = fc.used, fc.arr, fc.maxlen
        m.coalesce_ns.sample(t0 - fc.t_first)
        if self.tracer is not None:
            self.tracer.record(trace_mod.KIND_COALESCE, fc.t_first,
                               t0 - fc.t_first, iidx=fc.bidx,
                               cnt=fc.frames, seq=fc.seq)
        if fc.hw > used:
            arr[used:fc.hw] = 0       # rows past the fill: dead lanes
        ok_dev = self._dispatch_rows(arr, ml, fc.bidx)
        start_async = getattr(ok_dev, "copy_to_host_async", None)
        if start_async is not None:
            start_async()
        m.lanes_dispatched += fc.batch
        m.last_fill_pct = 100 * used // fc.batch
        rp = _RowsPending(arr, np.concatenate(fc.tags),
                          np.concatenate(fc.dups), used, ml)
        fl = _Inflight(ok_dev, [rp], t0, buf=(arr, used), owner=fc,
                       t_first=fc.t_first, seq=fc.seq, tsorig=fc.tsorig)
        fc.arr = None
        fc.reset()
        out = []
        if self.max_inflight <= 0:
            out = self._finish(fl)
        else:
            self.inflight.append(fl)
        if self.tracer is not None:
            self.tracer.record(trace_mod.KIND_DISPATCH, t_span,
                               time.perf_counter_ns() - t_span,
                               iidx=fc.bidx, cnt=used, seq=fl.seq)
        return out

    def _submit_lat_rows(self, rows, n: int, ml: int, guard, release_cb,
                         tsorig: int) -> list:
        """Low-latency lane: dispatch the shm view in place, sliced to the
        closest-fit ladder shape (a leading row slice is contiguous), with
        the seq re-checked AFTER the dispatch call returns and the credit
        held until the verdict retires."""
        nrows = rows.shape[0]
        tag, dup, ntxn, nmulti, nbytes, ndup = self._submit_tags(rows, n, ml)
        self.metrics.lat_txns += ntxn
        fit = next((s for s in self.lat_shapes if s >= n), None)
        nd = fit if fit is not None and fit < nrows else nrows
        t0 = time.perf_counter_ns()
        ok_dev = self._dispatch_rows(rows if nd == nrows else rows[:nd],
                                     ml, trace_mod.LANE_LAT)
        # no-torn-buffer invariant, view edition: the payload was never
        # copied under the seqlock, so the overrun check moves to AFTER
        # the device got its read of the region underway
        if self._torn(guard, rows, n, ml):
            if release_cb is not None:
                release_cb()
            return []
        self.metrics.txns_in += ntxn
        self.metrics.multisig_txns += nmulti
        self.metrics.dedup_drop += ndup
        start_async = getattr(ok_dev, "copy_to_host_async", None)
        if start_async is not None:
            start_async()
        self.metrics.lanes_filled += n
        self.metrics.msg_bytes += nbytes
        self.metrics.lanes_dispatched += nd
        self.metrics.last_fill_pct = 100 * n // nd
        seq = guard[1] if guard is not None else 0
        fl = _Inflight(ok_dev,
                       [_RowsPending(rows, tag, dup, n, ml, release_cb)],
                       t0, lane=1, t_first=t0, seq=seq, tsorig=tsorig)
        if self.max_inflight <= 0:
            if self.tracer is not None:
                self.tracer.record(trace_mod.KIND_DISPATCH, t0,
                                   time.perf_counter_ns() - t0,
                                   iidx=trace_mod.LANE_LAT, cnt=n, seq=seq)
            return self._finish(fl)
        q = self.lat_inflight
        q.append(fl)
        out = []
        while len(q) > self.max_inflight:
            out += self._finish(q.popleft())
        if self.tracer is not None:
            # dispatch call + over-budget drain, as in _flush_bucket
            self.tracer.record(trace_mod.KIND_DISPATCH, t0,
                               time.perf_counter_ns() - t0,
                               iidx=trace_mod.LANE_LAT, cnt=n, seq=seq)
        return out + self.harvest()

    def _query(self, tags):
        """tcache query of each tag (bool array)."""
        if hasattr(self.tcache, "query_batch"):
            return self.tcache.query_batch(tags)
        return np.array([self.tcache.query(int(t)) for t in tags],
                        dtype=bool)

    def _insert(self, tags):
        """tcache insert of each tag in turn; True where it was already
        there (FD_TCACHE_INSERT dup semantics, earlier tags of the same
        call included)."""
        if hasattr(self.tcache, "insert_batch_dedup"):
            return self.tcache.insert_batch_dedup(tags)
        return np.array([self.tcache.insert(int(t)) for t in tags],
                        dtype=bool)

    def _np_submit(self, rows, n: int, ml: int):
        """NumPy twin of fd_hostpath_submit_rows: (per-row txn tag, per-row
        txn dup, txns, multi-signature txns, message bytes, dup txns)."""
        word = _len_words(rows, n, ml)
        tag = np.ascontiguousarray(rows[:n, ml:ml + 8]).view(
            np.uint64).ravel()
        nbytes = int((word & PACKED_LEN_MASK).sum())
        first = ((word >> PACKED_SIG_IDX_SHIFT) & 0xFF) == 0
        starts = np.nonzero(first)[0]
        if not len(starts):
            return (np.zeros(n, np.uint64), np.zeros(n, bool), 0, 0,
                    nbytes, 0)
        dup_t = self._query(tag[starts])
        # row -> its txn's index; rows before the first start belong to
        # no txn and read as dead lanes
        txn = np.cumsum(first) - 1
        own = txn >= 0
        at = np.maximum(txn, 0)
        tag = np.where(own, tag[starts][at], np.uint64(0))
        dup = own & dup_t[at]
        nmulti = int(((word[starts] >> PACKED_SIG_MORE_SHIFT) != 0).sum())
        return tag, dup, len(starts), nmulti, nbytes, int(dup_t.sum())

    def _dispatch_rows(self, blob, ml: int, iidx: int):
        """Dispatch a packed blob; the first dispatch of its (rows, ml)
        shape counts as a compile (its wall time holds the jit
        trace+compile or AOT load) — the compile-storm signal."""
        shape = (blob.shape[0], ml)
        t0 = time.perf_counter_ns()
        ok_dev = self._dispatch_blob(blob, ml)
        if shape not in self._seen_shapes:
            self._seen_shapes.add(shape)
            dt = time.perf_counter_ns() - t0
            self.metrics.compile_cnt += 1
            self.metrics.compile_ns += dt
            trace_mod.record_compile(("verify",) + shape, dt)
            if self.tracer is not None:
                self.tracer.record(trace_mod.KIND_COMPILE, t0, dt,
                                   iidx=iidx)
        return ok_dev

    def _dispatch_blob(self, blob, maxlen):
        """verify_fn.dispatch_blob, named `fdtpu.verify.dispatch` in a
        device-trace capture."""
        if trace_mod.annot is not None:
            with trace_mod.annot("fdtpu.verify.dispatch"):
                return self.verify_fn.dispatch_blob(blob, maxlen=maxlen)
        return self.verify_fn.dispatch_blob(blob, maxlen=maxlen)

    def flush(self) -> list[tuple[bytes, txn_lib.Txn]]:
        """Dispatch every bucket with pending txns and harvest EVERYTHING
        (blocking); returns passing txns."""
        out = self._flush_lat()
        for bk in self.buckets:
            out += self._flush_bucket(bk)
        out += self.harvest(block=True)
        return out

    def dispatch_open(self) -> list[tuple[bytes, txn_lib.Txn]]:
        """Age-flush for the async tile: dispatch partially-filled buckets
        WITHOUT waiting for their results (they surface via harvest());
        any already-completed batches are returned.  The open frame call
        goes out only where the device queue has room: otherwise the
        harvest that makes room sends it."""
        out = self._flush_lat()
        for bk in self.buckets:
            out += self._flush_bucket(bk)
        if self._call_due():
            out += self._dispatch_call()
        return out

    def harvest(self, block: bool = False) -> list[tuple[bytes, txn_lib.Txn]]:
        """Collect verdicts of completed in-flight batches, in dispatch
        order per lane.  block=False stops at the first still-running
        batch (the tile's after_credit poll); block=True drains both
        queues.  The low-latency queue drains FIRST — its verdicts are
        the deadline-bound ones, and its batches never wait behind a
        still-running throughput batch.

        A throughput batch's host-side finish (verdict fetch + passing-txn
        materialization) runs MILLISECONDS at 2048 lanes, and several bulk
        batches routinely become ready inside one poll window — an
        unbounded drain here head-of-line-blocks the deadline lane behind
        tens of ms of bulk bookkeeping.  Non-blocking harvest therefore
        retires at most `harvest_quota` bulk batches per call (work is
        conserved — the rest retire on subsequent polls) and re-services
        the lat lane between bulk finishes.

        Then the open frame call is dispatched if the queue has room; a
        blocking harvest drains that call too."""
        out = self._drain_lat(block)
        n_bulk = 0
        while True:
            while self.inflight:
                if not block:
                    if n_bulk >= self.harvest_quota:
                        break
                    if not _is_ready(self.inflight[0].ok_dev):
                        break
                out += self._finish(self.inflight.popleft())
                n_bulk += 1
                # a bulk finish is ms of host work: close + drain the
                # deadline lane between finishes so it never queues behind
                if self.lat_due():
                    out += self._flush_lat(deadline=True)
                out += self._drain_lat(block=False)
            if not self._call_due():
                return out
            out += self._dispatch_call()
            if not block:
                return out

    def _drain_lat(self, block: bool = False) -> list:
        out = []
        while self.lat_inflight:
            if not block and not _is_ready(self.lat_inflight[0].ok_dev):
                break
            out += self._finish(self.lat_inflight.popleft())
        return out

    def _flush_bucket(self, bk: _Bucket,
                      rows: int | None = None) -> list:
        """Dispatch a bucket's open batch.  rows (low-latency lane only)
        dispatches just the first `rows` lanes — the closest-fit ladder
        shape — instead of the full accumulator width."""
        if not bk.pending:
            return []
        t0 = time.perf_counter_ns()
        tr_idx = bk.bidx | (trace_mod.LANE_LAT if bk.lane else 0)
        if bk.t_first:
            self.metrics.coalesce_ns.sample(t0 - bk.t_first)
            if self.tracer is not None:
                self.tracer.record(trace_mod.KIND_COALESCE, bk.t_first,
                                   t0 - bk.t_first, iidx=tr_idx,
                                   cnt=len(bk.pending))
        nrows = bk.batch if rows is None else min(int(rows), bk.batch)
        # bucket occupancy: filled sig lanes vs the full dispatched shape
        # (the padding delta is the age-flush's device-waste signal)
        self.metrics.lanes_filled += bk.used
        self.metrics.lanes_dispatched += nrows
        self.metrics.last_fill_pct = 100 * bk.used // nrows
        # jax dispatch is asynchronous: this returns a device future
        # without waiting for the TPU.  The numpy bucket arrays pass
        # straight through — a jitted verify_fn device_puts them itself,
        # and reset() below allocates FRESH arrays, so the callee can
        # consume these asynchronously without a torn read.  Packed
        # buckets upload as ONE blob via the verifier's dispatch_blob.
        # A closest-fit slice is row-major-contiguous, so the sliced
        # blob/arrays are exactly the smaller shape's layout.
        shape = (nrows, bk.maxlen)
        first_dispatch = shape not in self._seen_shapes
        if bk.packed and hasattr(self.verify_fn, "dispatch_blob"):
            blob = bk.arr if nrows == bk.batch else bk.arr[:nrows]
            ok_dev = self._dispatch_blob(blob, bk.maxlen)
        else:
            ok_dev = self.verify_fn(bk.msgs[:nrows], bk.lens[:nrows],
                                    bk.sigs[:nrows], bk.pubs[:nrows])
        if first_dispatch:
            # first dispatch of this (batch, maxlen) shape: the wall time
            # above includes the jit trace+compile (or AOT load) — the
            # compile-storm signal bench.py and /metrics report
            self._seen_shapes.add(shape)
            dt = time.perf_counter_ns() - t0
            self.metrics.compile_cnt += 1
            self.metrics.compile_ns += dt
            trace_mod.record_compile(("verify",) + shape, dt)
            if self.tracer is not None:
                self.tracer.record(trace_mod.KIND_COMPILE, t0, dt,
                                   iidx=tr_idx)
        # kick the device->host verdict copy off NOW: a fetch that
        # starts the copy waits out the whole transfer; with the async
        # copy started at dispatch, harvest's fetch finds the bits
        # already (or nearly) resident
        start_async = getattr(ok_dev, "copy_to_host_async", None)
        if start_async is not None:
            start_async()
        # the packed blob stays pinned under this dispatch; reset() below
        # rotates a FREE pool blob in, so the next batch packs while this
        # one uploads/verifies (double-buffered ingest)
        fl = _Inflight(ok_dev, bk.pending, t0,
                       buf=bk.arr if bk.packed else None, owner=bk,
                       lane=bk.lane, t_first=bk.t_first)
        bk.reset()
        if self.max_inflight <= 0:
            if self.tracer is not None:
                self.tracer.record(trace_mod.KIND_DISPATCH, t0,
                                   time.perf_counter_ns() - t0,
                                   iidx=tr_idx, cnt=len(fl.pending))
            return self._finish(fl)          # synchronous mode
        q = self.lat_inflight if bk.lane else self.inflight
        q.append(fl)
        out = []
        while len(q) > self.max_inflight:
            # bounded queue: retire the oldest before accepting more
            out += self._finish(q.popleft())
        if self.tracer is not None:
            # dispatch call + over-budget drain: a full inflight queue
            # blocks in the loop above, so this span IS the
            # dispatch-queue pressure stage of the SLO budget
            self.tracer.record(trace_mod.KIND_DISPATCH, t0,
                               time.perf_counter_ns() - t0, iidx=tr_idx,
                               cnt=len(fl.pending))
        return out + self.harvest()

    def _finish(self, fl: _Inflight) -> list[tuple[bytes, txn_lib.Txn]]:
        """Retire one dispatched batch: wait for its verdict, then rebuild
        its passing txns — `fdtpu.verify.harvest` in a device-trace
        capture."""
        if trace_mod.annot is not None:
            with trace_mod.annot("fdtpu.verify.harvest"):
                return self._retire(fl)
        return self._retire(fl)

    def _retire(self, fl: _Inflight) -> list[tuple[bytes, txn_lib.Txn]]:
        t_wait = time.perf_counter_ns()
        if self.heartbeat_cb is not None:
            # heartbeat through the device wait instead of blocking cold
            # in np.asarray: the supervisor's staleness check keeps seeing
            # a live tile, and HALT still lands.  (A _GuardedVerdict's
            # is_ready turns True at its deadline, so a hung device cannot
            # wedge this loop either.)  Adaptive backoff: the low-latency
            # lane's verdicts are often <1 ms out, and a fixed 500 us poll
            # ate up to half of that per harvest — start at 50 us and
            # decay toward the old cap for long throughput-batch waits.
            wait = 50e-6
            while not _is_ready(fl.ok_dev):
                self.heartbeat_cb()
                time.sleep(wait)
                wait = min(wait * 2, 500e-6)
        ok = np.asarray(fl.ok_dev)           # blocks only if still running
        self.metrics.verdict_wait_ns += time.perf_counter_ns() - t_wait
        now = time.perf_counter_ns()
        self.metrics.batches += 1
        self.metrics.batch_ns.sample(now - fl.t0)
        if fl.lane:
            self.metrics.lat_batches += 1
            if fl.t_first:
                self.metrics.lat_e2e_ns.sample(now - fl.t_first)
        elif fl.t_first:
            self.metrics.e2e_ns.sample(now - fl.t_first)
        tr_idx = ((fl.owner.bidx if fl.owner is not None else 0)
                  | (trace_mod.LANE_LAT if fl.lane else 0))
        # a packed call's spans count its rows; a bucket batch's, records
        rows = (fl.pending[0].n if isinstance(fl.pending[0], _RowsPending)
                else None) if fl.pending else None
        if self.tracer is not None:
            self.tracer.record(trace_mod.KIND_DEVICE, fl.t0, now - fl.t0,
                               iidx=tr_idx,
                               cnt=len(fl.pending) if rows is None else rows,
                               seq=fl.seq)
        out = []
        for p in fl.pending:
            if isinstance(p, _RowsPending):
                out += self._finish_rows(p, ok, fl)
            elif isinstance(p, _BurstPending):
                out += self._finish_burst(p, ok)
            elif all(ok[lane] for lane in p.lanes):
                if self.tcache.insert(p.tag):
                    # same tag verified twice inside one open batch window
                    self.metrics.dedup_drop += 1
                    continue
                self.metrics.verify_pass += 1
                out.append((p.payload, p.parsed))
            else:
                self.metrics.verify_fail += 1
        if fl.buf is not None:
            # verdict materialized => the in-order device queue finished
            # both the blob's upload and the verify that read it, and the
            # finish above rebuilt its wires; only now may the blob
            # re-enter the pack rotation
            fl.owner.release(fl.buf)
            fl.buf = None
        if self.tracer is not None:
            # harvest stage: verdict materialized -> passing txns rebuilt
            self.tracer.record(trace_mod.KIND_HARVEST, now,
                               time.perf_counter_ns() - now, iidx=tr_idx,
                               cnt=len(out) if rows is None else rows,
                               seq=fl.seq)
        return out

    def _finish_rows(self, rp: _RowsPending, ok, fl: _Inflight) -> list:
        """Harvest one packed call: a txn of k signature rows passes iff
        all k verdicts pass, and a passing txn's wire is rebuilt byte for
        byte as sent (compact-u16 k | sig_0 .. sig_k-1 | msg) from the
        call's rows (the call buffer, or a low-latency frame's still-pinned
        shm view, whose held credit is then released).

        Native path (round 11): verdict masking + conditional tag insert
        + wire build run as ONE C call (fd_hostpath_finish_rows) writing
        every passing wire into a persistent arena with an offsets table.
        The NumPy fallback is bit-identical.  Egress is either the legacy
        per-txn [(bytes, None)] list or — egress_packed — a single
        PackedVerdicts carrying the arena."""
        try:
            okv = np.asarray(ok[:rp.n])
            rows = rp.rows
            if (self._hp is not None and rows.dtype == np.uint8
                    and rows.strides[1] == 1):
                pv = self._hp_finish(rp, okv)
            else:
                pv = self._np_finish(rp, okv)
            if pv is None or pv.k == 0:
                return []
            if self.egress_packed:
                pv.seq, pv.tsorig = fl.seq, fl.tsorig
                return [pv]
            return [(w, None) for w in pv.wires()]
        finally:
            if rp.release_cb is not None:
                rp.release_cb()

    def _hp_finish(self, rp: _RowsPending, okv) -> "PackedVerdicts | None":
        """One-pass C finish: masks, inserts, and memcpy-builds the wires
        of one frag into the grow-only scratch arena (worst case
        n*(65+ml) bytes, allocated once per shape)."""
        n, ml = rp.n, rp.ml
        ok8 = okv.view(np.uint8) if okv.dtype == np.bool_ else okv.astype(
            np.uint8)
        ok8 = np.ascontiguousarray(ok8)
        dup8 = (rp.dup.view(np.uint8) if rp.dup.dtype == np.bool_
                else np.ascontiguousarray(rp.dup, dtype=np.uint8))
        cap = n * (65 + ml)
        if self._hp_arena.nbytes < cap:
            self._hp_arena = np.empty(cap, np.uint8)
        if len(self._hp_offs) < n + 1:
            self._hp_offs = np.empty(n + 1, np.int64)
            self._hp_tags = np.empty(n, np.uint64)
        while True:
            rc = self._hp.fd_hostpath_finish_rows(
                ctypes.c_void_p(rp.rows.ctypes.data),
                int(rp.rows.strides[0]), n, ml,
                ctypes.c_void_p(ok8.ctypes.data),
                ctypes.c_void_p(rp.tag.ctypes.data),
                ctypes.c_void_p(dup8.ctypes.data),
                ctypes.c_void_p(self.tcache.handle),
                ctypes.c_void_p(self._hp_arena.ctypes.data),
                int(self._hp_arena.nbytes),
                ctypes.c_void_p(self._hp_offs.ctypes.data),
                ctypes.c_void_p(self._hp_tags.ctypes.data),
                ctypes.c_void_p(self._hp_cnt.ctypes.data))
            if rc >= 0:
                break
            # arena too small (cannot happen with the worst-case sizing
            # above, kept for safety): the C call touched NOTHING — grow
            # and retry with identical semantics
            self._hp_arena = np.empty(-int(rc), np.uint8)
        k = int(rc)
        self.metrics.verify_fail += int(self._hp_cnt[0])
        self.metrics.dedup_drop += int(self._hp_cnt[1])
        self.metrics.verify_pass += k
        if k == 0:
            return None
        nb = int(self._hp_offs[k])
        # copy out of the scratch: a PackedVerdicts must survive the next
        # frag's finish (harvest retires several per poll)
        return PackedVerdicts(self._hp_arena[:nb].copy(),
                              self._hp_offs[:k + 1].copy(),
                              self._hp_tags[:k].copy(), k)

    # fallback ragged-build pad cap: the masked column copy stages at most
    # this many payload bytes (plus the same-shape bool mask) at once, so
    # one long-tail row no longer inflates the harvest footprint to
    # k*Lmax (~2x the payload) — chunking trades one masked copy for a
    # few, identical bytes out
    _NP_PAD_CAP = 1 << 18

    def _np_finish(self, rp: _RowsPending, okv) -> "PackedVerdicts | None":
        """NumPy finish (no .so / non-native tcache / exotic row strides):
        same verdict masking, insert semantics, and arena layout as the C
        path, built with vectorized column copies when every row is its
        own txn."""
        word = _len_words(rp.rows, rp.n, rp.ml)
        if (word >> PACKED_SIG_IDX_SHIFT).any():
            return self._np_finish_txns(rp, okv, word)
        ml = rp.ml
        okv = okv.astype(bool)
        live = rp.tag != 0
        passing = okv & ~rp.dup & live
        self.metrics.verify_fail += int((live & ~rp.dup & ~okv).sum())
        pass_idx = np.nonzero(passing)[0]
        if len(pass_idx) == 0:
            return None
        # insert tags only now (verify passed) — exact FD_TCACHE_INSERT
        # dup semantics across frags and within this one
        dup2 = self._insert(rp.tag[pass_idx])
        self.metrics.dedup_drop += int(dup2.sum())
        self.metrics.verify_pass += int((~dup2).sum())
        rows = rp.rows
        keep = pass_idx[~dup2]
        if len(keep) == 0:
            return None
        klens = np.minimum(word[keep], ml).astype(np.int64)
        k = len(keep)
        offs = np.empty(k + 1, np.int64)
        offs[0] = 0
        np.cumsum(65 + klens, out=offs[1:])
        arena = np.empty(int(offs[k]), np.uint8)
        if int(klens.min()) == int(klens.max()):
            # equal-length rows (template-stamped bursts): the arena IS a
            # (k, 65+L) matrix — three vectorized column copies, no pad
            L = int(klens[0])
            wires = arena.reshape(k, 65 + L)
            wires[:, 0] = 1
            wires[:, 1:65] = rows[keep, ml:ml + 64]
            wires[:, 65:] = rows[keep, :L]
        else:
            # ragged lengths: vectorized wire build over a padded
            # (c, 65+Lmax) staging block, chunked so pad + mask stay
            # under _NP_PAD_CAP regardless of the length tail, then
            # per-row sliced copies into the exact-size arena
            Lmax = int(klens.max())
            step = max(1, self._NP_PAD_CAP // (65 + Lmax))
            for c0 in range(0, k, step):
                c1 = min(c0 + step, k)
                kc, lc = keep[c0:c1], klens[c0:c1]
                Lm = int(lc.max())
                wires = np.empty((c1 - c0, 65 + Lm), np.uint8)
                wires[:, 0] = 1
                wires[:, 1:65] = rows[kc, ml:ml + 64]
                body = wires[:, 65:]
                msk = np.arange(Lm)[None, :] < lc[:, None]
                body[msk] = rows[kc, :Lm][msk]
                for j in range(c1 - c0):
                    o = int(offs[c0 + j])
                    arena[o:o + 65 + int(lc[j])] = wires[j, :65 + int(lc[j])]
        return PackedVerdicts(arena, offs, rp.tag[keep].copy(), k)

    def _np_finish_txns(self, rp: _RowsPending, okv,
                        word) -> "PackedVerdicts | None":
        """NumPy finish of a frame with multi-signature txns: a txn is a
        first row (signature index 0) and the rows up to the next; it
        passes iff it has the row count its marker gives and every one of
        its rows passes (the segmented minimum of _finish_burst)."""
        ml, rows = rp.ml, rp.rows
        starts = np.nonzero(((word >> PACKED_SIG_IDX_SHIFT) & 0xFF) == 0)[0]
        if not len(starts):
            return None
        nsig = np.diff(np.r_[starts, rp.n])
        whole = nsig == (word[starts] >> PACKED_SIG_MORE_SHIFT) + 1
        ok = np.minimum.reduceat(okv.astype(np.uint8), starts).astype(
            bool) & whole
        tag, dup = rp.tag[starts], rp.dup[starts]
        live = (tag != 0) & ~dup
        self.metrics.verify_fail += int((live & ~ok).sum())
        pass_idx = np.nonzero(live & ok)[0]
        if len(pass_idx) == 0:
            return None
        dup2 = self._insert(tag[pass_idx])
        self.metrics.dedup_drop += int(dup2.sum())
        keep = pass_idx[~dup2]
        self.metrics.verify_pass += len(keep)
        if len(keep) == 0:
            return None
        lens = np.minimum(word[starts[keep]] & PACKED_LEN_MASK, ml)
        wires = []
        for s0, k, L in zip(starts[keep].tolist(), nsig[keep].tolist(),
                            lens.tolist()):
            wires.append(bytes([k]) + rows[s0:s0 + k, ml:ml + 64].tobytes()
                         + rows[s0, :L].tobytes())
        offs = np.zeros(len(keep) + 1, np.int64)
        np.cumsum([len(w) for w in wires], out=offs[1:])
        arena = np.frombuffer(b"".join(wires), np.uint8).copy()
        return PackedVerdicts(arena, offs, tag[keep].copy(), len(keep))

    def _finish_burst(self, bp: _BurstPending, ok) -> list:
        """Vectorized harvest of one burst record: per-txn verdict via
        segmented minimum over its (contiguous) lanes, then one batched
        tcache insert with exact FD_TCACHE_INSERT dup semantics."""
        k = len(bp.lane0)
        if k == 0:
            return []
        start = int(bp.lane0[0])
        end = int(bp.lane0[-1] + bp.nsig[-1])
        seg = np.asarray(ok[start:end], dtype=np.uint8)
        acc = np.minimum.reduceat(seg, bp.lane0 - start).astype(bool)
        pass_idx = np.nonzero(acc)[0]
        self.metrics.verify_fail += k - len(pass_idx)
        if len(pass_idx) == 0:
            return []
        dup = self._insert(bp.tag[pass_idx])
        self.metrics.dedup_drop += int(dup.sum())
        self.metrics.verify_pass += int((~dup).sum())
        buf = bp.buf
        return [(buf[int(bp.start[i]):int(bp.start[i]) + int(bp.plen[i])],
                 None)
                for i, d in zip(pass_idx, dup) if not d]
