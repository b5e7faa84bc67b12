"""Production tile implementations + registry (ref: the fd_topo_run_tile_t
vtables in src/app/fdctl/run/tiles/ and the TILES[] registry in
src/app/fdctl/main.c:33-48).

A tile is a class with any subset of the mux callbacks (disco/mux.py).  The
registry maps kind -> class; fd_topo_run looks tiles up by TileSpec.kind.

The data plane mirrors the reference's frankendancer flow (SURVEY.md §1):

    source/net -> verify -> dedup -> pack -> bank -> sink

with the TPU twist in the verify tile: txn signatures from many frags are
coalesced into one fixed-shape device batch, flushed on size or age
(wiredancer's async insertion point, SURVEY.md §3.2), instead of the
reference's synchronous per-frag batch-of-<=16 verify.
"""

import os
import struct
import time
from collections import OrderedDict

import numpy as np

from ..ballet import txn as txn_lib
from ..tango.tcache import TCache
from ..utils import log
from . import trace as trace_mod
from .pipeline import (DEFAULT_LAT_SHAPES, LAT_PRIO_BIT, PackedVerdicts,
                       VerifyPipeline)


def source_txn_stream(seed: int, keys: int = 4, count: int = 0,
                      start: int = 0):
    """Regenerate the (tag, wire) stream a standalone non-burst
    SourceTile with cfg {seed, keys, count} publishes, without a
    topology: same rng recipe (key pool, blockhash, program id all
    drawn from default_rng(seed) in init order), same per-txn build.
    The tag is the wire's sig[0:8] LE — exactly the sig the verify
    tile stamps on the frag and the sink capture records.

    This is the fleet layer's replay surface: a failover host adopts a
    dead host's stream by re-running this generator (SourceTile
    `adopt_streams`), and the chaos harness derives the injected-txn
    universe from it for the exactly-once assertion."""
    from ..ops import ed25519 as ed
    rng = np.random.default_rng(int(seed))
    seeds = [rng.bytes(32) for _ in range(int(keys))]
    blockhash = rng.bytes(32)
    pool = [(s, ed.keypair_from_seed(s)[0]) for s in seeds]
    program = rng.bytes(32)
    i = int(start)
    while count == 0 or i < int(count):
        seed_i, pub = pool[i % len(pool)]
        msg = txn_lib.build_unsigned(
            [pub], blockhash, [(1, bytes([0]), i.to_bytes(8, "little"))],
            extra_accounts=[program])
        sig = ed.sign(seed_i, msg)
        yield (int.from_bytes(sig[:8], "little"),
               txn_lib.assemble([sig], msg))
        i += 1


class SourceTile:
    """Synthetic signed-txn generator (the fddev benchg analogue,
    src/app/fddev/tiles/fd_benchg.c): publishes `count` distinct valid
    txns then idles (count=0 -> unbounded).

    Two modes: standalone (default) signs with fresh keys against a random
    blockhash — enough for the verify path; executable=True generates REAL
    system transfers from cfg `seeds` (hex, funded in genesis) against
    cfg `blockhash`, so a downstream bank tile can execute them."""

    def init(self, ctx):
        from ..ops import ed25519 as ed
        cfg = ctx.cfg
        self.count = cfg.get("count", 0)
        self.executable = cfg.get("executable", False)
        self.pool = []
        # blockhash feedback (fddev benchg refreshes its blockhash the
        # same way over RPC): any in-link named *blockhash carries the
        # bank's latest root hash; txns sign against it from then on
        self._bh_ins = {
            i for i, il in enumerate(ctx.tile.in_links)
            if il.link.endswith("blockhash")}
        rng = np.random.default_rng(cfg.get("seed", 42))
        if self.executable:
            from ..flamenco.system_program import ix_transfer
            from ..flamenco.types import SYSTEM_PROGRAM_ID
            self._ix_transfer = ix_transfer
            self._system_id = SYSTEM_PROGRAM_ID
            seeds = [bytes.fromhex(s) for s in cfg["seeds"]]
            self.blockhash = bytes.fromhex(cfg["blockhash"])
        else:
            seeds = [rng.bytes(32) for _ in range(cfg.get("keys", 4))]
            self.blockhash = rng.bytes(32)
        for seed in seeds:
            pub, _, _ = ed.keypair_from_seed(seed)
            self.pool.append((seed, pub))
        self.program = rng.bytes(32)
        self.sent = 0
        self._ed = ed
        self._rng = rng
        # optional pacing (benchg's tps knob): min ns between txns, so
        # feedback topologies exercise refresh cycles instead of racing
        # the whole count out against the boot blockhash
        self.rate_ns = cfg.get("rate_ns", 0)
        self._last_gen_ns = 0
        # with a feedback link, hold generation until the bank's first
        # blockhash heartbeat arrives: txns pre-signed against the boot
        # hash while downstream tiles compile would all age out
        # (benchg's RPC-blockhash-first behaviour)
        self._bh_seen = not (cfg.get("wait_blockhash", True)
                             and self._bh_ins)
        # burst firehose mode (round 4): burst_n > 0 pre-builds one signed
        # template and stamps out `burst_n` txns per loop in numpy — unique
        # signature tag + unique instr data per txn, one native burst
        # publish.  Host signing (1 ms/python-int sign) would cap a source
        # at ~1 K/s; the verify DEVICE cost is identical for the stamped
        # copies because the verify graph is fixed-shape and
        # data-independent, so this is the honest firehose for throughput
        # work.
        # NOTE: every stamped txn fails sigverify (the tag overwrite
        # invalidates each row's signature), so nothing flows PAST the
        # verify tile — burst_n measures ingest->verify throughput at the
        # verify tiles' own counters; topologies needing executable flow
        # downstream use executable=True without burst_n.
        self._burst_n = int(cfg.get("burst_n", 0))
        # latency-class tagging (round 9): every `lat_every`-th txn is
        # published with LAT_PRIO_BIT set on its frag meta sig, marking
        # it for the verify tile's low-latency lane — the mixed
        # bulk+latency load the dual-lane bench and CI smoke drive.  0
        # (default) = no tagging.  The bit rides the META only; payload
        # sig bytes (the dedup tag) stay the clean value.  Packed-wire
        # mode stays bulk-only: one frag is one whole device blob, so a
        # per-txn class bit has no sub-frag routing to do there.
        self._lat_every = max(0, int(cfg.get("lat_every", 0)))
        # fleet failover adoption (round 17): `adopt_streams` is a list of
        # {"seed", "keys", "count"} stream specs from dead hosts; their
        # txns are regenerated (source_txn_stream) and published FIRST —
        # the in-flight work a failover host takes over.  Already-verified
        # sigs among them are rejected downstream (dedup preload /
        # verify tcache), so adoption never double-verdicts.
        self._adopt = []
        for st in (cfg.get("adopt_streams") or []):
            self._adopt.append(source_txn_stream(
                int(st["seed"]), int(st.get("keys", 4)),
                int(st.get("count", 0))))
        if self._burst_n:
            tpl = np.frombuffer(self._make_txn(0), np.uint8).copy()
            self._tpl = tpl
            self._tpl_len = len(tpl)
        # packed-wire firehose (round 8): the source writes frags ALREADY
        # in device-blob row layout (msg | sig64 | pub32 | len-le32, row
        # stride chunk-aligned via packed_row_ml) straight into the dcache
        # through ctx.out_reserve — one frag = one packed burst of
        # `packed_rows` rows, meta.sz carries the row count.  Downstream
        # the verify tile dispatches the dcache region as the device blob
        # with ZERO payload copies in between.  Same honesty note as
        # burst_n: tag stamping invalidates each row's signature.
        self._packed_rows = int(cfg.get("packed_rows", 0))
        if self._packed_rows:
            from ..tango.ring import PACKED_ROW_EXTRA, packed_row_ml
            ml = int(cfg.get("packed_ml") or packed_row_ml(256))
            stride = ml + PACKED_ROW_EXTRA
            wire = self._make_txn(0)
            msg, sig = wire[65:], wire[1:65]
            if len(msg) > ml:
                raise ValueError(
                    f"template msg {len(msg)}B exceeds packed ml {ml}")
            row = np.zeros(stride, np.uint8)
            row[:len(msg)] = np.frombuffer(msg, np.uint8)
            row[ml:ml + 64] = np.frombuffer(sig, np.uint8)
            row[ml + 64:ml + 96] = np.frombuffer(self.pool[0][1], np.uint8)
            row[ml + 96:ml + 100] = np.frombuffer(
                len(msg).to_bytes(4, "little"), np.uint8)
            self._row_tpl = row
            self._packed_ml = ml
            self._row_stride = stride
            self._msg_len = len(msg)
            # round-robin burst splitter: emit `burst_splits` frags per
            # loop so consecutive seqs deal rows across rr verify tiles
            # instead of one tile swallowing a whole mega-burst
            self._splits = max(1, int(cfg.get("burst_splits", 1)))

    def apply_knobs(self, ctx, vals):
        """Autotune pod application (disco/autotune.py KNOBS['source'])."""
        if "burst_splits" in vals and self._packed_rows:
            self._splits = max(1, int(vals["burst_splits"]))

    def _make_txn(self, i: int) -> bytes:
        seed, pub = self.pool[i % len(self.pool)]
        if self.executable:
            # nonzero prefix: dest must never collide with the all-zeros
            # system program id (duplicate account addresses in one txn)
            dest = b"\xd5" + bytes(15) + i.to_bytes(16, "little")
            msg = txn_lib.build_unsigned(
                [pub], self.blockhash,
                [(2, bytes([0, 1]), self._ix_transfer(1000 + i))],
                extra_accounts=[dest, self._system_id],
                readonly_unsigned_cnt=1)
        else:
            data = i.to_bytes(8, "little")  # distinct payload per i
            msg = txn_lib.build_unsigned(
                [pub], self.blockhash,
                [(1, bytes([0]), data)], extra_accounts=[self.program])
        sig = self._ed.sign(seed, msg)
        return txn_lib.assemble([sig], msg)

    def on_frag(self, ctx, iidx, meta, payload):
        if iidx in self._bh_ins and len(payload) >= 32:
            self.blockhash = bytes(payload[:32])
            self._bh_seen = True
            ctx.metrics.add("blockhash_refresh_cnt")

    def after_credit(self, ctx):
        if self._adopt:
            # adopted (failover) streams drain before our own resumes:
            # the dead host's in-flight work is the urgent half
            if self.rate_ns:
                now = time.monotonic_ns()
                if now - self._last_gen_ns < self.rate_ns:
                    return
                self._last_gen_ns = now
            try:
                tag, wire = next(self._adopt[0])
            except StopIteration:
                self._adopt.pop(0)
                return
            ctx.publish(wire, sig=tag & (LAT_PRIO_BIT - 1))
            ctx.metrics.add("adopt_pub_cnt")
            return
        if not self._bh_seen or (self.count and self.sent >= self.count):
            return
        if self.rate_ns:
            now = time.monotonic_ns()
            if now - self._last_gen_ns < self.rate_ns:
                return
            self._last_gen_ns = now
        if self._packed_rows:
            self._gen_packed(ctx)
            return
        if self._burst_n:
            n = self._burst_n
            if self.count:
                n = min(n, self.count - self.sent)
            L = self._tpl_len
            arr = np.tile(self._tpl, (n, 1))
            # unique tag (first 8 sig bytes) + unique instr data (last 8
            # payload bytes) per txn; the tag doubles as the app sig
            tags = self._rng.integers(1, 1 << 63, size=n, dtype=np.uint64)
            arr[:, 1:9] = tags.view(np.uint8).reshape(n, 8)
            arr[:, L - 8:] = np.arange(
                self.sent, self.sent + n, dtype=np.uint64
            ).view(np.uint8).reshape(n, 8)
            starts = np.arange(n, dtype=np.int64) * L
            lens = np.full(n, L, dtype=np.int32)
            mtags = tags
            if self._lat_every:
                mtags = tags.copy()
                mtags[::self._lat_every] |= np.uint64(LAT_PRIO_BIT)
            ctx.publish_burst(arr, starts, lens, mtags)
            self.sent += n
            ctx.metrics.add("txn_gen_cnt", n)
            return
        payload = self._make_txn(self.sent)
        # mask bit 63 — raw signature bytes are uniform, and a random
        # high bit must never read as a latency-class tag downstream
        sig64 = (int.from_bytes(payload[1:9], "little")
                 & (LAT_PRIO_BIT - 1))
        if self._lat_every and self.sent % self._lat_every == 0:
            sig64 |= LAT_PRIO_BIT
        ctx.publish(payload, sig=sig64)
        self.sent += 1
        ctx.metrics.add("txn_gen_cnt")

    def _gen_packed(self, ctx):
        """Stamp packed-blob frags in place in the out dcache: reserve the
        region, np.tile the template row into the shm view, overwrite tag
        + instr-data lanes, zero-pad a short tail, commit.  No staging
        buffer — the dcache bytes ARE the device blob."""
        rows, ml, stride = self._packed_rows, self._packed_ml, \
            self._row_stride
        L = stride
        for _ in range(self._splits):
            n = rows
            if self.count:
                n = min(n, self.count - self.sent)
            if n <= 0:
                return
            chunk, blk = ctx.out_reserve(rows * stride)
            if blk is None:        # halted mid-backpressure
                return
            blk = blk.reshape(rows, stride)
            np.copyto(blk[:n], self._row_tpl)
            tags = self._rng.integers(1, 1 << 63, size=n, dtype=np.uint64)
            blk[:n, ml:ml + 8] = tags.view(np.uint8).reshape(n, 8)
            blk[:n, L - 8:] = np.arange(
                self.sent, self.sent + n, dtype=np.uint64
            ).view(np.uint8).reshape(n, 8)
            if n < rows:
                blk[n:] = 0        # zero sig -> tag 0 -> dead lane
            ctx.out_commit(chunk, rows * stride, sig=int(tags[0]), sz=n)
            self.sent += n
            ctx.metrics.add("txn_gen_cnt", n)


class VerifyTile:
    """The verify tile (ref: src/app/fdctl/run/tiles/fd_verify.c).

    Round-robin data parallel: instance r of n keeps frags with
    seq % n == r (fd_verify.c:36-47).  Parse -> tcache pre-dedup ->
    fixed-shape device batch verify -> publish passing txns downstream with
    sig = low 64 bits of the first signature (the dedup tile's key).
    """

    def init(self, ctx):
        from ..ops import ed25519 as ed
        from ..utils import xla_cache
        import jax
        import jax.numpy as jnp
        xla_cache.enable()
        cfg = ctx.cfg
        self.rr_cnt = cfg.get("round_robin_cnt", 1)
        self.rr_idx = cfg.get("round_robin_idx", 0)
        batch = cfg.get("batch", 64)
        maxlen = cfg.get("msg_maxlen", 256)
        # multi-bucket ladder (full-MTU coverage): cfg buckets = [[b, l],...]
        buckets = cfg.get("buckets") or [[batch, maxlen]]
        self.flush_age_ns = cfg.get("flush_age_ns", 2_000_000)
        # dp-mesh serving path (round 7): dp_shards > 1 swaps the whole
        # verifier for a mesh-mode SigVerifier — each bucket's batch axis
        # shards P("dp", None) over the device mesh and dispatches the
        # donated shard_map step (parallel.mesh.shard_verify_blob).  The
        # AOT store holds single-chip executables only, so the sharded
        # tile boots from jit + the persistent XLA cache instead.
        self.dp_shards = int(cfg.get("dp_shards", 1))
        # dual-lane dispatch (round 9): [latency] enables a deadline-
        # driven low-latency lane of small pre-warmed shapes beside the
        # throughput buckets; latency-class frags carry LAT_PRIO_BIT in
        # the frag meta sig (priority admission)
        latc = cfg.get("latency") or {}
        self._lat_enabled = bool(int(latc.get("enabled", 0)))
        if self._lat_enabled and self.dp_shards > 1:
            # each ladder shape would need its own sharded program; keep
            # the dp-mesh path bulk-only until that lands
            log.warning("[latency] disabled: dp_shards=%d mesh verifier "
                        "is bulk-only", self.dp_shards)
            self._lat_enabled = False
        self._latc = latc
        lat_shapes = (tuple(int(s) for s in
                            (latc.get("shapes") or DEFAULT_LAT_SHAPES))
                      if self._lat_enabled else ())
        lat_ml = min(int(m) for _, m in buckets)
        lat_warm = [(s, lat_ml) for s in sorted(lat_shapes)]
        if self.dp_shards > 1:
            from ..models.verifier import SigVerifier, VerifierConfig
            from ..parallel import mesh as pm
            b0, ml0 = buckets[0]
            fn = SigVerifier(VerifierConfig(batch=b0, msg_maxlen=ml0),
                             mesh=pm.make_mesh(self.dp_shards))
        else:
            fn = self._make_single_chip_fn(cfg, buckets, lat_warm)
        self._init_pipeline(ctx, cfg, fn, buckets, lat_warm)
        # which device the verify graphs run on: a served path that lands
        # on the CPU must be visible from outside the process, and is a
        # warning unless the operator asked for the CPU
        from .metrics import DEVICE_PLATFORMS
        from .topo import cpu_pinned
        dev = jax.devices()[0]
        ctx.metrics.set("device_platform",
                        1 + DEVICE_PLATFORMS.index(dev.platform)
                        if dev.platform in DEVICE_PLATFORMS else 0)
        ctx.metrics.set("device_cnt", len(jax.devices()))
        (log.notice if dev.platform == "tpu" or cpu_pinned()
         else log.warning)(
            "verify device: platform=%s kind=%s count=%d", dev.platform,
            dev.device_kind, len(jax.devices()))

    def _make_single_chip_fn(self, cfg, buckets, lat_warm=()):
        from ..ops import ed25519 as ed
        import jax
        # AOT-first boot (VERDICT r4 #2): per-bucket serialized executables
        # load in ~1 s where trace+lower+compile takes minutes on a
        # contended core.  aot_require makes a miss FATAL — a spawn-context
        # tile silently cold-compiling is exactly the boot-timeout failure
        # the bench must never reproduce.
        from ..utils import aot
        aot_dir = cfg.get("aot_dir") or os.environ.get("FDTPU_AOT_DIR")
        compiled = {}          # (b, ml) -> 4-array executable
        packed = {}            # (b, ml) -> packed-blob executable
        if aot_dir:
            for b, ml in buckets:
                fp = aot.load(aot_dir, aot.key("verify-packed", b, ml))
                if fp is not None:
                    packed[(b, ml)] = fp
        # packed dispatch is all-or-nothing: the pipeline lays EVERY
        # bucket out row-interleaved once dispatch_blob exists, so a
        # partial packed set must fall back wholesale (a mixed state
        # previously left jit_fn None for packed-only buckets)
        if len(packed) != len(buckets):
            packed = {}
            if aot_dir:
                for b, ml in buckets:
                    f = aot.load(aot_dir, aot.key("verify", b, ml))
                    if f is not None:
                        compiled[(b, ml)] = f
        elif aot_dir:
            # opportunistic AOT for the low-latency ladder's small shapes;
            # misses fall back to the jit path below (warmed at boot, so
            # still no hot-path compile)
            for b, ml in lat_warm:
                f = aot.load(aot_dir, aot.key("verify-packed", b, ml))
                if f is not None:
                    packed[(b, ml)] = f
        missing = [] if packed else [
            tuple(b) for b in buckets if tuple(b) not in compiled]
        if missing and cfg.get("aot_require"):
            raise RuntimeError(
                f"verify tile refusing to cold-compile {missing}: no AOT "
                f"executable in {aot_dir!r} (run utils.aot.ensure_verify "
                f"before boot or drop aot_require)")
        # the lat ladder dispatches shapes outside the bucket set, so a
        # shape-polymorphic fallback must exist even when every bucket
        # is AOT-covered
        jit_fn = (jax.jit(ed.verify_batch)
                  if missing or (lat_warm and not packed) else None)

        class _Fn:
            """Pipeline-facing verifier: packed single-blob dispatch when
            every bucket has a packed AOT executable (the pipeline then
            lays its buckets out row-interleaved and uploads one blob),
            4-array dispatch otherwise.  Shapes outside the AOT set (the
            low-latency ladder) jit-compile once per shape — at boot
            warmup, never on the hot path."""

            _blob_jit = {}

            def __call__(self, msgs, lens, sigs, pubs):
                f = compiled.get((msgs.shape[0], msgs.shape[1]))
                return f(msgs, lens, sigs, pubs) if f is not None \
                    else jit_fn(msgs, lens, sigs, pubs)

            if packed:
                def dispatch_blob(self, blob, maxlen=None):
                    if maxlen is None:
                        maxlen = blob.shape[1] - ed.PACKED_EXTRA
                    f = packed.get((blob.shape[0], maxlen))
                    if f is not None:
                        return f(blob)
                    key = (blob.shape[0], maxlen)
                    jf = self._blob_jit.get(key)
                    if jf is None:
                        from functools import partial
                        jf = jax.jit(partial(ed.verify_blob,
                                             maxlen=maxlen, ml=maxlen))
                        self._blob_jit[key] = jf
                    return jf(np.asarray(blob))

        return _Fn()

    def _init_pipeline(self, ctx, cfg, fn, buckets, lat_warm=()):
        from ..ops import ed25519 as ed
        import jax.numpy as jnp

        # packed-wire mode (round 8): frag payloads arrive ALREADY in
        # device-blob row layout in the dcache; dispatch needs a blob
        # entry point even when no packed AOT executable is on disk
        self._packed_wire = bool(cfg.get("packed_wire", 0))
        if self._packed_wire and not hasattr(fn, "dispatch_blob"):
            fn = _jit_blob_fn(fn)
        latc = getattr(self, "_latc", None) or cfg.get("latency") or {}
        self._lat_enabled = getattr(self, "_lat_enabled", False)

        # warmup before signaling RUN: compiles any non-AOT bucket (the
        # graph can take minutes to build cold, and the run loop must never
        # stall that long — the supervisor would flag a stale heartbeat)
        # and primes the transfer path for AOT ones.  The low-latency
        # ladder's shapes warm here too: deadline closes dispatch
        # pre-warmed shapes only, so no compile storm can land on the
        # hot path (the no-compile contract the latency smoke gates on).
        warm_shapes = [(int(b), int(ml)) for b, ml in buckets]
        warm_shapes += [(int(b), int(ml)) for b, ml in lat_warm]
        # poke the cnc heartbeat between ladder rungs: a large shape
        # ladder compiling cold can exceed heartbeat_timeout_s, and a
        # supervisor killing a tile MID-COMPILE restarts the compile from
        # scratch — a livelock, not a recovery (same contract as
        # utils/aot._poke on the pre-spawn ensure paths)
        hb = getattr(ctx, "heartbeat", None)
        for b, ml in warm_shapes:
            if hb is not None:
                hb()
            if hasattr(fn, "dispatch_blob"):
                fn.dispatch_blob(np.zeros(
                    (b, ml + ed.PACKED_EXTRA),
                    np.uint8)).block_until_ready()
            else:
                fn(jnp.zeros((b, ml), jnp.uint8),
                   jnp.zeros((b,), jnp.int32),
                   jnp.zeros((b, 64), jnp.uint8),
                   jnp.zeros((b, 32), jnp.uint8)).block_until_ready()
        if hb is not None:
            hb()
        # self-healing dispatch (AFTER warmup: warmup failures must stay
        # fatal boot failures, not silently degrade a fresh tile): bounded
        # retries, verdict deadline, CPU ed25519 fallback after N
        # consecutive device failures, periodic re-probe.  The wrapper
        # preserves the duck-typed surface (dispatch_blob presence)
        # the pipeline autodetects packed layout from.
        from .pipeline import GuardedVerifier
        sup = cfg.get("supervision") or {}
        # the mux already armed this tile's FaultInjector (or None); share
        # it so the whole tile runs ONE deterministic fault stream
        mux = getattr(ctx, "_mux", None)
        self.guard = GuardedVerifier(
            fn,
            fail_threshold=int(sup.get("device_fail_threshold", 3)),
            retries=int(sup.get("device_retry", 1)),
            deadline_s=float(sup.get("device_deadline_s", 30.0)),
            reprobe_s=float(sup.get("device_reprobe_s", 5.0)),
            fault=getattr(mux, "fault", None))
        fn = self.guard
        self.pipe = VerifyPipeline(
            fn, buckets=[tuple(b) for b in buckets],
            tcache_depth=cfg.get("tcache_depth", 1 << 16),
            dp_shards=self.dp_shards,
            # async data plane by default (wiredancer's contract): filled
            # buckets dispatch without blocking the mux loop; verdicts are
            # harvested in after_credit once the device completes them
            max_inflight=cfg.get("max_inflight", 8),
            # packed-blob rotation depth (upload/compute double buffering):
            # a flushed blob stays pinned until its verdict lands while the
            # next batch packs into a pool blob
            n_buffers=cfg.get("n_buffers", 3),
            # fdtrace: coalesce/device/compile spans land in this tile's
            # shm trace ring next to the mux's frag/burst spans
            tracer=ctx.trace,
            # heartbeat through blocking device waits (flush/_finish):
            # a long in-flight batch must not read as a dead tile, and
            # HALT must still land mid-wait
            heartbeat_cb=getattr(ctx, "heartbeat", None),
            # low-latency lane (round 9): deadline-driven small-shape
            # dispatch beside the throughput buckets
            lat_shapes=[b for b, _ in lat_warm] or None,
            deadline_us=int(latc.get("deadline_us", 2000)),
            lat_max_inflight=int(latc.get("max_inflight", 2)),
            lat_spill_age_factor=float(latc.get("spill_age_factor", 4.0)),
            # round 11: one-pass C submit/harvest ([ingest] native_hostpath;
            # None defers to the FDTPU_INGEST_NATIVE_HOSTPATH env default)
            # and packed verdict egress (one arena frag per harvest instead
            # of per-txn frags; needs the dedup tile's packed_egress mode)
            native_hostpath=(None if cfg.get("native_hostpath") is None
                             else bool(cfg.get("native_hostpath"))),
            egress_packed=bool(cfg.get("egress_packed", 0)))
        # every shape above went through the verifier before the pipeline
        # existed — their first pipeline dispatch is not a compile
        self.pipe.mark_warm(warm_shapes)
        self._last_submit_ns = 0
        self._synced_batches = -1
        # optional XLA-level capture: FDTPU_JAX_TRACE_DIR=<dir> wraps the
        # tile's run after warmup in a jax.profiler trace (TensorBoard-
        # loadable) with the program's own profiler options, and names
        # the tile's host states in it (trace.start_capture); off by
        # default — it is NOT free like the shm span rings
        self._jax_trace_dir = cfg.get("jax_trace_dir") or os.environ.get(
            "FDTPU_JAX_TRACE_DIR")
        if self._jax_trace_dir:
            trace_mod.start_capture(self._jax_trace_dir)
        # burst data plane (round 4): frags drain from the ring via one
        # native call (mux on_burst path) with the round-robin filter
        # applied AT the ring, and passing txns publish via one burst
        # publish — the scalar per-frag path remains for cfg burst=False
        # (tests of the before_frag contract).
        self._burst = cfg.get("burst", True)
        if self._packed_wire:
            # zero-copy rx: the mux's on_burst_view path hands this tile
            # metas + the raw dcache; hide on_burst so the mux does NOT
            # allocate its BURST_RX*mtu rx scratch (a packed link's mtu is
            # batch*stride — hundreds of KB — and the scratch would be
            # BURST_RX times that)
            self.on_burst = None
            self.burst_rr = (self.rr_cnt, self.rr_idx)
            b0, ml0 = buckets[0]
            self._pw_batch = int(b0)
            self._pw_ml = int(ml0)
            self._pw_stride = int(ml0) + ed.PACKED_EXTRA
            self._held = {}        # iidx -> frags pinned awaiting verdict
            ctx.metrics.set("row_ml", self._pw_ml)
        elif self._burst:
            self.on_burst_view = None
            self.burst_rr = (self.rr_cnt, self.rr_idx)
        else:
            # hide both vtable hooks from the mux
            self.on_burst = None
            self.on_burst_view = None

    def before_frag(self, ctx, iidx, seq, sig) -> bool:
        return (seq % self.rr_cnt) != self.rr_idx

    def apply_knobs(self, ctx, vals):
        """Autotune pod application (disco/autotune.py KNOBS['verify']).
        Every target here is re-read on its hot path each call, so the
        new value is live from the next batch onward — no respawn."""
        if "flush_age_ns" in vals:
            self.flush_age_ns = max(1, int(vals["flush_age_ns"]))
        pipe = getattr(self, "pipe", None)
        if pipe is None:
            return
        if "max_inflight" in vals:
            pipe.max_inflight = max(1, int(vals["max_inflight"]))
        if "lat_max_inflight" in vals:
            pipe.lat_max_inflight = max(1, int(vals["lat_max_inflight"]))
        if "deadline_us" in vals:
            new = max(1, int(vals["deadline_us"]))
            old = max(1, int(pipe.deadline_us))
            # the spill age was derived as factor * deadline at init;
            # preserve the implied factor across deadline moves
            factor = pipe.lat_spill_age_ns / (old * 1000)
            pipe.deadline_us = new
            pipe.lat_spill_age_ns = int(factor * new * 1000)

    def _forward(self, ctx, passed):
        if self._burst:
            return self._forward_burst(ctx, passed)
        if not passed:
            return
        t0 = time.monotonic_ns()
        for payload, parsed in passed:
            # first sig's low 64 bits: signature_off is 1 for every
            # wire-valid txn (1-byte sig count prefix)
            tag = int.from_bytes(payload[1:9], "little")
            ctx.publish(payload, sig=tag)
        if ctx.trace is not None:
            ctx.trace.record(trace_mod.KIND_PUBLISH, t0,
                             time.monotonic_ns() - t0, cnt=len(passed))

    def _forward_burst(self, ctx, passed):
        """One native burst publish for all passing txns.  Packed verdict
        egress (round 11): a PackedVerdicts entry ships as ONE arena frag
        instead of k per-txn frags.  `fdtpu.verify.publish` in a
        device-trace capture."""
        if not passed:
            return
        if trace_mod.annot is not None:
            with trace_mod.annot("fdtpu.verify.publish"):
                return self._publish_burst(ctx, passed)
        return self._publish_burst(ctx, passed)

    def _publish_burst(self, ctx, passed):
        if any(isinstance(p, PackedVerdicts) for p in passed):
            for pv in passed:
                if isinstance(pv, PackedVerdicts):
                    self._publish_packed_verdicts(ctx, pv)
            passed = [p for p in passed
                      if not isinstance(p, PackedVerdicts)]
            if not passed:
                return
        import numpy as np
        t0 = time.monotonic_ns()
        bufs = [p for p, _ in passed]
        joined = b"".join(bufs)
        lens = np.array([len(b) for b in bufs], np.int32)
        starts = np.zeros(len(bufs), np.int64)
        np.cumsum(lens[:-1], out=starts[1:])
        sigs = np.array([int.from_bytes(b[1:9], "little") for b in bufs],
                        np.uint64)
        ctx.publish_burst(joined, starts, lens, sigs)
        if ctx.trace is not None:
            ctx.trace.record(trace_mod.KIND_PUBLISH, t0,
                             time.monotonic_ns() - t0, cnt=len(passed))

    def _publish_packed_verdicts(self, ctx, pv):
        """Stamp one harvest's passing wires downstream as a single packed
        frag: u32 offsets table (k+1 entries) then the wires back to back,
        written straight into the out dcache via out_reserve (the round-8
        ingest stamping idiom).  meta.sz = survivor count k (byte sizes
        overflow the u16 field); meta.sig = first survivor's tag, bit 63
        masked so arena frags never alias latency-class admission;
        meta.tsorig = the packed frame's origin, so the chain runs on from
        the oldest row's receive and not from this harvest."""
        t0 = time.monotonic_ns()
        hdr = 4 * (pv.k + 1)
        nb = hdr + int(pv.offs[pv.k])
        chunk, blk = ctx.out_reserve(nb)
        if blk is None:
            return  # halted while backpressured
        blk[:hdr].view(np.uint32)[:] = pv.offs
        blk[hdr:nb] = pv.arena
        sig0 = int(pv.tags[0]) & (LAT_PRIO_BIT - 1)
        ctx.out_commit(chunk, nb, sig=sig0, sz=pv.k, tsorig=pv.tsorig)
        if ctx.trace is not None:
            ctx.trace.record(trace_mod.KIND_PUBLISH, t0,
                             time.monotonic_ns() - t0, cnt=pv.k, seq=pv.seq)

    def on_frag(self, ctx, iidx, meta, payload):
        # priority admission: the producer's latency-class bit rides the
        # frag meta sig (meta-field threading, round 8 precedent: meta.sz)
        lat = bool(self._lat_enabled and (int(meta["sig"]) & LAT_PRIO_BIT))
        passed = self.pipe.submit(payload, lat=lat)
        self._last_submit_ns = time.monotonic_ns()
        self._forward(ctx, passed)
        self._sync_metrics(ctx)

    def on_burst(self, ctx, iidx, metas, buf, offs, kept):
        # zero-copy handoff: the ring rx scratch (buf, offs) feeds the
        # native parser directly; the pipeline copies the region once
        if self._lat_enabled and kept:
            prio = (metas["sig"][:kept].astype(np.uint64)
                    & np.uint64(LAT_PRIO_BIT)) != 0
            if prio.any():
                passed = self._submit_burst_split(buf, offs, kept, prio)
                self._last_submit_ns = time.monotonic_ns()
                self._forward_burst(ctx, passed)
                self._sync_metrics(ctx)
                return
        passed = self.pipe.submit_burst(packed=(buf, offs[:kept + 1]))
        self._last_submit_ns = time.monotonic_ns()
        self._forward_burst(ctx, passed)
        self._sync_metrics(ctx)

    def _submit_burst_split(self, buf, offs, kept, prio):
        """Mixed-class burst: latency-class txns (LAT_PRIO_BIT set in the
        frag meta sig) go scalar into the low-latency lane; the bulk runs
        between them keep the native packed-window path (submit_burst
        accepts any contiguous offs subrange).  Latency traffic is sparse
        by design, so the scalar hops are rare."""
        passed = []
        i = 0
        while i < kept:
            if prio[i]:
                passed += self.pipe.submit(
                    bytes(buf[offs[i]:offs[i + 1]]), lat=True)
                i += 1
            else:
                j = i
                while j < kept and not prio[j]:
                    j += 1
                passed += self.pipe.submit_burst(
                    packed=(buf, offs[i:j + 1]))
                i = j
        return passed

    def credits_held(self, iidx: int) -> int:
        """Frags this tile has consumed but still pins in the dcache (a
        low-latency frame's device call reads the shm view until its
        verdict lands) — the mux subtracts this from the fseq so the
        producer can't overwrite."""
        held = getattr(self, "_held", None)
        return held.get(iidx, 0) if held else 0

    def on_burst_view(self, ctx, iidx, metas, dcache):
        """Packed-wire rx: each meta is one packed frag of meta.sz rows
        already laid out as device-blob rows in the dcache.  A bulk frame's
        rows are copied into the pipeline's open call buffer and its flow
        credit returns at once; frames that arrive while the device queue
        is full merge into one call.  A low-latency frame is dispatched
        from the shm view in place, its credit held (credits_held) until
        its verdict materializes.  Either way the mcache seq is re-checked
        once the rows are read, so a torn read can never produce a verdict
        (no-torn-buffer invariant)."""
        b, stride = self._pw_batch, self._pw_stride
        mc = ctx.in_mcache(iidx)
        held = self._held
        for meta in metas:
            rows = dcache.rows(int(meta["chunk"]), b, stride)
            # pin BEFORE submit: a bulk frame releases inside, once copied
            held[iidx] = held.get(iidx, 0) + 1

            def _release(iidx=iidx):
                held[iidx] -= 1

            lat = bool(self._lat_enabled
                       and (int(meta["sig"]) & LAT_PRIO_BIT))
            passed = self.pipe.submit_packed_rows(
                rows, n=int(meta["sz"]),
                guard=(mc, int(meta["seq"])), release_cb=_release, lat=lat,
                tsorig=int(meta["tsorig"]) or int(meta["tspub"]))
            if passed:
                self._forward_burst(ctx, passed)
        self._last_submit_ns = time.monotonic_ns()
        self._sync_metrics(ctx)

    def after_credit(self, ctx):
        # batch-close-on-deadline (round 9): the low-latency lane's own
        # fine-grained age check runs every loop — independent of the
        # coarse flush_age_ns below, which bounds the bulk lane — so the
        # open lat batch ships the moment its oldest txn ages out
        if self._lat_enabled and self.pipe.lat_due():
            self._forward(ctx, self.pipe.dispatch_due())
        # harvest completed device batches first — never blocks
        passed = self.pipe.harvest()
        if passed:
            self._forward(ctx, passed)
        # sync on every completed batch, not only on passing ones: an
        # all-fail batch (e.g. the burst firehose's stamped sigs) must
        # still surface its verify_fail_cnt
        if self.pipe.metrics.batches != self._synced_batches:
            self._synced_batches = self.pipe.metrics.batches
            self._sync_metrics(ctx)
        # age-based flush: bound batch latency when inflow stalls
        # (BASELINE p99 < 2ms requires closing partial batches).  Async
        # mode only DISPATCHES the partial bucket; results surface on a
        # later harvest, so the mux loop still never waits on the device.
        # Gate on has_open (undispatched txns), not has_pending: inflight
        # batches only need harvesting, and re-firing dispatch_open while
        # they drain is a no-op busy loop (ADVICE r3).
        if (self.pipe.has_open
                and time.monotonic_ns() - self._last_submit_ns
                > self.flush_age_ns):
            if self.pipe.max_inflight:
                self._forward(ctx, self.pipe.dispatch_open())
            else:
                self._forward(ctx, self.pipe.flush())
            self._last_submit_ns = time.monotonic_ns()
            self._sync_metrics(ctx)

    def _sync_metrics(self, ctx):
        s = self.pipe.metrics
        ctx.metrics.set("txn_in_cnt", s.txns_in)
        ctx.metrics.set("parse_fail_cnt", s.parse_fail)
        ctx.metrics.set("dedup_drop_cnt", s.dedup_drop)
        ctx.metrics.set("too_long_cnt", s.too_long_drop)
        ctx.metrics.set("verify_fail_cnt", s.verify_fail)
        ctx.metrics.set("verify_pass_cnt", s.verify_pass)
        ctx.metrics.set("torn_drop_cnt", s.torn_drop)
        ctx.metrics.set("torn_txn_cnt", s.torn_txns)
        ctx.metrics.set("batch_cnt", s.batches)
        ctx.metrics.set("compile_cnt", s.compile_cnt)
        ctx.metrics.set("compile_ns", s.compile_ns)
        ctx.metrics.set("lanes_filled_cnt", s.lanes_filled)
        ctx.metrics.set("lanes_dispatched_cnt", s.lanes_dispatched)
        ctx.metrics.set("bucket_fill_pct", s.last_fill_pct)
        ctx.metrics.set("inflight_depth",
                        len(self.pipe.inflight) + len(self.pipe.lat_inflight))
        # dual-lane dispatch (round 9)
        ctx.metrics.set("lat_txn_cnt", s.lat_txns)
        ctx.metrics.set("lat_spill_cnt", s.lat_spill)
        ctx.metrics.set("lat_batch_cnt", s.lat_batches)
        ctx.metrics.set("lat_deadline_close_cnt", s.lat_deadline_closes)
        ctx.metrics.set("verdict_wait_ns", s.verdict_wait_ns)
        ctx.metrics.set("msg_bytes_cnt", s.msg_bytes)
        ctx.metrics.set("multisig_txn_cnt", s.multisig_txns)
        ctx.metrics.set("coalesced_frame_cnt", s.coalesced_frames)
        # self-healing dispatch health (GuardedVerifier): the degraded
        # gauge is what flips /healthz from "ok" to "degraded"
        g = self.guard
        ctx.metrics.set("degraded_mode", 1 if g.degraded else 0)
        ctx.metrics.set("device_fail_cnt", g.device_fail_cnt)
        ctx.metrics.set("fallback_lane_cnt", g.fallback_lanes)
        ctx.metrics.set("reprobe_cnt", g.reprobe_cnt)
        ctx.metrics.set("fallback_vps", g.fallback_vps())
        # shm histograms: full decomposition distributions, not just the
        # derived scalars — /metrics exports them as native Prometheus
        # le-bucketed histograms
        ctx.metrics.hist_store("batch_ns", s.batch_ns)
        ctx.metrics.hist_store("coalesce_ns", s.coalesce_ns)
        ctx.metrics.hist_store("lat_e2e_ns", s.lat_e2e_ns)

    def drain(self, ctx) -> bool:
        """Drain-protocol hook (mux SIGNAL_DRAIN): run the pipeline dry.
        Each poll dispatches every open bucket + the lat accumulator
        (dispatch_open covers both lanes) and harvests completed device
        batches non-blocking, publishing their verdicts downstream; the
        mux keeps heartbeating between polls so a multi-batch backlog
        can't read as a stale tile.  Returns True once nothing is open
        and nothing is in flight — every accepted txn verdicted."""
        pipe = getattr(self, "pipe", None)
        if pipe is None:
            return True
        if pipe.has_open:
            self._forward(ctx, pipe.dispatch_open())
        passed = pipe.harvest()
        if passed:
            self._forward(ctx, passed)
        if pipe.has_pending:
            return False
        self._sync_metrics(ctx)
        return True

    def fini(self, ctx):
        try:
            self._forward(ctx, self.pipe.flush())
            self._sync_metrics(ctx)
        except Exception:
            pass
        if self._jax_trace_dir:
            try:
                trace_mod.stop_capture()
            except Exception:
                pass


def _jit_blob_fn(base):
    """Wrap a 4-array verifier with a jit packed-blob entry point: the
    packed-wire tile dispatches dcache rows as one device blob, which
    needs dispatch_blob even when no packed AOT executable is on disk
    (first call per shape compiles; the persistent XLA cache and the
    warmup in _init_pipeline keep that off the hot loop)."""
    from functools import partial
    import jax
    from ..ops import ed25519 as ed

    class _BlobFn:
        def __init__(self):
            self._cache = {}

        def __call__(self, *a):
            return base(*a)

        def dispatch_blob(self, blob, maxlen=None):
            ml = (blob.shape[1] - ed.PACKED_EXTRA
                  if maxlen is None else maxlen)
            key = (blob.shape[0], ml)
            f = self._cache.get(key)
            if f is None:
                f = jax.jit(partial(ed.verify_blob, maxlen=ml, ml=ml))
                self._cache[key] = f
            return f(np.asarray(blob))

    return _BlobFn()


def _sock_backend(cfg):
    """Socket backend selection (ref: the xdp-vs-udpsock choice in
    fd_topo config): "native" = C++ recvmmsg/sendmmsg burst engine
    (waltz.pkteng), default = python sockets (waltz.udpsock)."""
    if cfg.get("backend") == "native":
        from ..waltz.pkteng import NativeUdpSock
        return NativeUdpSock
    from ..waltz.udpsock import UdpSock
    return UdpSock


# why _PackedWirePublisher.add drops a wire txn: its quic counter
# packed_drop_<reason>_cnt
DROP_PARSE, DROP_SIGS, DROP_LONG = "parse", "sigs", "long"


def _wire_row(wire: bytes, ml: int, max_sigs: int = txn_lib.ACTUAL_SIG_MAX):
    """Locate the packed-row fields of one wire txn: (signature count k,
    message, the k signatures back to back, the k signer pubkeys back to
    back), or the reason it cannot be stamped (DROP_*).  Validation is
    txn_lib.parse — the SAME gate the legacy per-txn path applies inside
    the verify tile.  A txn is refused when its message is longer than
    the row (the legacy path's too_long) or when its k rows do not fit
    one frame of max_sigs rows (its sig_overflow)."""
    try:
        t = txn_lib.parse(wire)
    except txn_lib.TxnParseError:
        return DROP_PARSE
    k = t.signature_cnt
    if k > max_sigs:
        return DROP_SIGS
    o = t.message_off
    if len(wire) - o > ml:
        return DROP_LONG
    so, ao = t.signature_off, t.acct_addr_off
    return (k, wire[o:], wire[so:so + 64 * k], wire[ao:ao + 32 * k])


class _PackedWirePublisher:
    """Accumulate reassembled wire txns into round-8 packed dcache rows
    (msg | sig64 | pub32 | len-le32 at packed_row_ml stride), stamped
    straight into the out dcache via ctx.out_reserve like SourceTile's
    _gen_packed — meta.sz carries the row count, zeroed tail rows read as
    dead lanes (sig tag 0).  The quic tiles' packed-publish mode: the
    wire->device path stays zero-copy end to end (one stamp here, shm
    views from there on).

    A txn of k signatures takes k contiguous rows, row i holding the
    message, signature i and signer i's pubkey, its len word marked with
    i and k (tango/ring.py PACKED_LEN_MASK); a frame never splits a txn,
    so one whose rows do not fit closes the frame first.

    The open reservation holds one downstream credit between loop
    iterations; flush-on-fill plus the tile's age-based flush bound how
    long a partial frag can sit.  The frame keeps its oldest row's
    span-chain origin and stamps it at flush (which runs outside frag
    processing on the age path), and records one KIND_COALESCE span
    (open -> flush, cnt = rows, txn_cnt = txns) in the tile's ring.
    Counters: packed_drop_cnt and packed_drop_<reason>_cnt per refused
    txn; sig_rows_cnt (rows) and packed_stamp_ns (time in add and flush)
    per frame, at its flush."""

    def __init__(self, ctx, rows: int, ml: int,
                 flush_age_ns: int = 2_000_000):
        self.ctx = ctx
        self.rows = int(rows)
        self.ml = int(ml)
        from ..tango.ring import PACKED_ROW_EXTRA, packed_row_marks
        self.stride = self.ml + PACKED_ROW_EXTRA
        self.flush_age_ns = int(flush_age_ns)
        self._max_sigs = min(self.rows, txn_lib.ACTUAL_SIG_MAX)
        self._marks = [None] + [packed_row_marks(k)
                                for k in range(1, self._max_sigs + 1)]
        self._chunk = None
        self._blk = None
        self._n = 0
        self._txns = 0
        self._sig0 = 0
        self._opened_ns = 0
        self._tsorig = 0
        self._stamp_ns = 0

    def add(self, wire: bytes) -> bool:
        """Stamp one wire txn into the open packed frag.  False = dropped
        (counted under its reason, see _wire_row).  packed_stamp_ns
        leaves out the wait for a downstream credit."""
        mono = time.monotonic_ns
        t0 = mono()
        row = _wire_row(wire, self.ml, self._max_sigs)
        if isinstance(row, str):
            self.ctx.metrics.add("packed_drop_cnt")
            self.ctx.metrics.add(f"packed_drop_{row}_cnt")
            self._stamp_ns += mono() - t0
            return False
        k, msg, sigs, pubs = row
        if self._blk is None or self._n + k > self.rows:
            self._stamp_ns += mono() - t0
            self.flush()
            if not self._open(sigs):
                # halted while backpressured
                self.ctx.metrics.add("reasm_drop_cnt")
                return False
            t0 = mono()
        n, ml = self._n, self.ml
        r = self._blk[n:n + k]
        # one broadcast of the message over the txn's k rows
        r[:, :len(msg)] = np.frombuffer(msg, np.uint8)
        r[:, ml:ml + 64] = np.frombuffer(sigs, np.uint8).reshape(k, 64)
        r[:, ml + 64:ml + 96] = np.frombuffer(pubs, np.uint8).reshape(k, 32)
        r[:, ml + 96:ml + 100] = (self._marks[k] | np.uint32(len(msg))
                                  ).view(np.uint8).reshape(k, 4)
        self._n = n + k
        self._txns += 1
        self._stamp_ns += mono() - t0
        if self._n >= self.rows:
            self.flush()
        return True

    def _open(self, sigs: bytes) -> bool:
        """Reserve the next frame (blocks on a downstream credit); False
        when the tile halted meanwhile."""
        chunk, blk = self.ctx.out_reserve(self.rows * self.stride)
        if blk is None:
            return False
        self._chunk = chunk
        self._blk = blk.reshape(self.rows, self.stride)
        self._blk[:] = 0  # unfilled tail rows must read as dead lanes
        self._opened_ns = time.monotonic_ns()
        self._tsorig = self.ctx.tsorig
        # same bit-63 mask as the per-txn publish: untagged wire ingest
        # must never alias into latency-class admission
        self._sig0 = int.from_bytes(sigs[:8], "little") & (LAT_PRIO_BIT - 1)
        return True

    def due(self) -> bool:
        return (self._n > 0
                and time.monotonic_ns() - self._opened_ns
                > self.flush_age_ns)

    def flush(self) -> None:
        if self._blk is None or self._n == 0:
            return
        t0 = time.monotonic_ns()
        seq = self.ctx.out_commit(self._chunk, self.rows * self.stride,
                                  sig=self._sig0, sz=self._n,
                                  tsorig=self._tsorig)
        now = time.monotonic_ns()
        if self.ctx.trace is not None:
            self.ctx.trace.record(
                trace_mod.KIND_COALESCE, self._opened_ns,
                now - self._opened_ns, cnt=self._n, seq=seq,
                txn_cnt=self._txns)
        m = self.ctx.metrics
        m.add("sig_rows_cnt", self._n)
        m.add("packed_stamp_ns", self._stamp_ns + now - t0)
        self._stamp_ns = 0
        self._chunk = self._blk = None
        self._n = self._txns = 0


class NetTile:
    """Packet ingress (ref: src/app/fdctl/run/tiles/fd_net.c): drains UDP
    socket bursts and steers by destination port to out links.

    cfg ports: {port: out_link_name}; port 0 = ephemeral, with the kernel's
    chosen port for the FIRST socket exported in the `bound_port` metrics
    slot once the tile is RUN (how tests discover where to send).

    DoS knob: pps_per_source > 0 arms a per-source-IP packet token bucket
    (rate_drop_cnt counts sheds; the `shedding` gauge feeds /healthz) over
    a bounded LRU source map — one flooding source is clamped before its
    packets cost the quic tile anything."""

    _SRC_MAP_CAP = 4096  # bounded per-source bucket table (LRU)

    def init(self, ctx):
        self._xdp_fds = ()
        self.socks = []
        self._pps = float(ctx.cfg.get("pps_per_source", 0) or 0)
        self._pps_burst = float(
            ctx.cfg.get("pps_burst", 0) or 2 * self._pps or 64)
        self._src_buckets: OrderedDict = OrderedDict()
        self._last_shed = -1e9
        if ctx.cfg.get("backend") == "xsk":
            # kernel-bypass tier (VERDICT r4 #6): XSK rings on a NIC
            # queue, fed by the in-kernel redirect program steering this
            # tile's (ip, port) flows into the XSKMAP — NIC -> XSK ->
            # quic with zero per-packet syscalls.  Ports must be
            # explicit (the redirect keys on them).
            from ..waltz.ebpf import KernelXdp
            from ..waltz.xsk import XskSock
            xcfg = ctx.cfg.get("xsk", {})
            ifname = xcfg.get("ifname", "lo")
            ip = xcfg.get("ip", "127.0.0.1")
            xs = XskSock(ifname, queue=int(xcfg.get("queue", 0)))
            kx = KernelXdp()
            flows = [(ip, int(port)) for port in ctx.cfg["ports"]]
            self._xdp_fds = kx.install_redirect(
                ifname, flows, {int(xcfg.get("queue", 0)): xs.fileno()})
            # one XSK serves every port; steer per-dst-port at publish
            self._xsk_outs = {int(port): ctx.out_index(link)
                              for port, link in ctx.cfg["ports"].items()}
            self.socks = [(xs, next(iter(self._xsk_outs.values())))]
            ctx.metrics.set("bound_port", sorted(self._xsk_outs)[0])
            return
        sock_cls = _sock_backend(ctx.cfg)
        for port, link in sorted(ctx.cfg["ports"].items()):
            s = sock_cls(bind_port=port)
            self.socks.append((s, ctx.out_index(link)))
        ctx.metrics.set("bound_port", self.socks[0][0].port)

    def apply_knobs(self, ctx, vals):
        """Autotune pod application (disco/autotune.py KNOBS['net']).
        Only retunes an ALREADY-armed bucket: pps == 0 means the operator
        chose no rate limiting, and autotune must not arm one."""
        if self._pps <= 0:
            return
        if "pps_per_source" in vals:
            self._pps = max(1.0, float(vals["pps_per_source"]))
        if "pps_burst" in vals:
            self._pps_burst = max(1.0, float(vals["pps_burst"]))

    def _admit(self, ctx, src, now: float) -> bool:
        """Per-source pps token bucket: True = forward, False = shed."""
        bk = self._src_buckets.get(src)
        if bk is None:
            if len(self._src_buckets) >= self._SRC_MAP_CAP:
                self._src_buckets.popitem(last=False)
            self._src_buckets[src] = bk = [self._pps_burst, now]
        else:
            self._src_buckets.move_to_end(src)
            bk[0] = min(self._pps_burst,
                        bk[0] + (now - bk[1]) * self._pps)
            bk[1] = now
        if bk[0] < 1.0:
            ctx.metrics.add("rate_drop_cnt")
            self._last_shed = now
            return False
        bk[0] -= 1.0
        return True

    def after_credit(self, ctx):
        pps = self._pps
        now = time.monotonic() if pps else 0.0
        if getattr(self, "_xsk_outs", None):
            xs = self.socks[0][0]
            default_out = self.socks[0][1]
            for pkt, dport in xs.recv_burst_dst():
                src = getattr(pkt, "addr", None)
                if pps and src and not self._admit(ctx, src[0], now):
                    continue
                ctx.publish(pkt.payload, sig=0,
                            out=self._xsk_outs.get(dport, default_out))
                ctx.metrics.add("rx_pkt_cnt")
        else:
            for s, out in self.socks:
                for pkt in s.recv_burst():
                    src = getattr(pkt, "addr", None)
                    if pps and src and not self._admit(ctx, src[0], now):
                        continue
                    ctx.publish(pkt.payload, sig=0, out=out)
                    ctx.metrics.add("rx_pkt_cnt")
        if pps:
            # overload-shedding signal for /healthz: holds ~5 s past the
            # last shed so scrapes can't miss a short burst
            ctx.metrics.set(
                "shedding", 1 if now - self._last_shed < 5.0 else 0)

    def fini(self, ctx):
        # teardown ordering: detach the XDP redirect FIRST (close the bpf
        # link/prog/map fds) so no in-flight packet is steered into a dead
        # XSKMAP entry, THEN close the sockets.  State is cleared before
        # closing, so a re-entrant fini (supervisor + atexit paths) is a
        # no-op.
        fds, self._xdp_fds = getattr(self, "_xdp_fds", ()), ()
        for fd in fds:
            try:
                os.close(fd)
            except OSError:
                pass
        socks, self.socks = getattr(self, "socks", []), []
        for s, _ in socks:
            try:
                s.close()
            except OSError:
                pass


class QuicTile:
    """TPU ingest tile (ref: src/app/fdctl/run/tiles/fd_quic.c).  Consumes
    net frags and publishes whole txns into the verify link via TpuReasm.
    UDP legacy mode today (one datagram = one txn, fd_quic.c:155-165); the
    QUIC stream path plugs into the same reasm."""

    def init(self, ctx):
        from .tpu_reasm import TpuReasm

        self._packed = _mk_packed_publisher(ctx)

        def _pub(txn_bytes: bytes):
            if self._packed is not None:
                # the publisher counts each txn it drops
                if self._packed.add(txn_bytes):
                    ctx.metrics.add("reasm_pub_cnt")
                return
            # mask bit 63: signature bytes are uniform, and untagged wire
            # ingest must never alias a random high bit into the verify
            # tile's latency-class admission (LAT_PRIO_BIT)
            sig64 = ((int.from_bytes(txn_bytes[1:9], "little")
                      if len(txn_bytes) >= 9 else 0) & (LAT_PRIO_BIT - 1))
            ctx.publish(txn_bytes, sig=sig64)
            ctx.metrics.add("reasm_pub_cnt")

        self.reasm = TpuReasm(
            ctx.cfg.get("reasm_depth", 64), _pub,
            conn_budget=int(ctx.cfg.get("reasm_conn_budget", 0)))

    def on_frag(self, ctx, iidx, meta, payload):
        if not self.reasm.publish_datagram(payload):
            ctx.metrics.add("reasm_drop_cnt")

    def on_burst(self, ctx, iidx, metas, buf, offs, kept):
        """Burst rx: one native drain of the net link per loop; each
        datagram still walks the reasm (legacy one-datagram-one-txn mode
        publishes straight through)."""
        for i in range(kept):
            if not self.reasm.publish_datagram(
                    bytes(buf[offs[i]:offs[i + 1]])):
                ctx.metrics.add("reasm_drop_cnt")

    def after_credit(self, ctx):
        p = self._packed
        if p is not None and p.due():
            p.flush()
        ctx.metrics.set("reasm_evict_cnt", self.reasm.metrics["evict_cnt"])

    def fini(self, ctx):
        if self._packed is not None:
            self._packed.flush()


def _mk_packed_publisher(ctx):
    """cfg packed_publish=1 -> a _PackedWirePublisher on out link 0 (the
    quic tiles' zero-copy mode); None keeps the legacy per-txn publish."""
    if not int(ctx.cfg.get("packed_publish", 0)):
        return None
    from ..tango.ring import packed_row_ml
    return _PackedWirePublisher(
        ctx,
        rows=int(ctx.cfg.get("packed_rows", 64)),
        ml=int(ctx.cfg.get("packed_ml", 0) or packed_row_ml(256)),
        flush_age_ns=int(ctx.cfg.get("packed_flush_age_ns", 2_000_000)))


class QuicServerTile:
    """Full QUIC TPU ingest (ref: src/app/fdctl/run/tiles/fd_quic.c QUIC
    path, fd_quic.c:399-466): terminates QUIC conns on a dedicated UDP
    socket (the reference's dedicated XDP queue analogue), reassembles
    one-txn-per-uni-stream payloads, and publishes whole txns to the
    verify link.

    cfg: port (0 = ephemeral; bound port exported in metrics),
         identity_seed (hex; fresh random if absent),
         require_client_cert (default False for open TPU ingest),
         DoS knobs threaded to QuicConfig (max_conns, max_conns_per_peer,
         retry, retry_half_open_threshold, conn_txn_rate/burst,
         conn_reasm_budget, lru_evict_idle, idle_timeout), reasm_conn_budget
         (TpuReasm-level per-conn bytes), packed_publish (+packed_rows/
         packed_ml/packed_flush_age_ns) for zero-copy row stamping.
    """

    def init(self, ctx):
        import os as _os

        from ..waltz.quic import QuicConfig, QuicEndpoint
        from .tpu_reasm import TpuReasm

        cfg = ctx.cfg
        self._packed = _mk_packed_publisher(ctx)

        def _pub(txn_bytes: bytes):
            if self._packed is not None:
                if self._packed.add(txn_bytes):
                    ctx.metrics.add("reasm_pub_cnt")
                # the publisher counts each txn it drops by its reason
                return
            # same bit-63 mask as QuicTile: no random latency-class tags
            sig64 = ((int.from_bytes(txn_bytes[1:9], "little")
                      if len(txn_bytes) >= 9 else 0) & (LAT_PRIO_BIT - 1))
            ctx.publish(txn_bytes, sig=sig64)
            ctx.metrics.add("reasm_pub_cnt")

        self.reasm = TpuReasm(
            cfg.get("reasm_depth", 256), _pub,
            conn_budget=int(cfg.get("reasm_conn_budget", 0)))
        self.sock = _sock_backend(cfg)(
            bind_port=cfg.get("port", 0), burst=256, mutable=True)
        seed_hex = cfg.get("identity_seed")
        seed = bytes.fromhex(seed_hex) if seed_hex else _os.urandom(32)
        qc = QuicConfig(
            identity_seed=seed,
            is_server=True,
            require_client_cert=cfg.get("require_client_cert", False),
            idle_timeout=float(cfg.get("idle_timeout", 10.0)),
            max_conns=int(cfg.get("max_conns", 4096)),
            max_conns_per_peer=int(cfg.get("max_conns_per_peer", 0)),
            retry=bool(cfg.get("retry", False)),
            retry_half_open_threshold=int(
                cfg.get("retry_half_open_threshold", 0)),
            lru_evict_idle=float(cfg.get("lru_evict_idle", 1.0)),
            conn_txn_rate=float(cfg.get("conn_txn_rate", 0.0)),
            conn_txn_burst=int(cfg.get("conn_txn_burst", 32)),
            # same -1/0/1 idiom as native_pack: -1 auto (C if it builds),
            # 0 force the Python fallback, 1 require the C burst engine
            crypto_native={0: False, 1: True}.get(
                int(cfg.get("crypto_native", -1))),
            initial_key_cache=int(cfg.get("initial_key_cache", 1024)),
        )
        if "conn_reasm_budget" in cfg:
            qc.conn_reasm_budget = int(cfg["conn_reasm_budget"])
        self.ep = QuicEndpoint(qc, self.sock.aio())
        # completed streams arrive as memoryviews into the decrypted rx
        # burst buffer; publish_datagram stamps them downstream (packed
        # dcache rows / mcache write) before the view can go stale — the
        # wire->row path pays zero payload copies
        self.ep.stream_views = True

        def _on_stream(conn, sid, data):
            self.reasm.publish_datagram(data)

        self.ep.on_stream = _on_stream
        self._last_msync = 0.0
        self._shed_total = 0
        self._shed_ts = -1e9
        ctx.metrics.set("bound_port", self.sock.port)

    def apply_knobs(self, ctx, vals):
        """Autotune pod application (KNOBS['quic_server']): per-conn txn
        token-bucket rates, read live by _txn_admit via ep.cfg.  Same
        already-armed rule as NetTile — rate 0 stays off."""
        ep = getattr(self, "ep", None)
        if ep is None or ep.cfg.conn_txn_rate <= 0:
            return
        ep.set_rate_knobs(
            conn_txn_rate=vals.get("conn_txn_rate"),
            conn_txn_burst=vals.get("conn_txn_burst"))

    def after_credit(self, ctx):
        now = time.monotonic()
        pkts = self.sock.recv_burst()
        if pkts:
            if ctx.trace is not None:
                # wire stage of the SLO budget: datagrams off the socket
                # through QUIC rx (decrypt + stream delivery + reassembly
                # publishes ride inside ep.rx via on_stream)
                t0 = time.monotonic_ns()
                self.ep.rx(pkts, now)
                ctx.trace.record(trace_mod.KIND_STAGE, t0,
                                 time.monotonic_ns() - t0, cnt=len(pkts))
            else:
                self.ep.rx(pkts, now)
        # deadline-driven service (not a fixed cadence): the endpoint
        # reports its earliest timer (PTO retransmit / idle reap) and we
        # run service exactly when it falls due — retransmits under load
        # are no longer quantized to a polling interval
        if now >= self.ep.next_timeout():
            self.ep.service(now)
        p = self._packed
        if p is not None and p.due():
            p.flush()
        if pkts or now - self._last_msync > 0.01:
            self._last_msync = now
            self._sync_metrics(ctx, now)

    def _sync_metrics(self, ctx, now: float) -> None:
        m = self.ep.metrics
        for k in ("pkt_rx", "pkt_tx", "conn_created", "conn_closed",
                  "streams_rx", "retrans", "pkt_undecryptable",
                  "pkt_malformed", "conn_reject", "rate_drop",
                  "crypto_native", "crypto_fallback",
                  "initial_keys_evict"):
            ctx.metrics.set(k + "_cnt", m[k])
        ctx.metrics.set("retry_sent_cnt", m["retry_tx"])
        r = self.reasm.metrics
        # every shed partial-stream, wire-level (endpoint recv_streams
        # budget/FIFO) or reasm-slot-level (TpuReasm conn budget/FIFO)
        ctx.metrics.set("reasm_evict_cnt",
                        m["reasm_evict"] + r["evict_cnt"])
        # completed txns dropped before publish (oversize/dup/empty, or
        # lost to a halt mid-stamp): this + reasm_pub_cnt +
        # packed_drop_cnt accounts every stream the endpoint delivered
        ctx.metrics.set("reasm_drop_cnt",
                        r["oversz_cnt"] + r["dup_cnt"] + r["empty_cnt"]
                        + r["pub_cnt"] - ctx.metrics.get("reasm_pub_cnt")
                        - ctx.metrics.get("packed_drop_cnt"))
        ctx.metrics.set("conn_cnt", len(self.ep.conns))
        ctx.metrics.set("half_open_cnt", self.ep.half_open)
        # overload-shedding signal for /healthz: any shed counter moving
        # within the last ~5 s flips the gauge (held so scrapes can't
        # miss a short burst)
        shed = (m["conn_reject"] + m["conn_evict"] + m["rate_drop"]
                + m["retry_tx"] + m["reasm_evict"]
                + r["evict_cnt"] + r["oversz_cnt"])
        if shed > self._shed_total:
            self._shed_total = shed
            self._shed_ts = now
        ctx.metrics.set("shedding", 1 if now - self._shed_ts < 5.0 else 0)

    def fini(self, ctx):
        if self._packed is not None:
            self._packed.flush()
        self.sock.close()


class DedupTile:
    """Cross-verify-tile dedup on the signature tag
    (ref: src/app/fdctl/run/tiles/fd_dedup.c, tango tcache)."""

    def init(self, ctx):
        from ..tango.tcache import NativeTCache, ShardedTCache
        depth = ctx.cfg.get("tcache_depth", 1 << 20)
        # fleet mode (round 17): shard the tcache by sig prefix, with
        # ownership following the steering ring (cfg shard_own lists this
        # host's shards); foreign-shard tags still dedup — fail-safe — but
        # are surfaced as a gauge so fleet top can see mis-steering
        self._sharded = int(ctx.cfg.get("shard_bits", 0))
        if self._sharded:
            self.tcache = ShardedTCache(
                depth, self._sharded,
                owned=ctx.cfg.get("shard_own"))
        else:
            try:
                self.tcache = NativeTCache(depth)
            except Exception:
                self.tcache = TCache(depth)
        # failover/restart preload: tags already verdicted fleet-wide
        # (a dead host's capture ledger + gossiped sig digests, or our own
        # ledger across a host rolling restart) — rejecting them here is
        # what keeps the fleet verdict set exactly-once.  One u64 hex tag
        # per line; torn/partial lines are skipped (the writer may have
        # died mid-append).
        path = ctx.cfg.get("preload_tags_path") or ""
        if path:
            n = 0
            try:
                with open(path) as f:
                    for line in f:
                        line = line.strip()
                        if not line:
                            continue
                        try:
                            tag = int(line, 16)
                        except ValueError:
                            continue
                        if 0 < tag < (1 << 64):
                            self.tcache.insert(tag)
                            n += 1
            except OSError:
                pass
            if n:
                ctx.metrics.add("preload_cnt", n)
        # packed verdict egress consumer (round 11): the upstream verify
        # tile ships ONE arena frag per harvest; on_burst_view unpacks it.
        # Hidden unless configured so ordinary per-txn links keep the
        # rx-scratch burst path; when configured, on_burst hides instead so
        # the mux skips its BURST_RX*mtu scratch (a packed link's mtu is a
        # whole arena — hundreds of KB).
        if ctx.cfg.get("packed_egress", 0):
            self.on_burst = None
        else:
            self.on_burst_view = None

    def on_frag(self, ctx, iidx, meta, payload):
        tag = int(meta["sig"])
        if self.tcache.insert(tag):
            ctx.metrics.add("dup_drop_cnt")
            return
        ctx.metrics.add("uniq_cnt")
        ctx.publish(payload, sig=tag)

    def on_burst(self, ctx, iidx, metas, buf, offs, kept):
        """Burst path: one batched tcache insert decides all verdicts,
        survivors forward in one burst publish."""
        tags = metas["sig"].astype(np.uint64)
        if hasattr(self.tcache, "insert_batch_dedup"):
            dup = self.tcache.insert_batch_dedup(tags)
        else:
            dup = np.array([self.tcache.insert(int(t)) for t in tags], bool)
        ndup = int(dup.sum())
        if ndup:
            ctx.metrics.add("dup_drop_cnt", ndup)
        keep = np.nonzero(~dup)[0]
        if not len(keep):
            return
        ctx.metrics.add("uniq_cnt", len(keep))
        starts = offs[:kept][keep]
        lens = (offs[1 : kept + 1] - offs[:kept])[keep].astype(np.int32)
        ctx.publish_burst(buf, starts, lens, tags[keep])

    def on_burst_view(self, ctx, iidx, metas, dcache):
        """Packed verdict egress rx: each frag is meta.sz wires behind a
        u32 offsets table (see VerifyTile._publish_packed_verdicts).  The
        frag is copied out of the shm view ONCE, then the mcache seq is
        re-checked — a producer lap mid-copy drops the frag whole
        (torn_drop_cnt) before anything derived from it is published.
        Tags re-derive from each wire's sig bytes (wire[1:9] LE), the
        same low-64 tag the per-txn path carries in meta.sig."""
        mc = ctx.in_mcache(iidx)
        for meta in metas:
            k = int(meta["sz"])
            if k <= 0:
                continue
            chunk, seq = int(meta["chunk"]), int(meta["seq"])
            hdr = 4 * (k + 1)
            # copy the offsets table out, then re-check the seq BEFORE
            # trusting it to size the payload copy (a torn table could
            # point anywhere); re-check again after the payload copy so
            # nothing derived from a lapped frag is ever published
            offs = dcache.view(chunk, hdr).view(np.uint32).astype(np.int64)
            rc, _ = mc.query(seq)
            if rc != 0:
                ctx.metrics.add("torn_drop_cnt")
                continue
            frag = dcache.view(chunk, hdr + int(offs[k]))[hdr:].copy()
            rc, _ = mc.query(seq)
            if rc != 0:
                ctx.metrics.add("torn_drop_cnt")
                continue
            starts = offs[:k]
            lens = (offs[1:] - offs[:k]).astype(np.int32)
            idx = starts[:, None] + np.arange(1, 9)
            tags = np.ascontiguousarray(frag[idx]).view(np.uint64).ravel()
            if hasattr(self.tcache, "insert_batch_dedup"):
                dup = self.tcache.insert_batch_dedup(tags)
            else:
                dup = np.array([self.tcache.insert(int(t)) for t in tags],
                               bool)
            ndup = int(dup.sum())
            if ndup:
                ctx.metrics.add("dup_drop_cnt", ndup)
            keep = np.nonzero(~dup)[0]
            if not len(keep):
                continue
            ctx.metrics.add("uniq_cnt", len(keep))
            # each verdict frame carries its own origin on to pack
            ctx.publish_burst(frag, starts[keep], lens[keep], tags[keep],
                              tsorig=int(meta["tsorig"]))


    def house(self, ctx):
        if self._sharded:
            ctx.metrics.set("shard_foreign_cnt",
                            int(self.tcache.foreign_cnt))


class PackTile:
    """Block-packing scheduler tile (ref: src/app/fdctl/run/tiles/fd_pack.c
    over src/ballet/pack/fd_pack.c): inserts verified txns into the
    fee-priority scheduler and emits conflict-free microblocks round-robin
    to bank out-links (out link i = bank lane i).

    The in-link is taken on the mux's native burst rx path (every link
    into pack has a dcache): a burst's txns are all inserted, then the
    scheduler runs once for the burst and each microblock goes out as
    one burst of per-txn frags (the reference inserts in after_frag and
    schedules when a bank is idle).  Banks release at once, so between
    bursts only a block boundary can make a held txn schedulable: the
    block ends every slot, in house, and the heap is drained again there.

    cfg: max_txn (per microblock, default 31)."""

    # the reference's [tiles.pack] max_pending_transactions default, and
    # one block per 400 ms slot
    MAX_PENDING = 4096
    BLOCK_NS = 400_000_000

    # pack.Pack.metrics -> tile metric slots, synced by delta through
    # LeaderPackTile._sync_pack (which also sets the pending gauge)
    _PACK_METRICS = (
        ("inserted", "txn_insert_cnt"),
        ("scheduled", "sched_txn_cnt"),
        ("microblocks", "microblock_cnt"),
        ("dropped_heap_full", "heap_full_drop_cnt"),
    )

    def init(self, ctx):
        from ..ballet.pack import Pack
        nbank = max(1, len(ctx.tile.out_links))
        self.pack = Pack(bank_tile_cnt=nbank,
                         max_txn_per_microblock=ctx.cfg.get("max_txn", 31),
                         max_pending=self.MAX_PENDING)
        self._block_t0 = time.monotonic_ns()
        self._last_pm = {k: 0 for k, _ in self._PACK_METRICS}

    def on_burst(self, ctx, iidx, metas, buf, offs, kept):
        """Burst rx: insert every txn of the burst, then schedule once.
        The rx scratch is reused by the next burst and held txns outlive
        this call, so the burst is copied out of it once."""
        ctx.metrics.add("burst_cnt")
        o = offs[:kept + 1].tolist()
        raw = buf[:o[kept]].tobytes()
        for a, b in zip(o, o[1:]):
            payload = raw[a:b]
            try:
                parsed = txn_lib.parse(payload)
            except txn_lib.TxnParseError:
                ctx.metrics.add("parse_fail_cnt")
                continue
            self.pack.insert(payload, parsed)
        self._drain(ctx)
        LeaderPackTile._sync_pack(self, ctx)

    def house(self, ctx):
        now = time.monotonic_ns()
        if now - self._block_t0 >= self.BLOCK_NS:
            self.pack.end_block()
            self._block_t0 = now
            self._drain(ctx)
        LeaderPackTile._sync_pack(self, ctx)

    def _drain(self, ctx):
        progressed = True
        while progressed and self.pack.pending:
            progressed = False
            for bank in range(self.pack.bank_cnt):
                mb = self.pack.schedule(bank)
                if mb is None:
                    continue
                lens = np.array([len(p) for p in mb.payloads], np.int64)
                starts = np.zeros_like(lens)
                np.cumsum(lens[:-1], out=starts[1:])
                ctx.publish_burst(b"".join(mb.payloads), starts, lens,
                                  np.full(len(lens), bank, np.uint64),
                                  out=bank)
                # bank tiles are synchronous sinks for now: release at once
                self.pack.done(bank)
                progressed = True


class BankTile:
    """Executing bank tile (ref: src/app/fdctl/run/tiles/fd_bank.c — there a
    thin FFI shim into the Agave runtime; here the real thing: the flamenco
    Runtime executes microblock txns against a funk fork, freezes the slot
    after `slot_txn_max` txns or `slot_ns`, and rolls to the next slot).

    cfg: genesis_path (required), slot_txn_max, slot_ns."""

    def init(self, ctx):
        import hashlib
        from ..flamenco.genesis import Genesis
        from ..flamenco.runtime import Runtime
        self.rt = Runtime(Genesis.read(ctx.cfg["genesis_path"]))
        # blockhash feedback: an out link named *blockhash carries the
        # root hash to sources after every slot roll (real recency
        # semantics end-to-end).  pin_genesis_blockhash remains for
        # topologies without the link (sources can't refresh there).
        self._bh_out = next(
            (i for i, ln in enumerate(ctx.tile.out_links)
             if ln.endswith("blockhash")), None)
        # executed txns flow to PoH on the non-blockhash out link(s);
        # publishing them on the tiny-MTU blockhash link would wedge
        self._poh_outs = [i for i, ln in enumerate(ctx.tile.out_links)
                          if not ln.endswith("blockhash")]
        if ctx.cfg.get("pin_genesis_blockhash", self._bh_out is None):
            self.rt.blockhash_queue.pin(self.rt.root_hash)
        if ctx.cfg.get("blockhash_max_age"):
            self.rt.blockhash_queue.max_age = ctx.cfg["blockhash_max_age"]
        self.slot_txn_max = ctx.cfg.get("slot_txn_max", 1024)
        self.slot_ns = ctx.cfg.get("slot_ns", 400_000_000)
        self._hashlib = hashlib
        self._slot = 1
        self._bank = self.rt.new_bank(1)
        self._slot_t0 = time.monotonic_ns()
        self._last_bh_ns = 0
        self._poh = self.rt.root_hash
        self._txns_executed = 0
        self.rpc = None
        if ctx.cfg.get("rpc_port") is not None:
            # dev RPC served from the bank process (the reference's full-FD
            # path serves RPC from the validator; Frankendancer delegates
            # to Agave's) — submitted txns drain into the bank in house()
            from ..flamenco.rpc import RpcServer
            tile = self

            class _Provider:
                def slot(self):
                    return tile._slot

                def blockhash(self):
                    return tile.rt.root_hash

                def balance(self, pk: bytes) -> int:
                    # the bank xid can be published by a slot roll between
                    # reading it and the funk lookup (HTTP thread vs tile
                    # loop); retry, then fall back to the root view
                    for _ in range(3):
                        xid = tile._bank.xid
                        try:
                            acct = tile.rt.accdb.load(xid, pk)
                            break
                        except Exception:
                            continue
                    else:
                        acct = tile.rt.accdb.load(None, pk)
                    return 0 if acct is None else acct.lamports

                def txn_count(self):
                    return tile._txns_executed

            self.rpc = RpcServer(_Provider(), port=ctx.cfg["rpc_port"])
            ctx.metrics.set("rpc_port", self.rpc.port)

    def on_frag(self, ctx, iidx, meta, payload):
        self._exec(ctx, payload)

    def _exec(self, ctx, payload):
        res = self._bank.execute_txn(payload)
        if res.ok:
            self._txns_executed += 1
            ctx.metrics.add("txn_exec_cnt")
            for out in self._poh_outs:  # bank_poh: executed txns -> PoH
                ctx.publish(payload, sig=self._slot, out=out)
        else:
            ctx.metrics.add("txn_fail_cnt")
        if self._bank.txn_cnt >= self.slot_txn_max:
            self._roll(ctx)

    def house(self, ctx):
        if self.rpc is not None:
            for raw in self.rpc.drain():
                # RPC submissions bypass the verify tile, so the bank must
                # check signatures itself before execution (the executor's
                # contract is "already signature-verified" txns)
                if self._rpc_sigs_ok(raw):
                    self._exec(ctx, raw)
                else:
                    ctx.metrics.add("txn_fail_cnt")
        if (self._bank.txn_cnt
                and time.monotonic_ns() - self._slot_t0 > self.slot_ns):
            self._roll(ctx)
        elif (self._bh_out is not None
              and time.monotonic_ns() - self._last_bh_ns
              > min(self.slot_ns, 200_000_000)):
            # heartbeat the current blockhash even with no traffic, so
            # feedback-gated sources can begin producing
            self._last_bh_ns = time.monotonic_ns()
            ctx.publish(self.rt.root_hash, sig=self._slot, out=self._bh_out)

    @staticmethod
    def _rpc_sigs_ok(raw: bytes) -> bool:
        from ..ops.ed25519 import verify_one_host
        try:
            parsed = txn_lib.parse(raw)
        except txn_lib.TxnParseError:
            return False
        msg = parsed.message(raw)
        sigs = parsed.signatures(raw)
        pubs = parsed.signer_pubkeys(raw)
        return all(verify_one_host(s, msg, p) for s, p in zip(sigs, pubs))

    def _roll(self, ctx):
        """Freeze + root the slot, open the next (single-fork leader mode;
        fork choice arrives with the choreo layer)."""
        self._poh = self._hashlib.sha256(self._poh).digest()
        self._bank.freeze(self._poh)
        self.rt.publish(self._slot)
        self._slot += 1
        self._bank = self.rt.new_bank(self._slot)
        self._slot_t0 = time.monotonic_ns()
        ctx.metrics.add("slot_cnt")
        if self._bh_out is not None:
            self._last_bh_ns = time.monotonic_ns()
            ctx.publish(self.rt.root_hash, sig=self._slot, out=self._bh_out)

    def fini(self, ctx):
        if self._bank.txn_cnt:
            self._roll(ctx)
        if self.rpc is not None:
            self.rpc.close()


class SignTile:
    """Key-isolation signer (ref: src/app/fdctl/run/tiles/fd_sign.c).  The
    only tile whose process reads the private key; serves role-typed signing
    requests arriving on in-links and replies on the SAME-INDEX out link
    (in_links[i] requests -> out_links[i] responses).  Requests whose
    payload shape is illegal for the role are refused with an empty frag.

    cfg: key_path (JSON keypair file)."""

    def init(self, ctx):
        from ..ops import ed25519 as ed
        from . import keyguard
        self._kg = keyguard
        self._ed = ed
        self.seed, self.pub = keyguard.keypair_read(ctx.cfg["key_path"])

    def on_frag(self, ctx, iidx, meta, payload):
        role = payload[0] if payload else 0
        msg = bytes(payload[1:])
        if not self._kg.role_payload_ok(role, msg):
            ctx.metrics.add("refuse_cnt")
            ctx.publish(b"", sig=role, out=iidx)
            return
        sig = self._ed.sign(self.seed, msg)
        ctx.metrics.add("sign_cnt")
        ctx.publish(sig, sig=role, out=iidx)


class PohTile:
    """Proof-of-history tile (ref: src/app/fdctl/run/tiles/fd_poh.c /
    src/disco/poh/fd_poh_tile.c): continuously advances the sha256 hash
    chain, mixes in executed microblocks from the bank as txn entries, and
    emits serialized entries (sig = slot) to the shred link.  Ticks are
    emitted from housekeeping; after ticks_per_slot ticks the slot advances
    and the final entry is flagged slot-complete (ctl ERR bit repurposed is
    NOT used — the shred tile watches sig slot changes and the tick count
    embedded in the frag's ctl field stays standard; slot completion rides
    the `sig` high bit).

    cfg: seed_hash (hex, default zeros), hashes_per_tick, ticks_per_slot,
    start_slot."""

    SLOT_DONE_BIT = 1 << 63

    def init(self, ctx):
        from ..ballet import entry as entry_lib
        self._el = entry_lib
        cfg = ctx.cfg
        self.hash = bytes.fromhex(cfg["seed_hash"]) if "seed_hash" in cfg \
            else bytes(32)
        self.hashes_per_tick = cfg.get("hashes_per_tick", 16)
        self.ticks_per_slot = cfg.get("ticks_per_slot", 8)
        self.slot = cfg.get("start_slot", 1)
        self.tick = 0
        # With a bank in-link the BANK's slot (carried in each frag's sig)
        # is authoritative for slot boundaries, so PoH/shred slots contain
        # exactly the txns the bank executed in that slot — otherwise a
        # follower replaying slot N would execute a different txn set than
        # the leader's slot-N bank and fail the bank-hash check.  Ticks
        # advance slots only in standalone (no-bank) topologies.
        self.bank_driven = bool(ctx.tile.in_links)

    def _emit(self, ctx, e, slot_done: bool):
        sig = self.slot | (self.SLOT_DONE_BIT if slot_done else 0)
        ctx.publish(e.serialize(), sig=sig)

    def on_frag(self, ctx, iidx, meta, payload):
        """A bank frag: one executed txn payload to absorb (sig = slot the
        bank executed it in; entries group per frag burst for simplicity —
        one txn per entry is legal)."""
        bslot = int(meta["sig"]) & ~self.SLOT_DONE_BIT
        if self.bank_driven and bslot > self.slot:
            # bank rolled: close our current slot before absorbing the
            # first txn of the new one
            self._emit(ctx, self._el.Entry(0, self.hash, []), True)
            self.slot = bslot
            self.tick = 0
        mix = self._el.txn_mixin([payload])
        self.hash = self._el.next_hash(self.hash, 1, mix)
        self._emit(ctx, self._el.Entry(1, self.hash, [payload]), False)
        ctx.metrics.add("mixin_cnt")
        ctx.metrics.add("hash_cnt")

    def house(self, ctx):
        self.hash = self._el.next_hash(self.hash, self.hashes_per_tick, None)
        ctx.metrics.add("hash_cnt", self.hashes_per_tick)
        self.tick += 1
        done = (not self.bank_driven) and self.tick >= self.ticks_per_slot
        self._emit(ctx, self._el.Entry(self.hashes_per_tick, self.hash, []),
                   done)
        if done:
            self.tick = 0
            self.slot += 1

    def fini(self, ctx):
        # close the slot so downstream sees a complete block
        if self.tick:
            self.hash = self._el.next_hash(self.hash, self.hashes_per_tick,
                                           None)
            self._emit(ctx, self._el.Entry(
                self.hashes_per_tick, self.hash, []), True)


class LeaderPackTile:
    """Leader-lane pack scheduler (round 14; ref: fd_pack.c between dedup
    and the banks, here between verify and the device PoH tile): consumes
    verify's verdict egress — per-txn frags or the PR-11 packed arena
    format — runs ballet.pack's fee-priority heap + account-conflict
    scheduling host-side, and emits each conflict-free microblock as ONE
    frag in entry.serialize_txn_batch wire (sig = monotonic microblock
    seq, bit 63 clear so it can never read as a slot-done entry sig).

    Vote-vs-regular admission rides the cost model: simple votes bypass
    the max_pending heap cap (the reserved vote lane), so a fee-paying
    flood can't crowd consensus traffic out of the block.

    Sharding (round 15): with shard_cnt > 1 every shard consumes ALL
    verify links and keeps only the txns whose fee payer hashes to it
    (acct_key(fee_payer) % shard_cnt — deterministic, so a respawned
    shard steers identically).  The fee payer is always writable, so a
    fee payer's whole conflict neighborhood lands on one shard and
    cross-shard write conflicts are the rare multi-payer-hot-account
    case — serialized by microblock ordering at the merge, same as the
    single-packer's done(0)-immediately semantics.  Sharded microblocks
    egress in a merge wire (budget header + serialized batch) to
    LeaderMergeTile, which owns the GLOBAL block budgets.

    cfg: max_txn (per microblock, default 31), max_pending (heap cap, 0 =
    unbounded), block_us (end_block cadence, default 400_000),
    packed_egress (consume arena frags), shard_cnt/shard_idx (fee-payer
    sharding; shard_cnt > 1 switches egress to the merge wire),
    native_pack (-1 auto, 0 force the Python fallback, 1 require the C
    hot loop)."""

    # merge wire: n_acct u32 | cost u64 | vote_cost u64 | data u32 |
    # n_acct * (acct_key u64 | write_cost u64) | serialize_txn_batch
    MERGE_HDR = struct.Struct("<IQQI")
    MERGE_ITEM = struct.Struct("<QQ")

    # pack.Pack.metrics -> tile metric slots (synced by delta so a
    # respawned tile's fresh Pack never rewinds shm counters)
    _PACK_METRICS = (
        ("inserted", "txn_insert_cnt"),
        ("vote_inserted", "vote_insert_cnt"),
        ("scheduled", "sched_txn_cnt"),
        ("microblocks", "microblock_cnt"),
        ("dropped_oversize", "oversize_drop_cnt"),
        ("dropped_heap_full", "heap_full_drop_cnt"),
        ("delayed_conflict", "conflict_delay_cnt"),
    )

    def init(self, ctx):
        from ..ballet import entry as entry_lib
        from ..ballet import pack as pack_lib
        self._el = entry_lib
        self._pl = pack_lib
        native = {0: False, 1: True}.get(ctx.cfg.get("native_pack", -1))
        self.pack = pack_lib.Pack(
            bank_tile_cnt=1,
            max_txn_per_microblock=ctx.cfg.get("max_txn", 31),
            max_pending=ctx.cfg.get("max_pending", 0),
            native=native)
        self.shard_cnt = ctx.cfg.get("shard_cnt", 1)
        self.shard_idx = ctx.cfg.get("shard_idx", 0)
        self.block_us = ctx.cfg.get("block_us", 400_000)
        self._block_t0 = time.monotonic_ns()
        self._mb_seq = 0
        self._last_pm = {k: 0 for k, _ in self._PACK_METRICS}
        self._drain_stall = 0
        if not ctx.cfg.get("packed_egress", 0):
            self.on_burst_view = None

    def _sync_pack(self, ctx):
        pm = self.pack.metrics
        for key, slot in self._PACK_METRICS:
            d = pm[key] - self._last_pm[key]
            if d:
                ctx.metrics.add(slot, d)
                self._last_pm[key] = pm[key]
        ctx.metrics.set("pending", self.pack.pending)

    def _insert(self, ctx, payload: bytes):
        if self.shard_cnt > 1:
            # deterministic fee-payer steering: a broken header steers to
            # shard 0, whose full parse rejects it with the real error
            fp = txn_lib.fee_payer(payload)
            shard = (self._pl.acct_key(fp) % self.shard_cnt
                     if fp is not None else 0)
            if shard != self.shard_idx:
                return
            ctx.metrics.add("shard_steer_cnt")
        ctx.metrics.add("txn_in_cnt")
        try:
            parsed = txn_lib.parse(payload)
        except txn_lib.TxnParseError:
            ctx.metrics.add("parse_fail_cnt")
            return
        self.pack.insert(bytes(payload), parsed)

    def on_frag(self, ctx, iidx, meta, payload):
        self._insert(ctx, payload)
        self._emit(ctx)
        self._sync_pack(ctx)

    def on_burst_view(self, ctx, iidx, metas, dcache):
        """Packed verdict egress rx (the DedupTile unpack): copy the frag
        out of the shm view once, re-checking the mcache seq before the
        offsets table is trusted and again after the payload copy, so
        nothing derived from a producer-lapped frag is ever inserted."""
        mc = ctx.in_mcache(iidx)
        for meta in metas:
            k = int(meta["sz"])
            if k <= 0:
                continue
            chunk, seq = int(meta["chunk"]), int(meta["seq"])
            hdr = 4 * (k + 1)
            offs = dcache.view(chunk, hdr).view(np.uint32).astype(np.int64)
            rc, _ = mc.query(seq)
            if rc != 0:
                ctx.metrics.add("torn_drop_cnt")
                continue
            frag = dcache.view(chunk, hdr + int(offs[k]))[hdr:].copy()
            rc, _ = mc.query(seq)
            if rc != 0:
                ctx.metrics.add("torn_drop_cnt")
                continue
            for w in range(k):
                self._insert(ctx, bytes(frag[offs[w]:offs[w + 1]]))
        self._emit(ctx)
        self._sync_pack(ctx)

    def _emit(self, ctx) -> bool:
        """Schedule + publish until the heap can't progress.  One bank
        lane whose locks release immediately (the PoH tile is a
        synchronous consumer), so within a microblock conflicts are
        excluded and across microblocks ordering does the serializing."""
        progressed = False
        while True:
            mb = self.pack.schedule(0)
            if mb is None:
                break
            payload = self._el.serialize_txn_batch(mb.payloads)
            if self.shard_cnt > 1:
                payload = self._merge_wire(mb) + payload
            ctx.publish(payload, sig=self._mb_seq)
            self._mb_seq += 1
            ctx.metrics.add("cu_consumed",
                            sum(h.cost.total for h in mb.txns))
            self.pack.done(0)
            progressed = True
        return progressed

    def _merge_wire(self, mb) -> bytes:
        """Budget header for LeaderMergeTile's global accounting: total /
        vote cost, data bytes, and per-account write costs (u64 keys —
        the merge never re-parses).  Accounts are unique across the
        microblock's txns by construction (write-write conflicts are
        excluded within one microblock)."""
        total = vote = data = 0
        items: dict = {}
        for h in mb.txns:
            total += h.cost.total
            if h.cost.is_simple_vote:
                vote += h.cost.total
            data += len(h.payload)
            for k, c in self._pl.writable_key_costs(h).items():
                items[k] = items.get(k, 0) + c
        return self.MERGE_HDR.pack(len(items), total, vote, data) + \
            b"".join(self.MERGE_ITEM.pack(k, c) for k, c in items.items())

    def after_credit(self, ctx):
        if self.pack.pending:
            self._emit(ctx)
            self._sync_pack(ctx)

    def house(self, ctx):
        if (time.monotonic_ns() - self._block_t0) // 1000 >= self.block_us:
            self.pack.end_block()
            self._block_t0 = time.monotonic_ns()
        self._sync_pack(ctx)

    def drain(self, ctx) -> bool:
        """Drain-protocol hook: flush the heap so a rolling restart loses
        nothing.  Block limits reset (end_block) so leftover txns aren't
        stuck behind this block's budget; a heap that still can't
        progress after two budget resets is dropped with a counter —
        never a silent hang of the drain protocol."""
        progressed = self._emit(ctx)
        if not self.pack.pending:
            self._sync_pack(ctx)
            return True
        if progressed:
            self._drain_stall = 0
            return False
        self._drain_stall += 1
        self.pack.end_block()
        self._block_t0 = time.monotonic_ns()
        if self._drain_stall >= 3:
            ctx.metrics.add("drain_drop_cnt", self.pack.clear_pending())
            self._sync_pack(ctx)
            return True
        return False

    def fini(self, ctx):
        try:
            self._emit(ctx)
            self._sync_pack(ctx)
        except Exception:
            pass  # downstream rings may already be gone


class LeaderMergeTile:
    """Shard-merge stage of the sharded leader lane (round 15): consumes
    the merge-wire microblock frags from every leader_pack shard and
    interleaves them round-robin into ONE tick-stream, enforcing the
    GLOBAL block/vote/data and per-account write budgets here — each
    shard's Pack only pre-filters against its local copy, so this tile
    is the consensus-critical accounting authority.

    Admission: one pass over the shards per round starting at a rotating
    cursor, admitting at most one head microblock per shard per pass
    (the round-robin interleave).  A head that would overflow a budget
    stays queued (merge_budget_defer_cnt) until the block rolls; a full
    pass with queued work but zero admissions counts merge_stall_cnt.
    Admitted frags re-publish the inner serialize_txn_batch payload
    (merge header stripped) with this tile's own monotonic microblock
    seq, so PohDevTile sees exactly the single-packer wire.

    Drain convergence: any single shard microblock fits a fresh budget
    (see pack.MergeBudget), so resetting the block always unblocks."""

    def init(self, ctx):
        from collections import deque
        from ..ballet import pack as pack_lib
        self._deque = deque
        self.budget = pack_lib.MergeBudget()
        self.block_us = ctx.cfg.get("block_us", 400_000)
        self._block_t0 = time.monotonic_ns()
        self._qs: dict = {}  # iidx -> deque of (cost, vote, data, items, inner)
        self._rr = 0
        self._mb_seq = 0
        self._drain_stall = 0

    def on_frag(self, ctx, iidx, meta, payload):
        b = bytes(payload)
        try:
            n_items, cost, vote, data = \
                LeaderPackTile.MERGE_HDR.unpack_from(b, 0)
            off = LeaderPackTile.MERGE_HDR.size
            items = [LeaderPackTile.MERGE_ITEM.unpack_from(b, off + 16 * i)
                     for i in range(n_items)]
            inner = b[off + 16 * n_items:]
        except struct.error:
            ctx.metrics.add("parse_fail_cnt")
            return
        self._qs.setdefault(iidx, self._deque()).append(
            (cost, vote, data, items, inner))
        ctx.metrics.add("mb_rx_cnt")
        self._admit(ctx)

    def _admit(self, ctx) -> bool:
        keys = sorted(self._qs)
        if not keys:
            return False
        admitted_any = False
        while True:
            progressed = False
            deferred = False
            for off in range(len(keys)):
                q = self._qs[keys[(self._rr + off) % len(keys)]]
                if not q:
                    continue
                cost, vote, data, items, inner = q[0]
                if not self.budget.try_admit(cost, vote, data, items):
                    ctx.metrics.add("merge_budget_defer_cnt")
                    deferred = True
                    continue
                q.popleft()
                ctx.publish(inner, sig=self._mb_seq)
                self._mb_seq += 1
                ctx.metrics.add("mb_merge_cnt")
                progressed = True
            self._rr = (self._rr + 1) % len(keys)
            if not progressed:
                if deferred:
                    ctx.metrics.add("merge_stall_cnt")
                break
            admitted_any = True
        ctx.metrics.set("merge_q", sum(len(q) for q in self._qs.values()))
        return admitted_any

    def house(self, ctx):
        if (time.monotonic_ns() - self._block_t0) // 1000 >= self.block_us:
            self.budget.end_block()
            self._block_t0 = time.monotonic_ns()
        self._admit(ctx)

    def after_credit(self, ctx):
        if any(self._qs.values()):
            self._admit(ctx)

    def drain(self, ctx) -> bool:
        """Drain-protocol hook: flush every queued microblock.  Budget
        resets force progress (any one microblock fits a fresh block);
        the drop path is an unreachable safety net, never silent."""
        self._admit(ctx)
        if not any(self._qs.values()):
            return True
        self.budget.end_block()
        self._block_t0 = time.monotonic_ns()
        if self._admit(ctx):
            self._drain_stall = 0
            return not any(self._qs.values())
        self._drain_stall += 1
        if self._drain_stall >= 3:
            n = sum(len(q) for q in self._qs.values())
            ctx.metrics.add("drain_drop_cnt", n)
            for q in self._qs.values():
                q.clear()
            return True
        return False

    def fini(self, ctx):
        try:
            self._admit(ctx)
            if any(self._qs.values()):
                self.budget.end_block()
                self._admit(ctx)
        except Exception:
            pass  # downstream rings may already be gone


class PohDevTile:
    """Device-batched PoH tile (round 14; ref: fd_poh_tile.c's hashing
    core over ballet.poh_engine.PohEngine): extends the slot hash chain
    through (lanes, 32) span dispatches on the shared packed rotation
    engine instead of host hashlib.  Lane 0 is the chain; the remaining
    lanes re-verify previously emitted entries (the embarrassingly-
    parallel verify_entries re-check, riding the same dispatch).

    Speculation (round 15, K ticks deep): mixins sit at the END of each
    tick — P = hashes_per_tick - mb_per_tick - 1 plain hashes, then up
    to mb_per_tick single-hash mixin entries, then a tail.  One window
    dispatch pre-hashes K whole ticks from the current head as 2K
    chained steps ((P, None), (tail, None) per tick), so every tick
    boundary AND every mixin insertion point (state @ P) comes back as a
    step plane.  A tick that closes empty consumes one speculated tick
    (spec_hit) with zero extra hashing; a tick that closes with j
    microblocks SPLICES: a second small engine re-hashes only from the
    saved state @ P — steps (1, m_1)..(1, m_j), inactive padding,
    (tail - j, None), per-step hash caps (1,..,1,tail) — so the re-hash
    costs tail - j wasted hashes (rehash_cnt) instead of the whole tick,
    and the later speculated ticks are invalidated (their chain
    assumption broke).  Mixins are device-batched via
    entry.txn_mixins_device; emitted-entry re-checks ride spare window
    lanes.

    In: microblock frags from leader_pack (entry.serialize_txn_batch
    wire).  Out: serialized entries, sig = slot | SLOT_DONE_BIT — the
    same contract as PohTile, so shred/store consume either.

    cfg: seed_hash (hex), hashes_per_tick, ticks_per_slot, start_slot,
    spec_ticks (K, speculation depth in ticks), spec_spans (total window
    engine lanes: 1 chain + N-1 recheck), mb_per_tick (mixin entries per
    tick; capped at hashes_per_tick - 1), mixin_txn_max (pad width for
    the mixin tree shape), nbuf, depth, unroll."""

    SLOT_DONE_BIT = 1 << 63

    def init(self, ctx):
        from collections import deque

        from ..ballet import entry as entry_lib
        from ..ballet.poh_engine import PohEngine
        self._el = entry_lib
        cfg = ctx.cfg
        self.hash = bytes.fromhex(cfg["seed_hash"]) if "seed_hash" in cfg \
            else bytes(32)
        self.hashes_per_tick = cfg.get("hashes_per_tick", 16)
        self.ticks_per_slot = cfg.get("ticks_per_slot", 8)
        self.slot = cfg.get("start_slot", 1)
        self.tick = 0
        # spec_spans = total concurrent span lanes: 1 chain lane + the
        # emitted-entry re-check lanes
        self.recheck_lanes = max(0, cfg.get("spec_spans", 3) - 1)
        self.mb_cap = min(cfg.get("mb_per_tick", 8),
                          self.hashes_per_tick - 1)
        if self.mb_cap < 1:
            raise ValueError("hashes_per_tick must be >= 2 for mixins")
        self.mixin_txn_max = cfg.get("mixin_txn_max", 32)
        self.K = max(1, cfg.get("spec_ticks", 4))
        # tick anatomy: P plain hashes, then the mixin region + tail
        self.P = self.hashes_per_tick - self.mb_cap - 1
        tail = self.mb_cap + 1
        # window engine: K ticks of (P, tail) step pairs.  Step 0's cap
        # is the full hashes_per_tick so recheck lanes (entry n up to a
        # whole tick) fit in the shared first step.
        caps = [self.hashes_per_tick, tail] \
            + [max(self.P, 1), tail] * (self.K - 1)
        self.eng = PohEngine(
            lanes=1 + self.recheck_lanes,
            steps=2 * self.K,
            max_hashes=self.hashes_per_tick,
            step_caps=caps,
            nbuf=cfg.get("nbuf", 2), depth=cfg.get("depth"),
            unroll=cfg.get("unroll", 8))
        # splice engine: re-hash from the saved mixin insertion point —
        # j mixin steps (1 hash each) + the plain tail, never a full tick
        self.seng = PohEngine(
            lanes=1,
            steps=tail,
            max_hashes=tail,
            step_caps=(1,) * self.mb_cap + (tail,),
            nbuf=2, unroll=cfg.get("unroll", 8))
        # compile BEFORE signaling RUN: both span graphs and the
        # mixin-tree shape the hot path will use
        self.eng.warm()
        self.seng.warm()
        entry_lib.txn_mixins_device(
            [[b"\x00" * 65]], pad_batch=self.mb_cap,
            pad_width=self.mixin_txn_max)
        self._mb_q = deque()          # parsed microblocks awaiting a tick
        self._recheck_q = deque(maxlen=256)   # (start, n, mixin|None, end)
        self._pending_disp = deque()  # window-dispatch FIFO
        self._win = None              # current speculation window record
        self._win_pos = 0             # speculated ticks already consumed

    # -------------------------------------------------------------- ingest
    def on_frag(self, ctx, iidx, meta, payload):
        try:
            txns, _ = self._el.deserialize_txn_batch(bytes(payload))
        except ValueError:
            ctx.metrics.add("parse_fail_cnt")
            return
        if not txns or len(txns) > self.mixin_txn_max:
            ctx.metrics.add("parse_fail_cnt")
            return
        self._mb_q.append(txns)
        ctx.metrics.add("mb_rx_cnt")

    # ------------------------------------------------------------- harvest
    def _emit(self, ctx, e, slot_done: bool, slot: int):
        ctx.publish(e.serialize(), sig=slot
                    | (self.SLOT_DONE_BIT if slot_done else 0))
        ctx.metrics.add("entry_cnt")

    def _process(self, ctx, verdicts):
        for v in verdicts:
            planes = self.eng.split_verdict(v)
            rec = self._pending_disp.popleft()
            for lane, exp in rec["rechecks"]:
                if bytes(planes[lane, 0]) == exp:
                    ctx.metrics.add("recheck_ok_cnt")
                else:
                    ctx.metrics.add("recheck_fail_cnt")
            # harvest the window: per speculated tick, the state at the
            # mixin insertion point (plane 2t) and the tick end (2t+1)
            rec["mid"] = [bytes(planes[0, 2 * t]) for t in range(self.K)]
            rec["end"] = [bytes(planes[0, 2 * t + 1]) for t in range(self.K)]
            rec["heads"] = [rec["head"]] + rec["end"][:-1]
            rec["ready"] = True

    # ---------------------------------------------------------- tick cycle
    def _open_window(self, ctx):
        rec = {"head": self.hash, "rechecks": [], "heads": None,
               "mid": None, "end": None, "ready": False}
        steps = []
        for _ in range(self.K):
            steps.append((self.P, None))
            steps.append((self.mb_cap + 1, None))
        lanes = [(self.hash, steps)]
        for lane in range(1, 1 + self.recheck_lanes):
            if not self._recheck_q:
                break
            start, n, mix, end = self._recheck_q.popleft()
            lanes.append((start, [(n, mix)]))
            rec["rechecks"].append((lane, end))
        self._pending_disp.append(rec)
        self._win = rec
        self._win_pos = 0
        ctx.metrics.add("dispatch_cnt")
        self._process(ctx, self.eng.submit_lanes(lanes))

    def _close_tick(self, ctx, final: bool = False):
        j = min(len(self._mb_q), self.mb_cap)
        mbs = [self._mb_q.popleft() for _ in range(j)]
        if self._mb_q:
            ctx.metrics.add("mb_deferred_cnt", len(self._mb_q))
        done = final or (self.tick + 1 >= self.ticks_per_slot)
        win = self._win
        if not win["ready"]:
            self._process(ctx, self.eng.drain())
        t = self._win_pos
        if j == 0:
            # speculation lands: the pre-hashed tick IS the tick, and
            # the window stays live for the next one
            ctx.metrics.add("spec_hit_cnt")
            end = win["end"][t]
            self._emit(ctx, self._el.Entry(self.hashes_per_tick, end, []),
                       done, self.slot)
            self._recheck_q.append(
                (win["heads"][t], self.hashes_per_tick, None, end))
            self.hash = end
            self._win_pos += 1
            if self._win_pos >= self.K:
                self._win = None
        else:
            # mixins landed: splice from the saved state @ P — only the
            # mixin region re-hashes; the later speculated ticks assumed
            # a plain chain and are invalidated
            ctx.metrics.add("spec_miss_cnt")
            ctx.metrics.add("rehash_cnt", self.mb_cap + 1 - j)
            mix_arr = self._el.txn_mixins_device(
                mbs, pad_batch=self.mb_cap, pad_width=self.mixin_txn_max)
            mixins = [bytes(mix_arr[i]) for i in range(j)]
            steps = [(1, m) for m in mixins]
            steps += [(0, None)] * (self.mb_cap - j)
            steps.append((self.mb_cap + 1 - j, None))
            ctx.metrics.add("splice_dispatch_cnt")
            # entry ordering is consensus-critical: the splice retires
            # synchronously before the next tick opens on its end state
            verdicts = self.seng.submit_lanes([(win["mid"][t], steps)])
            verdicts += self.seng.drain()
            planes = self.seng.split_verdict(verdicts[-1])
            h = win["heads"][t]
            end = bytes(planes[0, 0])
            self._emit(ctx, self._el.Entry(self.P + 1, end, mbs[0]),
                       False, self.slot)
            self._recheck_q.append((h, self.P + 1, mixins[0], end))
            ctx.metrics.add("mixin_cnt")
            h = end
            for si in range(1, j):
                end = bytes(planes[0, si])
                self._emit(ctx, self._el.Entry(1, end, mbs[si]),
                           False, self.slot)
                self._recheck_q.append((h, 1, mixins[si], end))
                ctx.metrics.add("mixin_cnt")
                h = end
            n_rem = self.mb_cap + 1 - j
            end = bytes(planes[0, self.mb_cap])
            self._emit(ctx, self._el.Entry(n_rem, end, []), done, self.slot)
            self._recheck_q.append((h, n_rem, None, end))
            self.hash = end
            self._win = None
        ctx.metrics.add("hash_cnt", self.hashes_per_tick)
        ctx.metrics.add("tick_cnt")
        if done:
            self.tick = 0
            self.slot += 1
        else:
            self.tick += 1

    def house(self, ctx):
        if self._win is None:
            self._open_window(ctx)
        else:
            self._close_tick(ctx)
            if self._win is None:
                self._open_window(ctx)
        ctx.metrics.set("mb_queue", len(self._mb_q))
        ctx.metrics.set("spec_depth",
                        (self.K - self._win_pos) if self._win else 0)

    def after_credit(self, ctx):
        verdicts = self.eng.poll()
        if verdicts:
            self._process(ctx, verdicts)
        ctx.metrics.set("inflight_depth",
                        self.eng.inflight_depth + self.seng.inflight_depth)

    def drain(self, ctx) -> bool:
        """Drain-protocol hook: absorb every queued microblock into
        closed ticks, then run the engine dry."""
        if self._win is not None:
            self._close_tick(ctx)
            if self._mb_q:
                if self._win is None:
                    self._open_window(ctx)
                return False
        elif self._mb_q:
            self._open_window(ctx)
            return False
        self._process(ctx, self.eng.drain())
        self.seng.drain()
        return True

    def fini(self, ctx):
        try:
            # close the slot so downstream sees a complete block
            if self._win is None:
                self._open_window(ctx)
            while self._mb_q:
                self._close_tick(ctx)
                if self._win is None and self._mb_q:
                    self._open_window(ctx)
            if self._win is None:
                self._open_window(ctx)
            self._close_tick(ctx, final=True)
            self._process(ctx, self.eng.drain())
        except Exception:
            pass  # downstream rings may already be gone


class _ShredSigBatcher:
    """Batched leader-signature admission for turbine ingress (round 13).

    The old path paid one device graph dispatch PER SHRED (host merkle
    walk + ops.ed25519.verify_one): admission cost scaled with packet
    rate.  Queued shreds now clear as a burst — every merkle root walks
    in ONE batched sha256 graph (ballet.bmtree.batch_walk_roots) and the
    64-byte root signatures verify through the SAME batched SigVerifier
    packed admission the txn lane uses.  Forwarding is deferred until
    the burst verdict; the caller re-checks dedup at verdict time before
    inserting, so the insert-only-after-signed discipline (forge-then-
    censor resistance) is unchanged.

    backend="device" is the batched path; "host" keeps per-shred
    python-int verification (control-plane rates, no device graphs)."""

    # padded batch geometry: leaf data spans at most the wire MTU minus
    # the signature; the proof-length nibble caps the walk depth at 15
    LEAF_MAXLEN = 1228 - 64
    PROOF_DEPTH = 15

    def __init__(self, batch: int = 32, backend: str = "device",
                 flush_age_us: int = 2000):
        if backend not in ("device", "host"):
            raise ValueError(f"unknown sig backend {backend!r}")
        self.batch = max(1, int(batch))
        self.backend = backend
        self.flush_age_us = flush_age_us
        self._q: list = []            # (shred, raw, tag, leader)
        self._t0 = None               # monotonic_ns of oldest queued shred
        if backend == "device":
            from ..ballet import bmtree
            from ..models.verifier import SigVerifier, VerifierConfig
            self._bm = bmtree
            self._roots_fn = bmtree.batch_walk_roots_jit()
            self._sv = SigVerifier(VerifierConfig(batch=self.batch,
                                                  msg_maxlen=32))

    def __len__(self) -> int:
        return len(self._q)

    @property
    def full(self) -> bool:
        return len(self._q) >= self.batch

    def due(self) -> bool:
        """Age deadline: a partial batch must not hold shreds hostage
        when the ingress rate drops (same flush-on-size-or-age shape as
        the verify tile's coalescer)."""
        return (self._t0 is not None
                and time.monotonic_ns() - self._t0
                >= self.flush_age_us * 1000)

    def add(self, s, raw: bytes, tag: int, leader) -> None:
        if self._t0 is None:
            self._t0 = time.monotonic_ns()
        self._q.append((s, raw, tag, leader))

    def warm(self) -> None:
        """Pre-RUN compile of the batched admission graphs (same
        discipline as VerifyTile's warmup: the first live burst must not
        stall the mux loop through a cold compile)."""
        if self.backend != "device":
            return
        b = self.batch
        np.asarray(self._roots_fn(
            np.zeros((b, self.LEAF_MAXLEN), np.uint8),
            np.zeros((b,), np.int32), np.zeros((b,), np.int32),
            np.zeros((b, self.PROOF_DEPTH, self._bm.MERKLE_NODE_SZ),
                     np.uint8),
            np.zeros((b,), np.int32)))
        np.asarray(self._sv.packed_dispatch(
            np.zeros((b, 32), np.uint8), np.full((b,), 32, np.int32),
            np.zeros((b, 64), np.uint8), np.zeros((b, 32), np.uint8)))

    def flush(self) -> list:
        """Verify everything queued: [(shred, raw, tag, ok)], FIFO."""
        q, self._q, self._t0 = self._q, [], None
        if not q:
            return []
        if self.backend == "host":
            out = []
            for s, raw, tag, leader in q:
                root = s.merkle_root()
                ok = (root is not None and leader is not None
                      and _ed25519_verify_host(s.signature, root, leader))
                out.append((s, raw, tag, ok))
            return out
        out = []
        for i in range(0, len(q), self.batch):
            out.extend(self._verify_chunk(q[i:i + self.batch]))
        return out

    def _verify_chunk(self, chunk: list) -> list:
        from ..ballet.shred import TYPE_LEGACY_CODE, TYPE_LEGACY_DATA
        b = self.batch
        leaf = np.zeros((b, self.LEAF_MAXLEN), np.uint8)
        lens = np.zeros((b,), np.int32)
        idxs = np.zeros((b,), np.int32)
        proofs = np.zeros((b, self.PROOF_DEPTH, self._bm.MERKLE_NODE_SZ),
                          np.uint8)
        depths = np.zeros((b,), np.int32)
        sigs = np.zeros((b, 64), np.uint8)
        pubs = np.zeros((b, 32), np.uint8)
        elig = np.zeros((b,), bool)
        for j, (s, _raw, _tag, leader) in enumerate(chunk):
            # legacy (non-merkle) shreds have no signable root; unknown
            # leaders are unverifiable — both fail without a dispatch lane
            if leader is None or s.type in (TYPE_LEGACY_DATA,
                                            TYPE_LEGACY_CODE):
                continue
            ld = s.merkle_leaf_data()
            leaf[j, :len(ld)] = np.frombuffer(ld, np.uint8)
            lens[j] = len(ld)
            idxs[j] = s.tree_index()
            for d, node in enumerate(s.proof_nodes()):
                proofs[j, d] = np.frombuffer(node, np.uint8)
            depths[j] = s.merkle_proof_len
            sigs[j] = np.frombuffer(s.signature, np.uint8)
            pubs[j] = np.frombuffer(leader, np.uint8)
            elig[j] = True
        roots = np.asarray(self._roots_fn(leaf, lens, idxs, proofs, depths))
        ok = np.asarray(self._sv.packed_dispatch(
            roots, np.full((b,), 32, np.int32), sigs, pubs))
        ok = ok.astype(bool) & elig
        return [(s, raw, tag, bool(ok[j]))
                for j, (s, raw, tag, _leader) in enumerate(chunk)]


class ShredTile:
    """Shredder tile (ref: src/app/fdctl/run/tiles/fd_shred.c over
    src/disco/shred/fd_shredder.c + fd_shred_dest.c): accumulates a slot's
    entries, cuts merkle FEC sets (signing each root through the keyguard),
    fans the shreds out to every out link except the sign request link, and
    — when turbine is configured — sends each shred over UDP to its
    computed Turbine destination (leader: the tree root per shred;
    non-leader: retransmits received shreds to its children).

    In-links: entries from poh (sig = slot | done-bit) and, for the
    retransmit role, raw shreds from net links named in cfg `net_ins`.
    Out links: optional keyguard request link `shred_sign` plus shred
    fan-out links.
    cfg: shred_version, fec_data_cnt (default 32), turbine:
      {identity: hexpub, fanout, port, slots_per_epoch,
       stakes: {hexpub: [stake, ip, port]}}; batched-admission knobs
    sig_batch (default 32), sig_flush_age_us (default 2000),
    sig_backend ("device" | "host").

    INTEROP (round 5, closes VERDICT r4 #7): the turbine tree shuffle
    (disco/shred_dest.py) now rides the reference's MODE_SHIFT
    bounded-rand, fixture-verified against the compiled reference
    algorithm (tests/test_wsample_ref_conformance.py) — trees match
    reference/Agave nodes tree-for-tree, so mixed deployments compute
    identical retransmit children."""

    def init(self, ctx):
        from ..ballet import entry as entry_lib, shred as shred_lib
        from . import keyguard
        self._el, self._sl, self._kg = entry_lib, shred_lib, keyguard
        self.kgc = (keyguard.KeyguardClient(ctx, "shred_sign", "sign_shred")
                    if "shred_sign" in ctx.tile.out_links else None)
        self.version = ctx.cfg.get("shred_version", 1)
        self.data_cnt = ctx.cfg.get("fec_data_cnt", 32)
        self._fanout = [i for i, ln in enumerate(ctx.tile.out_links)
                        if ln != "shred_sign"]
        self.batch_max = ctx.cfg.get("batch_max", 16 << 10)
        self.net_ins = set(ctx.cfg.get("net_ins", ()))
        # fail at wiring time, not on the first FEC cut: a topology that
        # feeds this tile entries (any non-net in-link) but gives it no
        # shred_sign out-link could never sign a merkle root (ADVICE r3 —
        # previously died with AttributeError deep in _cut)
        entry_ins = [il.link for il in ctx.tile.in_links
                     if il.link not in self.net_ins]
        if entry_ins and self.kgc is None:
            raise ValueError(
                f"shred tile receives entries on {entry_ins} but has no "
                "'shred_sign' out link to the keyguard; wire one or make "
                "this a net-ins-only retransmit tile")
        self.slot = None
        self.entries = []
        self._size = 0
        self.fec_idx = 0
        self._init_turbine(ctx)

    def _init_turbine(self, ctx):
        self.turbine = None
        tb = ctx.cfg.get("turbine")
        if not tb:
            return
        from ..flamenco.leaders import leader_schedule
        from ..tango.tcache import TCache
        from ..waltz.udpsock import UdpSock
        from . import shred_dest as sd_mod
        self._sd = sd_mod
        self.identity = bytes.fromhex(tb["identity"])
        self.tree_fanout = tb.get("fanout", 200)
        spe = tb.get("slots_per_epoch", 432_000)
        self._stake_map = {}
        ci = sd_mod.StakeCI(self.identity, spe)
        for pkhex, (stake, ip, port) in tb["stakes"].items():
            pk = bytes.fromhex(pkhex)
            self._stake_map[pk] = stake
            if ip:
                ci.set_contact(pk, ip, port)
        self.stake_ci = ci
        sched = {}

        def leaders(slot):
            ep = slot // spe
            if ep not in sched:
                sched[ep] = leader_schedule(
                    ep, {pk: st for pk, st in self._stake_map.items()
                         if st > 0}, spe)
            return sched[ep][slot % spe]

        self._leaders = leaders
        self.tsock = UdpSock(bind_port=tb.get("port", 0))
        self._retx_seen = TCache(1 << 14)
        self.turbine = tb
        # batched leader-signature admission (round 13): merkle walks and
        # signature checks amortize across a burst instead of paying one
        # device dispatch per shred; warm BEFORE signaling RUN (the first
        # burst must not stall the mux loop through a cold compile)
        self._sigb = _ShredSigBatcher(
            batch=ctx.cfg.get("sig_batch", 32),
            backend=ctx.cfg.get("sig_backend", "device"),
            flush_age_us=ctx.cfg.get("sig_flush_age_us", 2000))
        self._sigb.warm()
        ctx.metrics.set("turbine_port", self.tsock.port)

    def _sdest(self, slot):
        ep = self.stake_ci.epoch_of(slot)
        if ep not in self.stake_ci.stakes:
            # static config stakes apply to every epoch until a stake
            # feed (replay epoch boundary) overrides them
            self.stake_ci.set_stakes(ep, self._stake_map)
        return self.stake_ci.sdest_for(slot, self._leaders)

    def _turbine_send(self, ctx, shreds, raws, first: bool):
        """Leader (first=True): root dest per shred.  Retransmitter:
        children per shred."""
        if self.turbine is None or not shreds:
            return
        from ..waltz.aio import Pkt
        sd = self._sdest(shreds[0].slot)
        if sd is None:
            return
        pkts = []
        if first:
            for s, raw in zip(shreds, raws):
                d = sd.idx_to_dest(sd.compute_first([s])[0])
                if d is not None and d.ip and d.pubkey != self.identity:
                    pkts.append(Pkt(raw, d.addr))
        else:
            for s, raw in zip(shreds, raws):
                for idx in sd.compute_children([s], self.tree_fanout)[0]:
                    d = sd.idx_to_dest(idx)
                    if d is not None and d.ip and d.pubkey != self.identity:
                        pkts.append(Pkt(raw, d.addr))
        if pkts:
            self.tsock.send_burst(pkts)
            ctx.metrics.add("turbine_tx_cnt", len(pkts))

    def _cut(self, ctx, slot_complete: bool):
        if not self.entries and not slot_complete:
            return
        batch = self._el.serialize_batch(self.entries)
        self.entries = []
        self._size = 0
        fs = self._sl.make_fec_set(
            batch, self.slot, parent_off=1 if self.slot else 0,
            version=self.version, fec_set_idx=self.fec_idx,
            sign_fn=lambda root: self.kgc.sign(self._kg.ROLE_LEADER, root),
            data_cnt=self.data_cnt, code_cnt=self.data_cnt,
            slot_complete=slot_complete)
        self.fec_idx += self.data_cnt
        ctx.metrics.add("fec_set_cnt")
        raws = fs.data_shreds + fs.code_shreds
        for raw in raws:
            for out in self._fanout:
                ctx.publish(raw, sig=self.slot, out=out)
                ctx.metrics.add("shred_tx_cnt")
        if self.turbine is not None:
            self._turbine_send(
                ctx, [self._sl.parse(r) for r in raws], raws, first=True)

    def _on_net_shred(self, ctx, payload):
        """Turbine ingress (non-leader): verify leader signature, dedup,
        store-forward + retransmit to my children exactly once per shred
        (fd_shred.c's retransmit path).  Admission is BATCHED (round 13):
        the shred queues into _ShredSigBatcher and forwards only when the
        burst verdict lands (size or age triggered) — one merkle-walk and
        one signature dispatch per burst instead of per shred."""
        try:
            s = self._sl.parse(payload)
        except self._sl.ShredParseError:
            ctx.metrics.add("shred_parse_fail_cnt")
            return
        if self.turbine is None:
            # no signature gate: publish the dcache view as-is — the out
            # ring copies it, so no per-shred bytes() materialization
            for out in self._fanout:
                ctx.publish(payload, sig=s.slot, out=out)
            ctx.metrics.add("shred_rx_cnt")
            return
        tag = (s.slot << 17) | (s.idx << 1) | (1 if s.is_data else 0)
        # query-only dedup BEFORE the signature check; the tag is
        # inserted only after the shred proves leader-signed, so a
        # forged copy cannot poison the cache and censor the real one
        # (same discipline as pipeline.py's pre-dedup)
        if self._retx_seen.query(tag):
            return                              # duplicate: drop entirely
        try:
            leader = self._leaders(s.slot)
        except Exception:
            leader = None
        # ONE copy per shred: payload is an in-ring dcache view the mux
        # will reuse, but the verdict is deferred — the same buffer then
        # serves every fan-out publish AND the retransmit send
        self._sigb.add(s, bytes(payload), tag, leader)
        if self._sigb.full:
            self._admit(ctx, self._sigb.flush())

    def _admit(self, ctx, verdicts):
        """Apply a batched admission verdict (FIFO): re-check dedup (a
        duplicate may have queued in the SAME burst window), insert,
        fan out, retransmit."""
        if not verdicts:
            return
        ctx.metrics.add("sig_batch_cnt")
        for s, raw, tag, ok in verdicts:
            if not ok:
                ctx.metrics.add("shred_sig_fail_cnt")
                continue
            if self._retx_seen.query(tag):
                continue                # dup admitted earlier in the burst
            self._retx_seen.insert(tag)
            for out in self._fanout:
                ctx.publish(raw, sig=s.slot, out=out)
            ctx.metrics.add("shred_rx_cnt")
            if self._leaders(s.slot) != self.identity:
                self._turbine_send(ctx, [s], [raw], first=False)

    def after_credit(self, ctx):
        if self.turbine is not None and self._sigb.due():
            ctx.metrics.add("sig_deadline_flush_cnt")
            self._admit(ctx, self._sigb.flush())

    def on_frag(self, ctx, iidx, meta, payload):
        if ctx.tile.in_links[iidx].link in self.net_ins:
            self._on_net_shred(ctx, payload)
            return
        sig = int(meta["sig"])
        slot = sig & ~PohTile.SLOT_DONE_BIT
        done = bool(sig & PohTile.SLOT_DONE_BIT)
        if self.slot is None:
            self.slot = slot
        if slot != self.slot:  # missed the done marker: close anyway
            self._cut(ctx, True)
            self.slot, self.fec_idx = slot, 0
        e, _ = self._el.Entry.deserialize(payload)
        self.entries.append(e)
        self._size += len(payload)
        if done:
            self._cut(ctx, True)
            self.slot, self.fec_idx = slot + 1, 0
        elif self._size >= self.batch_max:
            self._cut(ctx, False)  # mid-slot set: bound FEC batch size

    def fini(self, ctx):
        if self.entries and self.slot is not None:
            try:
                self._cut(ctx, True)
            except Exception:
                pass  # keyguard may already be down
        if self.turbine is not None:
            try:
                self._admit(ctx, self._sigb.flush())  # drain the tail
            except Exception:
                pass  # downstream rings may already be gone
            self.tsock.close()


class StoreTile:
    """Shred sink into the blockstore (ref: src/app/fdctl/run/tiles/
    fd_store.c): inserts incoming shreds, tracks FEC recovery and complete
    slots.  cfg: max_slots; the `complete_slot` metrics slot exports the
    highest fully-assembled slot (how tests observe block completion)."""

    def init(self, ctx):
        from ..ballet.shred import ShredParseError
        from ..flamenco.blockstore import Blockstore, SlotArchive
        self._perr = ShredParseError
        # optional disk archive (fd_blockstore's RocksDB role): completed
        # slots persist past the in-memory retention window
        arch_path = ctx.cfg.get("archive_path")
        self.store = Blockstore(
            ctx.cfg.get("max_slots", 1024),
            archive=SlotArchive(arch_path) if arch_path else None)
        self.complete = 0

    def on_frag(self, ctx, iidx, meta, payload):
        try:
            self.store.insert_shred(payload)
        except self._perr:
            ctx.metrics.add("parse_fail_cnt")
            return
        ctx.metrics.add("shred_store_cnt")
        slot = int(meta["sig"]) & ~PohTile.SLOT_DONE_BIT
        if slot > self.complete and self.store.slot_complete(slot):
            self.complete = slot
            ctx.metrics.set("complete_slot", slot)


class ShredRecoverIngest:
    """Batched RS-recover workload over the packed rotation core (round
    13): one FEC set per row in ballet.reedsol's recover_blob layout
    (surv | ref | have), the per-set reconstruction bit-matrices riding
    in a SIBLING array stamped alongside each rotating buffer.  The
    dispatch/harvest/backpressure machinery is models.verifier's
    PackedDispatchEngine — the same engine sigverify ingest rotates —
    via a shred-recover WorkloadDesc (composed, not subclassed: the
    engine import pulls jax, which must stay out of tiles.py module
    import for net-only processes)."""

    def __init__(self, k_max: int = 32, n_max: int = 64, sz: int = 1019,
                 batch: int = 8, nbuf: int = 2, depth: int | None = None):
        import functools

        import jax

        from ..ballet import reedsol as rs
        from ..models.verifier import PackedDispatchEngine, WorkloadDesc
        self._rs = rs
        self._jax = jax
        self.k_max, self.n_max, self.sz = k_max, n_max, sz
        self.batch = batch
        self._fn = jax.jit(functools.partial(
            rs.recover_blob, k_max=k_max, n_max=n_max, sz=sz))
        self._eng = PackedDispatchEngine(
            WorkloadDesc(
                name="shred-recover",
                rows=batch,
                row_bytes=rs.recover_blob_row_bytes(k_max, n_max, sz),
                true_rows=batch,
                dispatch=self._dispatch),
            nbuf=nbuf, depth=depth)
        # sibling bit-matrix per rotating buffer, paired by buffer id
        self._bitmats = [
            np.zeros((batch, 8 * n_max, 8 * k_max), np.int8)
            for _ in range(nbuf)]
        self._bidx = {id(b): i for i, b in enumerate(self._eng._bufs)}

    # engine passthroughs (observability + harvest surface)
    @property
    def dispatches(self):
        return self._eng.dispatches

    @property
    def inflight_depth(self):
        return self._eng.inflight_depth

    def poll(self):
        return self._eng.poll()

    def drain(self):
        return self._eng.drain()

    def _dispatch(self, buf):
        bm = self._bitmats[self._bidx[id(buf)]]
        return self._fn(self._jax.device_put(buf),
                        self._jax.device_put(bm))

    def warm(self) -> None:
        """Pre-RUN compile: run one zero-filled dispatch to completion
        (padding rows are self-consistent, so the verdict is all-ok)."""
        self._eng.submit_packed(lambda buf: None, 0)
        self._eng.drain()

    def submit_sets(self, sets: list):
        """Stamp up to `batch` recover_args triples — every set must be
        at this engine's fixed sz and within (k_max, n_max) — into one
        rotating row blob + sibling bit-matrix and dispatch.  Returns
        verdicts retired by the inflight window this call (each a
        (batch, n_max*sz + 1) u8 array; pair rows to sets FIFO)."""
        if len(sets) > self.batch:
            raise ValueError(f"{len(sets)} sets > engine batch {self.batch}")
        return self._eng.submit_packed(
            lambda buf: self._stamp(buf, sets), len(sets))

    def _stamp(self, buf, sets) -> None:
        rs = self._rs
        k_max, n_max, sz = self.k_max, self.n_max, self.sz
        ks, ns = k_max * sz, n_max * sz
        buf[:] = 0
        bm = self._bitmats[self._bidx[id(buf)]]
        bm[:] = 0
        for r, (shreds, k, set_sz) in enumerate(sets):
            n = len(shreds)
            if set_sz != sz or k > k_max or n > n_max:
                raise ValueError(
                    f"set geometry (k={k}, n={n}, sz={set_sz}) outside "
                    f"engine ({k_max}, {n_max}, {sz})")
            have = [i for i, s in enumerate(shreds) if s is not None]
            if len(have) < k:
                raise ValueError(
                    f"unrecoverable: only {len(have)} of {k} needed shreds")
            use = tuple(have[:k])
            row = buf[r]
            for c, i in enumerate(use):
                row[c * sz:(c + 1) * sz] = np.frombuffer(
                    shreds[i], np.uint8, count=sz)
            for i in have:
                row[ks + i * sz:ks + (i + 1) * sz] = np.frombuffer(
                    shreds[i], np.uint8, count=sz)
                row[ks + ns + i] = 1
            bm[r, :8 * n, :8 * k] = rs._recover_bitmat(k, n, use)

    def split_verdict(self, v: np.ndarray):
        """(full (batch, n_max, sz) u8, ok (batch,) bool) off one verdict
        row blob."""
        ns = self.n_max * self.sz
        full = v[:, :ns].reshape(len(v), self.n_max, self.sz)
        return full, v[:, ns].astype(bool)


class ShredRecoverTile:
    """FEC recovery tile (round 13; ref: fd_fec_resolver.c feeding
    fd_store): accumulates verified shreds into per-(slot, fec_set_idx)
    resolvers and, when a set becomes recoverable, stamps its survivors
    into a packed recover row dispatched through the SAME double-buffer
    engine shape as sigverify ingest — the reconstruction matmul runs
    once per BURST of sets, not once per set.  All-data completions
    (repair serves data only) publish immediately with no device work.

    In: shred links (the shred tile's verified fan-out).  Out: one
    reassembled entry-batch payload per recovered FEC set (sig = slot).
    cfg: fec_data_cnt (k_max, default 32), fec_code_cnt (default =
    fec_data_cnt), shred_sz (default derived from the geometry's proof
    depth), batch_sets (rows per dispatch, default 8), nbuf, depth,
    flush_age_us (partial-batch deadline, default 5000).
    metrics: shred_rx_cnt, shred_parse_fail_cnt, fec_complete_cnt,
    fec_recovered_cnt, fec_dispatch_cnt, fec_fail_cnt, recover_pending
    (gauge)."""

    def init(self, ctx):
        from ..ballet import shred as shred_lib
        self._sl = shred_lib
        self.k_max = ctx.cfg.get("fec_data_cnt", 32)
        self.c_max = ctx.cfg.get("fec_code_cnt", self.k_max)
        self.n_max = self.k_max + self.c_max
        sz = ctx.cfg.get("shred_sz")
        if sz is None:
            # protected span = 1139 - 20 * proof_len for this geometry
            sz = 1139 - 20 * max(1, (self.n_max - 1).bit_length())
        self.sz = sz
        self.batch_sets = ctx.cfg.get("batch_sets", 8)
        self.flush_age_us = ctx.cfg.get("flush_age_us", 5000)
        self.ingest = ShredRecoverIngest(
            k_max=self.k_max, n_max=self.n_max, sz=sz,
            batch=self.batch_sets, nbuf=ctx.cfg.get("nbuf", 2),
            depth=ctx.cfg.get("depth"))
        from collections import deque
        self.ingest.warm()       # compile BEFORE signaling RUN
        # bounded working state: open resolvers and the recovered-set
        # dedup both evict oldest-first (a slot's worth of sets is tiny
        # next to these bounds; unbounded growth would leak across epochs)
        self.max_open = ctx.cfg.get("max_open_sets", 1 << 12)
        self._sets = OrderedDict()        # (slot, fec_set_idx) -> resolver
        self._queue: list = []   # (key, resolver, recover_args triple)
        self._queued = OrderedDict()      # recovered-set dedup (as a set)
        self._q_t0 = None
        self._pending = deque()  # dispatch FIFO: [(key, resolver), ...]

    def _publish(self, ctx, key, regions):
        payload = self._sl.FecResolver.assemble_payload(regions)
        ctx.publish(payload, sig=key[0])
        ctx.metrics.add("fec_complete_cnt")

    def _dispatch(self, ctx):
        sets, self._queue = self._queue, []
        self._q_t0 = None
        if not sets:
            return
        args = [a for (_k, _r, a) in sets]
        self._pending.append([(k, r) for (k, r, _a) in sets])
        ctx.metrics.add("fec_dispatch_cnt")
        for v in self.ingest.submit_sets(args):
            self._retire(ctx, v)

    def _retire(self, ctx, verdict):
        full, ok = self.ingest.split_verdict(verdict)
        metas = self._pending.popleft()
        for r, (key, resolver) in enumerate(metas):
            if not bool(ok[r]):
                # a surviving shred inconsistent with the re-derived
                # encoding: the set is corrupt, drop it (ERR_CORRUPT)
                ctx.metrics.add("fec_fail_cnt")
                continue
            ctx.metrics.add("fec_recovered_cnt")
            self._publish(ctx, key, resolver.data_regions(full[r]))

    def on_frag(self, ctx, iidx, meta, payload):
        try:
            s = self._sl.parse(payload)
        except self._sl.ShredParseError:
            ctx.metrics.add("shred_parse_fail_cnt")
            return
        ctx.metrics.add("shred_rx_cnt")
        key = (s.slot, s.fec_set_idx)
        if key in self._queued:
            return                       # set already recovering/complete
        fr = self._sets.get(key)
        if fr is None:
            fr = self._sets[key] = self._sl.FecResolver()
            while len(self._sets) > self.max_open:
                self._sets.popitem(last=False)
        if not fr.add(s) or not fr.ready():
            return
        self._queued[key] = None
        while len(self._queued) > self.max_open:
            self._queued.popitem(last=False)
        self._sets.pop(key, None)
        args = fr.recover_args()
        if args is None:
            # all-data completion: regions read straight off the shreds
            self._publish(ctx, key, fr.data_regions())
            return
        shreds, k, set_sz = args
        if (set_sz != self.sz or k > self.k_max
                or len(shreds) > self.n_max):
            # geometry outside the compiled engine: host per-set fallback
            # (counted, never silent — cfg should match the deployment)
            ctx.metrics.add("fec_host_fallback_cnt")
            try:
                full = self._sl.reedsol.recover(shreds, k, set_sz,
                                                device=False)
            except ValueError:
                ctx.metrics.add("fec_fail_cnt")
                return
            self._publish(ctx, key, fr.data_regions(full))
            return
        self._queue.append((key, fr, args))
        if self._q_t0 is None:
            self._q_t0 = time.monotonic_ns()
        if len(self._queue) >= self.batch_sets:
            self._dispatch(ctx)

    def after_credit(self, ctx):
        for v in self.ingest.poll():     # non-blocking verdict harvest
            self._retire(ctx, v)
        if (self._q_t0 is not None
                and time.monotonic_ns() - self._q_t0
                >= self.flush_age_us * 1000):
            self._dispatch(ctx)
        ctx.metrics.set("recover_pending", len(self._pending))

    def fini(self, ctx):
        try:
            self._dispatch(ctx)
            for v in self.ingest.drain():
                self._retire(ctx, v)
        except Exception:
            pass  # downstream rings may already be gone


def _ed25519_verify_one(sig: bytes, msg: bytes, pub: bytes) -> bool:
    from ..ops.ed25519 import verify_one
    return verify_one(sig, msg, pub)


def _ed25519_verify_host(sig: bytes, msg: bytes, pub: bytes) -> bool:
    """Host python-int verify for control-plane rates: same acceptance
    rules as verify_one, no device round trip (a synchronous device
    dispatch + fetch per item would dominate control-plane work)."""
    from ..ops.ed25519 import verify_one_host
    return verify_one_host(sig, msg, pub)


class ReplayTile:
    """Follower-side fork-aware replay + consensus tile (ref:
    src/disco/tvu/fd_tvu.c over src/choreo — replay competing forks into
    fork banks, count replayed votes into ghost, vote per TowerBFT, root
    when the tower roots).  The state machine is flamenco.replay.ForkReplay;
    this tile feeds it shreds and exports its decisions.

    Votes are signed through the keyguard when the `vote_sign`/`sign_vote`
    link pair is wired; signed vote txns are published to every other out
    link (toward gossip / the local TPU ingest).

    cfg: genesis_path, poh_start (hex), vote_account (hex, enables
    voting), identity_pub (hex; with keyguard) | key_path.
    metrics: replay_slot (highest replayed), ghost_head, root_slot,
    dead_slot_cnt, vote_cnt, txn_replay_cnt."""

    def init(self, ctx):
        from ..ballet.shred import ShredParseError
        from ..choreo.voter import Voter
        from ..flamenco.blockstore import Blockstore
        from ..flamenco.genesis import Genesis
        from ..flamenco.replay import ForkReplay
        from ..flamenco.runtime import Runtime
        from . import keyguard
        self._perr = ShredParseError
        self._kg = keyguard
        self.store = Blockstore(ctx.cfg.get("max_slots", 1024))
        self.rt = Runtime(Genesis.read(ctx.cfg["genesis_path"]))
        poh = ctx.cfg.get("poh_start")
        poh = bytes.fromhex(poh) if poh else bytes(32)
        if "vote_sign" in ctx.tile.out_links:
            self.kgc = keyguard.KeyguardClient(ctx, "vote_sign", "sign_vote")
            identity = bytes.fromhex(ctx.cfg["identity_pub"])
            self._local_sign = None
        else:
            self.kgc = None
            if ctx.cfg.get("key_path"):
                from ..ops import ed25519 as ed
                seed, identity = keyguard.keypair_read(ctx.cfg["key_path"])
                self._local_sign = lambda m: ed.sign(seed, m)
            else:
                identity = bytes(32)
                self._local_sign = None
        vote_acct = ctx.cfg.get("vote_account")
        self.voter = Voter(
            vote_account=bytes.fromhex(vote_acct) if vote_acct else bytes(32),
            node_pubkey=identity)
        self.fr = ForkReplay(self.rt, self.store, self.voter, poh)
        self._vote_outs = [i for i, ln in enumerate(ctx.tile.out_links)
                          if ln != "vote_sign"]

    def on_frag(self, ctx, iidx, meta, payload):
        try:
            completed = self.store.insert_shred(payload)
        except self._perr:
            return
        if completed:
            # only a completed FEC set can complete a slot: keeps the
            # O(n)-over-store drain scan off the per-shred hot path
            self._drain(ctx)

    def _sign_and_publish_vote(self, ctx, msg: bytes):
        from ..ballet import txn as txn_lib
        if self.kgc is not None:
            sig = self.kgc.sign(self._kg.ROLE_VOTER, msg)
        elif self._local_sign is not None:
            sig = self._local_sign(msg)
        else:
            return
        payload = txn_lib.assemble([sig], msg)
        for out in self._vote_outs:
            ctx.publish(payload, sig=int.from_bytes(sig[:8], "little"),
                        out=out)

    def _drain(self, ctx):
        events = self.fr.drain()
        if not events:
            return
        for res, decision in events:
            if not res.ok:
                ctx.metrics.add("dead_slot_cnt")
                continue
            ctx.metrics.add("txn_replay_cnt", res.txn_cnt)
            if decision is not None and decision.slot is not None:
                ctx.metrics.add("vote_cnt")
                if decision.txn_message is not None:
                    self._sign_and_publish_vote(ctx, decision.txn_message)
        ctx.metrics.set("replay_slot",
                        max(self.fr.replayed, default=self.rt.root_slot))
        ctx.metrics.set("ghost_head", self.fr.head)
        ctx.metrics.set("root_slot", self.rt.root_slot)


class GossipTile:
    """Cluster gossip tile (ref: src/app/fdctl/run/tiles/fd_gossip.c over
    src/flamenco/gossip): runs a GossipNode over its own UDP socket,
    bootstrapping from cfg `entrypoints` ([["ip", port], ...]).

    Signing is keyguard-routed when the `gossip_sign`/`sign_gossip` link
    pair is wired (cfg `identity_pub` hex; the tile then holds NO private
    key material — the reference's key-isolation contract,
    src/disco/keyguard/fd_keyguard.h:4-23).  Fallback for link-less
    topologies: in-tile signing from cfg key_path.

    cfg: identity_pub | key_path, gossip_port (0 = ephemeral, exported in
    `bound_port`), tpu_port, repair_port, entrypoints."""

    def init(self, ctx):
        from ..flamenco import gossip as gossip_mod
        from ..waltz.udpsock import UdpSock
        from . import keyguard
        self._g = gossip_mod
        if "gossip_sign" in ctx.tile.out_links:
            kgc = keyguard.KeyguardClient(ctx, "gossip_sign", "sign_gossip")
            sign_fn = lambda m: kgc.sign(keyguard.ROLE_GOSSIP, m)  # noqa: E731
            pub = bytes.fromhex(ctx.cfg["identity_pub"])
        else:
            from ..ops import ed25519 as ed
            seed, pub = keyguard.keypair_read(ctx.cfg["key_path"])
            sign_fn = lambda m: ed.sign(seed, m)  # noqa: E731
        self.sock = UdpSock(bind_port=ctx.cfg.get("gossip_port", 0))
        ctx.metrics.set("bound_port", self.sock.port)
        contact = gossip_mod.contact_info_body(
            ctx.cfg.get("advertise_ip", "127.0.0.1"), self.sock.port,
            ctx.cfg.get("tpu_port", 0), ctx.cfg.get("repair_port", 0))
        _ed25519_verify_one(bytes(64), b"warm", bytes(32))  # pre-RUN warmup
        self.node = gossip_mod.GossipNode(
            pub, sign_fn, _ed25519_verify_one, contact)
        self.entrypoints = [tuple(e) for e in ctx.cfg.get("entrypoints", [])]

    def house(self, ctx):
        from ..waltz.aio import Pkt
        outs = self.node.tick()
        # bootstrap: push our contact at the entrypoints until peers appear
        if not outs and self.entrypoints:
            push = self._g.encode_push(self.node.crds.values())
            outs = [(push, ep) for ep in self.entrypoints]
        if outs:
            self.sock.send_burst([Pkt(p, a) for p, a in outs])
        ctx.metrics.set("peer_cnt", len(self.node.crds.peers()))

    def after_credit(self, ctx):
        from ..waltz.aio import Pkt
        for pkt in self.sock.recv_burst():
            ctx.metrics.add("rx_pkt_cnt")
            replies = self.node.handle(pkt.payload, pkt.addr)
            if replies:
                self.sock.send_burst([Pkt(p, a) for p, a in replies])

    def fini(self, ctx):
        self.sock.close()


class RepairTile:
    """Shred repair tile (ref: src/app/fdctl/run/tiles/fd_repair.c): serves
    window-index requests from the local blockstore view AND runs the
    request side (RepairPlanner: gap detection, retry pacing,
    stake-weighted peer rotation) against configured peers.

    Request signing is keyguard-routed when the `repair_sign`/`sign_repair`
    link pair is wired (cfg `identity_pub` hex; no private key in-tile);
    fallback: in-tile signing from cfg key_path.  Repaired shreds are
    published to every out link except the sign request link (the store
    fan-in).

    cfg: identity_pub | key_path, repair_port (0 = ephemeral ->
    `bound_port`), peers ([[pubhex, ip, port, stake], ...]),
    plan_interval_s (default 0.05), leader_stakes ({pubhex: stake}) +
    slots_per_epoch — when given, repaired shreds must carry the slot
    leader's signature over their merkle root before they are stored or
    republished (repair peers are untrusted; without the schedule the
    tile accepts structurally-valid shreds only, flagged in metrics)."""

    def init(self, ctx):
        from ..ballet import shred as shred_lib
        from ..ballet.shred import ShredParseError
        from ..flamenco import repair as repair_mod
        from ..flamenco.blockstore import Blockstore
        from ..waltz.udpsock import UdpSock
        from . import keyguard
        self._sl = shred_lib
        self._perr = ShredParseError
        self._rm = repair_mod
        if "repair_sign" in ctx.tile.out_links:
            kgc = keyguard.KeyguardClient(ctx, "repair_sign", "sign_repair")
            sign_fn = lambda m: kgc.sign(keyguard.ROLE_REPAIR, m)  # noqa: E731
            pub = bytes.fromhex(ctx.cfg["identity_pub"])
        else:
            from ..ops import ed25519 as ed
            seed, pub = keyguard.keypair_read(ctx.cfg["key_path"])
            sign_fn = lambda m: ed.sign(seed, m)  # noqa: E731
        self._leaders = None
        if ctx.cfg.get("leader_stakes"):
            from ..flamenco.leaders import leader_schedule
            stakes = {bytes.fromhex(k): v
                      for k, v in ctx.cfg["leader_stakes"].items()}
            spe = ctx.cfg.get("slots_per_epoch", 432_000)
            sched = {}

            def leaders(slot):
                ep = slot // spe
                if ep not in sched:
                    sched[ep] = leader_schedule(ep, stakes, spe)
                return sched[ep][slot % spe]

            self._leaders = leaders
        # leader-signature gate on the blockstore's FEC resolvers too
        # (ADVICE r4): _response_shred_ok already screens repair traffic,
        # but the store-level root_check means even a shred slipping in
        # through another path cannot pin a bogus first-member root
        # repair-path crypto runs on the HOST verifier (python ints,
        # ~ms/item): these are control-plane rates, and every
        # ops.verify_one call would pay a synchronous device round trip
        # per request/shred (code-review r5)
        root_check = None
        if self._leaders is not None:
            def root_check(slot, root, sig):
                try:
                    leader = self._leaders(slot)
                except Exception:
                    return False
                return _ed25519_verify_host(sig, root, leader)
        self.store = Blockstore(ctx.cfg.get("max_slots", 1024),
                                root_check=root_check)
        self.sock = UdpSock(bind_port=ctx.cfg.get("repair_port", 0))
        ctx.metrics.set("bound_port", self.sock.port)
        self.server = repair_mod.RepairServer(
            _ed25519_verify_host,
            self.store.shred_raw, self.store.highest_shred,
            parent_of=self.store.parent_slot)
        self.client = repair_mod.RepairClient(sign_fn, pub)
        self.planner = repair_mod.RepairPlanner(self.client)
        self.peers = [(bytes.fromhex(p), (ip, port), stake)
                      for p, ip, port, stake in ctx.cfg.get("peers", ())]
        self._fanout = [i for i, ln in enumerate(ctx.tile.out_links)
                        if ln != "repair_sign"]
        self.plan_interval_s = ctx.cfg.get("plan_interval_s", 0.05)
        self._last_plan = 0.0

    def on_frag(self, ctx, iidx, meta, payload):
        """Shreds from the local store fan-in: track them so the planner
        stops re-requesting.  NOT pre_verified — upstream validation is
        config-dependent (a net-ins-only shred tile without turbine
        forwards unchecked), so the store's door gate runs here; it costs
        one HOST ed25519 verify per shred (~ms), not a device RTT."""
        try:
            sh = self._sl.parse(payload)
            self.store.insert_shred(bytes(payload), parsed=sh)
        except self._perr:
            return
        self.planner.on_shred(sh.slot, sh.idx)

    def _response_shred_ok(self, sh) -> bool:
        """Repair peers are untrusted: with a leader schedule configured,
        a response shred must carry the slot leader's signature over its
        merkle root (same check the turbine ingress runs)."""
        if self._leaders is None:
            return True
        root = sh.merkle_root()
        if root is None:
            return False
        try:
            leader = self._leaders(sh.slot)
        except Exception:
            return False
        return _ed25519_verify_host(sh.signature, root, leader)

    def _repair_wants(self) -> list[int]:
        """Slots worth repairing: known but incomplete (replay drives this
        list in the full validator; blockstore gaps are the local proxy)."""
        return [s for s in sorted(self.store.slots)
                if not self.store.slot_complete(s)][:64]

    def house(self, ctx):
        if not self.peers:
            return
        now = time.monotonic()
        if now - self._last_plan < self.plan_interval_s:
            return
        self._last_plan = now
        from ..waltz.aio import Pkt
        reqs = self.planner.plan(self.store, self._repair_wants(),
                                 self.peers)
        if reqs:
            self.sock.send_burst(
                [Pkt(req.serialize(), peer[1]) for req, peer in reqs])
            ctx.metrics.add("req_tx_cnt", len(reqs))

    def after_credit(self, ctx):
        from ..waltz.aio import Pkt
        for pkt in self.sock.recv_burst():
            # explicit wire discriminator byte (ADVICE r3: length-based
            # discrimination misparsed 113-byte responses as requests)
            if pkt.payload[:1] == bytes([self._rm.MSG_REQUEST]):
                ctx.metrics.add("req_cnt")
                resp = self.server.handle(pkt.payload)
                if resp is not None:
                    self.sock.send_burst([Pkt(resp, pkt.addr)])
                    ctx.metrics.add("served_cnt")
                continue
            raw = self.client.handle_response(bytes(pkt.payload))
            if raw is None:
                continue
            try:
                sh = self._sl.parse(raw)
            except self._perr:
                continue
            if not self._response_shred_ok(sh):
                ctx.metrics.add("resp_sig_fail_cnt")
                continue
            ctx.metrics.add("repaired_cnt")
            self.planner.on_shred(sh.slot, sh.idx)
            try:
                # pre_verified: _response_shred_ok above IS the leader-
                # signature gate (it also guards the republish below) —
                # re-running it inside the store would double the
                # repair path's crypto cost (code-review r5)
                self.store.insert_shred(raw, parsed=sh, pre_verified=True)
            except self._perr:
                continue
            for out in self._fanout:
                ctx.publish(raw, sig=sh.slot, out=out)

    def fini(self, ctx):
        self.sock.close()


class SinkTile:
    """Counts and drops (the fd_blackhole tile).

    cfg capture_path (optional): append every frag to that file as
    `u64 sig | u32 len | payload` — the offline re-verification surface
    the leader conformance/chaos harnesses read entry and microblock
    streams back from.  Capture forces the per-frag path (burst delivery
    is disabled) so file order is exactly publish order."""

    def init(self, ctx):
        self._cap = None
        path = ctx.cfg.get("capture_path") or ""
        if path:
            self._cap = open(path, "ab", buffering=0)
            self.on_burst = None       # per-frag so sigs ride along

    def on_frag(self, ctx, iidx, meta, payload):
        ctx.metrics.add("frag_cnt")
        if self._cap is not None:
            b = bytes(payload)
            self._cap.write(int(meta["sig"]).to_bytes(8, "little")
                            + len(b).to_bytes(4, "little") + b)

    def on_burst(self, ctx, iidx, metas, buf, offs, kept):
        ctx.metrics.add("frag_cnt", kept)

    def fini(self, ctx):
        if self._cap is not None:
            self._cap.close()


class MetricTile:
    """Prometheus exporter over HTTP (ref: run/tiles/fd_metric.c:135-263),
    snapshotting every tile's shared-memory metrics block."""

    def init(self, ctx):
        # same path-aware handler (/metrics + /healthz) the supervisor's
        # TopoRun(metrics_port=...) endpoint serves — one implementation
        from .run import MetricsHttpServer
        self.server = MetricsHttpServer(
            ctx.topo, port=ctx.cfg.get("port", 7999))

    def fini(self, ctx):
        self.server.close()


class NetmuxTile:
    """Frag fan-in multiplexer: N input links -> one output link, payload
    and app sig forwarded unchanged (ref:
    src/app/fdctl/run/tiles/fd_netmux.c — there it muxes net/quic/shred
    traffic onto one wire so consumers join a single mcache; same
    topology contract here)."""

    # traffic accounting rides the mux-layer counters (in_frag_cnt /
    # out_frag_cnt — disco/mux.py), matching the reference where netmux
    # has no tile-specific metrics section

    def on_frag(self, ctx, iidx, meta, payload):
        ctx.publish(payload, sig=int(meta["sig"]))

    def on_burst(self, ctx, iidx, metas, buf, offs, kept):
        ctx.publish_burst(
            buf, offs[:kept],
            (offs[1:kept + 1] - offs[:kept]).astype(np.int32),
            metas["sig"].astype(np.uint64))


class BlackholeTile:
    """Filters every frag BEFORE the payload copy (ref:
    src/app/fdctl/run/tiles/fd_blackhole.c before_frag sets opt_filter):
    the consumer-side packet sink used to terminate links whose traffic a
    topology variant doesn't consume.  Unlike SinkTile it never touches
    the dcache — pure metadata-rate drop."""

    def before_frag(self, ctx, iidx, seq, sig) -> bool:
        return True  # filter: payload never read; the mux counts the
        # drop in the standard in_filt_cnt slot


TILES: dict[str, type] = {
    "net": NetTile,
    "netmux": NetmuxTile,
    "blackhole": BlackholeTile,
    "quic": QuicTile,
    "quic_server": QuicServerTile,
    "source": SourceTile,
    "verify": VerifyTile,
    "dedup": DedupTile,
    "pack": PackTile,
    "bank": BankTile,
    "sign": SignTile,
    "poh": PohTile,
    "leader_pack": LeaderPackTile,
    "leader_merge": LeaderMergeTile,
    "poh_dev": PohDevTile,
    "shred": ShredTile,
    "shred_recover": ShredRecoverTile,
    "store": StoreTile,
    "gossip": GossipTile,
    "repair": RepairTile,
    "replay": ReplayTile,
    "sink": SinkTile,
    "metric": MetricTile,
}
