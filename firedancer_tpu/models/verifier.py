"""The flagship "model": a fixed-shape batched ed25519 verifier.

Equivalent role to the verify tile's crypto core
(ref: src/app/fdctl/run/tiles/fd_verify.c + fd_ed25519_verify_batch_single_msg),
with the wiredancer-style batch insertion point (SURVEY.md §3.2): the host
pipeline coalesces txn signatures into fixed (BATCH, MSG_MAXLEN) buffers, the
device returns pass/fail bits.
"""

import time
from collections import deque
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from firedancer_tpu.ops import ed25519 as ed


@dataclass(frozen=True)
class VerifierConfig:
    batch: int = 4096        # BASELINE.md config #2: 4096 single-sig txns
    msg_maxlen: int = 128    # padded message bucket (wire txn MTU is 1232)


class SigVerifier:
    """Jitted fixed-shape verifier.  One instance per (batch, maxlen) bucket —
    the host pipeline picks a bucket per batch, mirroring how the reference
    picks SIMD batch widths at compile time (fd_sha512.h:266-361).

    mesh / n_shards turn this into the MULTI-CHIP serving verifier: the
    batch axis shards over a 1-D 'dp' device mesh (parallel.mesh — the
    TPU-native round_robin_cnt/idx of fd_verify.c:36-47).  Dispatch places
    each packed blob with NamedSharding(P("dp", None)) and runs the
    shard_map'd verify step with the blob DONATED (steady-state dispatch
    allocates nothing per call); batches not divisible by the mesh pad
    host-side with the padding lanes masked False on device.  Per-lane
    verdicts for REAL lanes are bit-identical to the single-chip engine —
    verify is embarrassingly lane-parallel."""

    def __init__(self, cfg: VerifierConfig = VerifierConfig(),
                 mesh=None, n_shards: int | None = None):
        if n_shards is not None and mesh is None:
            from firedancer_tpu.parallel import mesh as pm
            mesh = pm.make_mesh(n_shards)
        if mesh is not None and "dp" not in mesh.shape:
            raise ValueError(
                f"verifier mesh needs a 'dp' axis, got {dict(mesh.shape)}")
        self.mesh = mesh
        self.n_shards = int(mesh.shape["dp"]) if mesh is not None else 1
        self.cfg = cfg
        self._fn = jax.jit(ed.verify_batch)
        self._packed_cache = {}
        self._mesh_step = None       # lazily-built sharded 4-array step
        self._blob_sharding = None
        if mesh is not None:
            from firedancer_tpu.parallel import mesh as pm
            self._blob_sharding = pm.blob_sharding(mesh)

    def example_args(self, valid: bool = True, seed: int = 1234):
        """Build a host-side example batch (valid signatures by default)."""
        return make_example_batch(self.cfg.batch, self.cfg.msg_maxlen, valid, seed)

    # -- packed ingest ----------------------------------------------------
    # One contiguous (batch, ml+100) blob per dispatch: msgs[:ml] | sigs |
    # pubs | lens, uploaded with a SINGLE device_put and unpacked on
    # device inside the jitted verify graph: one transfer per batch
    # instead of four — the wiredancer DMA-push shape
    # (src/wiredancer/c/wd_f1.h:85-113: txns enter the card as one
    # contiguous write, not per-field buffers).

    def packed_dispatch(self, msgs, lens, sigs, pubs, ml: int | None = None):
        """Drop-in for __call__: same verdict device array, single-blob
        upload.  ml trims message columns to a known static bound (e.g.
        max true length in a fixed-length bench batch); default packs the
        full msg_maxlen."""
        msgs = np.asarray(msgs)
        lens = np.ascontiguousarray(lens, dtype=np.int32)
        if ml is None:
            ml = msgs.shape[1]
        packed = np.concatenate(
            [msgs[:, :ml], np.asarray(sigs), np.asarray(pubs),
             lens.view(np.uint8).reshape(len(lens), 4)], axis=1)
        if self.mesh is not None:
            return self._dispatch_sharded(packed, ml, msgs.shape[1])
        blob = jax.device_put(packed)
        return self._packed_fn(ml, msgs.shape[1])(blob)

    def _dispatch_sharded(self, packed: np.ndarray, ml: int, maxlen: int):
        """Sharded single-blob dispatch: pad rows to the mesh, place with
        P(dp, None) (ONE device_put splits the contiguous blob into
        per-device row slices), run the donated shard_map step.  Padding
        lanes are masked False on device; the verdict is trimmed back to
        the caller's batch."""
        from firedancer_tpu.parallel import mesh as pm
        b = packed.shape[0]
        padded = pm.pad_rows(packed, self.n_shards)
        rows = b if padded.shape[0] != b else None
        dev = jax.device_put(padded, self._blob_sharding)
        ok = self._packed_fn(ml, maxlen, rows=rows)(dev)
        return ok[:b] if rows is not None else ok

    def dispatch_blob(self, blob, maxlen: int | None = None):
        """Dispatch an ALREADY-packed (batch, maxlen+100) row-interleaved
        bucket (the pipeline's packed_rows layout, filled in place by the
        native burst parser): one device_put, zero host-side concat."""
        if maxlen is None:
            maxlen = blob.shape[1] - ed.PACKED_EXTRA
        if self.mesh is not None:
            return self._dispatch_sharded(np.asarray(blob), maxlen, maxlen)
        return self._packed_fn(maxlen, maxlen)(jax.device_put(blob))

    def _packed_fn(self, ml: int, maxlen: int, rows: int | None = None):
        key = (ml, maxlen, rows)
        fn = self._packed_cache.get(key)
        if fn is None:
            if self.mesh is not None:
                from firedancer_tpu.parallel import mesh as pm
                fn = pm.shard_verify_blob(
                    self.mesh, maxlen=maxlen, ml=ml, true_rows=rows)
            else:
                fn = jax.jit(partial(ed.verify_blob, maxlen=maxlen, ml=ml))
            self._packed_cache[key] = fn
        return fn

    def make_ingest(self, ml: int | None = None, nbuf: int = 2,
                    depth: int | None = None) -> "PackedIngest":
        """Double-buffered fresh-ingest engine over this verifier's packed
        dispatch."""
        return PackedIngest(self, ml=ml, nbuf=nbuf, depth=depth)

    def __call__(self, msgs, msg_len, sigs, pubkeys):
        if self.mesh is not None:
            return self._mesh_verify(msgs, msg_len, sigs, pubkeys)
        return self._fn(msgs, msg_len, sigs, pubkeys)

    def _mesh_verify(self, msgs, msg_len, sigs, pubkeys):
        """Per-sig 4-array verify over the dp mesh (shard_verify_step):
        uneven batches pad host-side (zero sig/pub lanes verify False and
        are trimmed from the verdict)."""
        from firedancer_tpu.parallel import mesh as pm
        if self._mesh_step is None:
            self._mesh_step = pm.shard_verify_step(self.mesh)
        arrs = (np.asarray(msgs), np.asarray(msg_len, dtype=np.int32),
                np.asarray(sigs), np.asarray(pubkeys))
        b = arrs[2].shape[0]
        padded = tuple(pm.pad_rows(a, self.n_shards) for a in arrs)
        ok, _passes = self._mesh_step(*pm.shard_batch(self.mesh, *padded))
        return ok[:b] if padded[2].shape[0] != b else ok


@dataclass(frozen=True)
class WorkloadDesc:
    """Everything the double-buffer rotation core needs to know about a
    workload (round 13): PackedIngest used to hard-code the sigverify
    pieces — row geometry, the packed verify dispatch, the verdict trim —
    which made the engine unusable for the second packed workload (shred
    recover).  The descriptor names them:

      name              AOT key family / debug label ("verify-packed",
                        "shred-recover", ...)
      rows, row_bytes   rotating-blob geometry (rows includes any mesh
                        padding; padding rows stay zero forever)
      true_rows         rows the caller actually fills — verdicts trim to
                        this on harvest
      dispatch          np blob -> async device verdict handle (the
                        single-device_put upload + jitted compute)
      dispatch_external optional caller-owned-blob variant (zero-copy
                        submit_rows); defaults to `dispatch`
      harvest           optional host post-process applied to the
                        materialized verdict before the trim (e.g. the
                        shred workload splits packed full||ok columns)
    """

    name: str
    rows: int
    row_bytes: int
    true_rows: int
    dispatch: object
    dispatch_external: object = None
    harvest: object = None


class PackedDispatchEngine:
    """Workload-agnostic upload/compute double-buffering (the wiredancer
    async-DMA-push shape, src/wiredancer/c/wd_f1.h:85-113: work streams
    into the card while the previous batch computes).

    `nbuf` rotating host-side packed blobs: batch k+1 packs into a free
    buffer and starts its single-blob device_put + dispatch while batch
    k's compute runs on device.  An explicit inflight window (`depth`,
    dispatch-ahead bound) applies backpressure: when full, a submit
    harvests (blocks on) the OLDEST verdict before dispatching more —
    bounded queueing, never unbounded run-ahead.

    Buffer-safety invariant (tests/test_ingest_overlap.py): a blob
    returns to the free ring only when its batch's verdict has
    MATERIALIZED on host — the upload and the compute that read it are
    then provably complete on the in-order device queue, so the buffer
    can be repacked without a torn read even on backends where
    device_put aliases host memory (jax CPU).

    The workload itself — what a row means, what graph runs, what the
    verdict looks like — lives entirely in the WorkloadDesc; sigverify
    (PackedIngest) and shred recover (disco.tiles.ShredRecoverIngest)
    share this core."""

    def __init__(self, desc: WorkloadDesc, nbuf: int = 2,
                 depth: int | None = None):
        if nbuf < 2:
            raise ValueError(f"need >= 2 buffers to overlap, got {nbuf}")
        if depth is None:
            depth = nbuf - 1
        if depth < 1:
            raise ValueError(f"inflight depth must be >= 1, got {depth}")
        self.desc = desc
        self.depth = depth
        self.rows = desc.rows
        self._bufs = [np.zeros((desc.rows, desc.row_bytes), dtype=np.uint8)
                      for _ in range(nbuf)]
        self._free = deque(range(nbuf))
        self._inflight: deque[tuple[object, int]] = deque()  # (ok_dev, buf)
        # observability: dispatches, blocking harvests forced by a full
        # window (backpressure events), the deepest window reached, and
        # the host-side pack cost (BENCH ingest_pack_us_txn)
        self.dispatches = 0
        self.backpressure_waits = 0
        self.max_depth_seen = 0
        self.pack_ns = 0
        self.pack_txns = 0

    @property
    def inflight_depth(self) -> int:
        return len(self._inflight)

    @property
    def pack_us_txn(self) -> float:
        """Mean host-side pack cost per lane (us) across all submits."""
        return self.pack_ns / max(self.pack_txns, 1) / 1e3

    def stats(self) -> dict:
        """Observability snapshot (round 14: every packed workload —
        sigverify, shred recover, poh — reports the same counters to its
        tile metrics / BENCH record instead of cherry-picking fields)."""
        return {
            "dispatches": self.dispatches,
            "backpressure_waits": self.backpressure_waits,
            "max_depth_seen": self.max_depth_seen,
            "inflight_depth": self.inflight_depth,
            "pack_us_txn": self.pack_us_txn,
        }

    def _harvest_oldest(self) -> np.ndarray:
        ok_dev, bidx = self._inflight.popleft()
        ok = np.asarray(ok_dev)          # blocks until upload+compute done
        if bidx is not None:             # caller-owned blobs never pool
            self._free.append(bidx)
        if self.desc.harvest is not None:
            ok = self.desc.harvest(ok)
        tr = self.desc.true_rows
        return ok[:tr] if len(ok) != tr else ok

    def _enqueue(self, ok_dev, bidx, out: list) -> None:
        # start the device->host verdict copy NOW (r4 lesson: a harvest
        # fetch that starts the copy waits out the whole transfer)
        start_async = getattr(ok_dev, "copy_to_host_async", None)
        if start_async is not None:
            start_async()
        self._inflight.append((ok_dev, bidx))
        self.dispatches += 1
        self.max_depth_seen = max(self.max_depth_seen, len(self._inflight))
        while len(self._inflight) > self.depth:
            out.append(self._harvest_oldest())

    def submit_packed(self, fill_fn, count: int) -> list[np.ndarray]:
        """Generic rotating submit: acquire a free buffer (harvesting the
        oldest verdict first under backpressure), fill it via
        fill_fn(buf) — timed into the pack stats with `count` work
        items — and dispatch through the workload descriptor.  Returns
        any verdicts retired by the inflight window this call, in
        dispatch order."""
        out = []
        if not self._free:
            # every buffer is pinned under an inflight dispatch: apply
            # backpressure by retiring the oldest before repacking
            self.backpressure_waits += 1
            out.append(self._harvest_oldest())
        bidx = self._free.popleft()
        buf = self._bufs[bidx]
        t_pack = time.perf_counter_ns()
        try:
            fill_fn(buf)
        except BaseException:
            # a failed pack must not leak the rotation buffer: the row
            # blob was never dispatched, so it goes straight back on the
            # free ring and the engine stays usable
            self._free.appendleft(bidx)
            raise
        self.pack_ns += time.perf_counter_ns() - t_pack
        self.pack_txns += count
        self._enqueue(self.desc.dispatch(buf), bidx, out)
        return out

    def submit_rows(self, rows) -> list[np.ndarray]:
        """Zero-copy submit (round 8): `rows` is an ALREADY-packed row
        blob — e.g. a dcache view the producer stamped in wire format —
        dispatched as-is with NO host repack.

        The no-torn-buffer invariant transfers to the CALLER: `rows` must
        stay unmutated until this batch's verdict is harvested (on jax CPU
        device_put aliases host memory).  The dispatch is pinned in the
        same inflight window as rotation buffers but never enters the free
        ring — the caller owns the memory."""
        out = []
        dispatch = self.desc.dispatch_external or self.desc.dispatch
        self._enqueue(dispatch(rows), None, out)
        return out

    def poll(self) -> list[np.ndarray]:
        """Harvest every verdict that is ALREADY materialized, in
        dispatch order, without blocking (round 13: a tile housekeeping
        hook drains finished device work between frags; blocking there
        would stall ingest).  Backends whose arrays lack is_ready()
        report nothing ready — callers fall back to drain()/submit
        retirement."""
        out = []
        while self._inflight:
            ready = getattr(self._inflight[0][0], "is_ready", None)
            if ready is None or not ready():
                break
            out.append(self._harvest_oldest())
        return out

    def drain(self) -> list[np.ndarray]:
        """Harvest every outstanding verdict, in dispatch order."""
        out = []
        while self._inflight:
            out.append(self._harvest_oldest())
        return out


class PackedIngest(PackedDispatchEngine):
    """Sigverify workload over the rotation core (VERDICT r5 Next #4):
    rows are the packed row-interleaved verify layout
    (msg[ml] | sig | pub | len), dispatch is the verifier's single-blob
    packed verify, verdict is the per-lane bool vector.

    Multi-chip (round 7): over a mesh-mode verifier the SAME rotation
    runs sharded — buffer rows pad to a multiple of the mesh (the
    per-device slices are contiguous host-side), each rotation's upload
    is still ONE device_put (against NamedSharding(P("dp", None)), which
    splits the blob across chips), and the dispatch runs the donated
    shard_map step.  The no-torn-buffer invariant is unchanged per
    shard: verdict materialization still proves every chip's upload and
    verify complete before the blob re-enters the free ring."""

    def __init__(self, verifier: "SigVerifier", ml: int | None = None,
                 nbuf: int = 2, depth: int | None = None):
        self.verifier = verifier
        cfg = verifier.cfg
        self.batch = cfg.batch
        self.ml = cfg.msg_maxlen if ml is None else ml
        self.maxlen = cfg.msg_maxlen
        # sharded rotation: rows pad to the mesh so every device gets an
        # equal slice; rows beyond batch stay zero forever (pack never
        # touches them) and are masked False on device
        self.shards = verifier.n_shards
        rows = self.batch + ((-self.batch) % self.shards)
        super().__init__(
            WorkloadDesc(
                name="verify-packed",
                rows=rows,
                row_bytes=self.ml + ed.PACKED_EXTRA,
                true_rows=self.batch,
                dispatch=self._dispatch_rotating,
                dispatch_external=self._dispatch_external,
            ),
            nbuf=nbuf, depth=depth)

    def _dispatch_rotating(self, buf):
        v = self.verifier
        if v.mesh is not None:
            blob = jax.device_put(buf, v._blob_sharding)
            rows = self.batch if self.rows != self.batch else None
            return v._packed_fn(self.ml, self.maxlen, rows=rows)(blob)
        return v._packed_fn(self.ml, self.maxlen)(jax.device_put(buf))

    def _dispatch_external(self, rows):
        ml = rows.shape[1] - ed.PACKED_EXTRA
        v = self.verifier
        if v.mesh is not None:
            if rows.shape[0] % v.n_shards:
                raise ValueError(
                    f"rows batch {rows.shape[0]} not divisible by "
                    f"mesh shards {v.n_shards}")
            blob = jax.device_put(np.asarray(rows), v._blob_sharding)
            return v._packed_fn(ml, ml)(blob)
        return v._packed_fn(ml, ml)(jax.device_put(rows))

    def _pack_into(self, buf, msgs, lens, sigs, pubs):
        # bulk since round 6; round 7 collapses the four column writes
        # into ONE C-level concatenate pass straight into the blob
        ml = self.ml
        msgs = np.asarray(msgs)
        lens = np.ascontiguousarray(lens, dtype=np.int32)
        np.concatenate(
            [msgs[:, :ml], np.asarray(sigs), np.asarray(pubs),
             lens.view(np.uint8).reshape(len(lens), 4)],
            axis=1, out=buf[:self.batch])

    def submit(self, msgs, lens, sigs, pubs) -> list[np.ndarray]:
        """Pack one batch into a rotating buffer and dispatch it.  Returns
        any verdicts retired by the inflight window this call (in dispatch
        order); the submitted batch's own verdict surfaces on a later
        submit() or drain()."""
        return self.submit_packed(
            lambda buf: self._pack_into(buf, msgs, lens, sigs, pubs),
            self.batch)


def use_legacy_pack() -> bool:
    """FDTPU_INGEST_LEGACY_PACK=1 routes packed ingest through the
    host-side `_pack_into` concatenate (the pre-round-8 path, kept
    bit-identical) instead of zero-copy `submit_rows` / dcache views."""
    import os
    return os.environ.get("FDTPU_INGEST_LEGACY_PACK", "0") == "1"


def use_native_hostpath() -> bool:
    """FDTPU_INGEST_NATIVE_HOSTPATH=0 disables the round-11 one-pass C
    submit/harvest kernel (native/hostpath.cpp), forcing the NumPy
    fallback (an A/B knob).  Default
    on; the pipeline also falls back on its own when the .so cannot
    build or the tcache is not native."""
    import os
    return os.environ.get("FDTPU_INGEST_NATIVE_HOSTPATH", "1") != "0"


def host_verify_arrays(msgs, lens, sigs, pubs):
    """CPU ed25519 fallback backend (degraded mode): per-lane host verify
    (ops.ed25519.verify_one_host) with acceptance rules bit-identical to
    the device graph.  Orders of magnitude slower than a device dispatch;
    the point is to keep verdicts FLOWING while the device path heals
    (pipeline.GuardedVerifier), not to keep line rate."""
    msgs = np.asarray(msgs, dtype=np.uint8)
    lens = np.asarray(lens).astype(np.int64)
    sigs = np.asarray(sigs, dtype=np.uint8)
    pubs = np.asarray(pubs, dtype=np.uint8)
    out = np.zeros(len(msgs), dtype=bool)
    for i in range(len(msgs)):
        sig = bytes(sigs[i])
        pub = bytes(pubs[i])
        if not (any(sig) or any(pub)):
            # all-zero sig+pub = padding lane; the device rejects it too
            # ((0,...) decompresses to a small-order point), skip the
            # expensive scalar math
            continue
        ln = max(0, min(int(lens[i]), msgs.shape[1]))
        out[i] = ed.verify_one_host(sig, bytes(msgs[i, :ln]), pub)
    return out


def host_verify_blob(blob, maxlen: int | None = None):
    """CPU fallback over the packed row-interleaved blob layout
    (row = msg[ml] | sig[64] | pub[32] | len-le32, ed25519.PACKED_EXTRA):
    the same wire format dispatch_blob uploads, verified lane by lane on
    the host.  Verdict[i] matches the device's verify_blob bit for bit."""
    blob = np.asarray(blob, dtype=np.uint8)
    ml = (blob.shape[1] - ed.PACKED_EXTRA) if maxlen is None else int(maxlen)
    lens = np.ascontiguousarray(
        blob[:, ml + 96:ml + 100]).view(np.int32).ravel() & ed.PACKED_LEN_MASK
    return host_verify_arrays(
        blob[:, :ml], np.clip(lens, 0, ml),
        blob[:, ml:ml + 64], blob[:, ml + 64:ml + 96])


def make_example_batch(
    batch: int,
    maxlen: int,
    valid: bool = True,
    seed: int = 1234,
    sign_pool: int | None = None,
):
    """Generate `batch` (msg, sig, pubkey) triples host-side.

    Signing is host python-int math (control plane); distinct keys/messages
    per lane.  With valid=False, a quarter of lanes get corrupted sigs.
    `sign_pool` bounds the number of distinct host signings (each costs a
    python-int scalar mult); lanes beyond it repeat pool entries — device
    verify work is identical either way, so benches use a small pool."""
    rng = np.random.default_rng(seed)
    msgs = np.zeros((batch, maxlen), dtype=np.uint8)
    lens = np.full((batch,), min(64, maxlen), dtype=np.int32)
    sigs = np.zeros((batch, 64), dtype=np.uint8)
    pubs = np.zeros((batch, 32), dtype=np.uint8)

    if sign_pool is not None and sign_pool < 1:
        raise ValueError(f"sign_pool must be >= 1, got {sign_pool}")
    nsign = batch if sign_pool is None else min(batch, sign_pool)
    npool = min(batch, 32, nsign)
    pool = []
    for i in range(npool):
        seed_b = rng.bytes(32)
        pub, a, prefix = ed.keypair_from_seed(seed_b)
        pool.append((seed_b, pub))
    signed = []
    for i in range(nsign):
        seed_b, pub = pool[i % npool]
        m = rng.bytes(int(lens[i]))
        signed.append((m, ed.sign(seed_b, m), pub))
    for i in range(batch):
        m, sig, pub = signed[i % nsign]
        msgs[i, : len(m)] = np.frombuffer(m, dtype=np.uint8)
        lens[i] = len(m)
        sigs[i] = np.frombuffer(sig, dtype=np.uint8)
        pubs[i] = np.frombuffer(pub, dtype=np.uint8)
    if not valid:
        bad = rng.choice(batch, size=max(1, batch // 4), replace=False)
        sigs[bad, 0] ^= 1
    return (
        jnp.asarray(msgs),
        jnp.asarray(lens),
        jnp.asarray(sigs),
        jnp.asarray(pubs),
    )
