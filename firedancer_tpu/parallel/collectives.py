"""Multi-chip collectives for the verify pipeline (ref: SURVEY.md §5
"distributed communication backend" — the reference's cross-host story is
the Solana protocol itself; ours adds the ICI tier the reference never
had: XLA collectives over a chip mesh).

Two collective patterns:

  * ring_point_fold — an all-reduce whose element is a curve POINT and
    whose op is group addition: partials rotate around the ICI ring via
    ppermute while every chip accumulates, n-1 steps (the ring-collective
    shape ring-attention uses, applied to EC aggregation).
  * shard_rlc_verify — the v5e-8 "data-parallel MSM" (BASELINE.json
    config #5): each chip runs the random-linear-combination batch-verify
    MSM over its shard of signatures; per-chip partial points ring-fold to
    the total, the scalar combination psums (limb-wise, then one mod-L
    reduce), and every chip checks the single group equation.

Both run on any jax mesh — the 8-virtual-CPU-device test mesh compiles
the identical SPMD program a v5e-8 slice executes over ICI.
"""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from firedancer_tpu.ops import curve25519 as cv
from firedancer_tpu.ops import ed25519 as ed
from firedancer_tpu.ops import f25519 as fe
from firedancer_tpu.ops import scalar25519 as sc
from firedancer_tpu.ops import sha512 as sh


def _ring_fold_local(p: cv.Point, axis: str, n: int) -> cv.Point:
    """All-reduce point addition inside shard_map: rotate a carry copy of
    the original partial around the ring, adding at each stop.  n is the
    static axis size (jax < 0.6 has no lax.axis_size; the mesh knows)."""
    perm = [(i, (i + 1) % n) for i in range(n)]

    def body(_, state):
        acc, carry = state
        carry = cv.Point(*(jax.lax.ppermute(t, axis, perm) for t in carry))
        return (cv.add(acc, carry), carry)

    acc, _ = jax.lax.fori_loop(0, n - 1, body, (p, p))
    return acc


def ring_point_fold(mesh: Mesh, axis: str = "dp"):
    """Jitted fn: (22,)-limbed per-device Points (stacked on a leading
    device axis, n × (22,)) -> the group sum, replicated to every device."""

    def local(X, Y, Z, T):
        p = cv.Point(X[0], Y[0], Z[0], T[0])  # this device's partial
        s = _ring_fold_local(p, axis, mesh.shape[axis])
        return tuple(t[None] for t in s)

    shard = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(axis), P(axis), P(axis), P(axis)),
        out_specs=(P(axis), P(axis), P(axis), P(axis)),
    )
    return jax.jit(shard)


def shard_rlc_verify(mesh: Mesh, m: int = 2, axis: str = "dp"):
    """Multi-chip RLC batch verification (data-parallel MSM).

    Returns fn(msgs, msg_len, sigs, pubkeys, z_bytes) -> (all_ok scalar,
    prechecks (batch,)): True iff EVERY signature in the global batch
    passes (w.h.p. over the host-supplied 128-bit z randomness).  The
    check is Σ_i z_i s_i · B  ==  Σ_i [z_i]R_i + Σ_i [z_i k_i]A_i with
    both sides assembled across the mesh: chips compute shard-local MSM
    partials, the points ring-fold over ICI, the scalar c psums limb-wise
    (8 devices × 12-bit limbs stays far inside int32), and each chip
    evaluates the final equation on the replicated totals."""

    def local(msgs, msg_len, sigs, pubkeys, z_bytes):
        r_bytes = sigs[:, :32]
        s_bytes = sigs[:, 32:]
        ok_s = sc.is_canonical(s_bytes)
        ok_a, a_pt = cv.decompress(pubkeys)
        ok_r, r_pt = cv.decompress(r_bytes)
        ok_a &= ~cv.is_small_order_affine(a_pt)
        ok_r &= ~cv.is_small_order_affine(r_pt)
        pre = ok_s & ok_a & ok_r

        pre_img = jnp.concatenate([r_bytes, pubkeys, msgs], axis=1)
        k_limbs = sc.reduce_512(
            sh.sha512(pre_img, msg_len.astype(jnp.int32) + 64))
        z_limbs = sc.bytes_to_limbs(z_bytes, 11)
        s_limbs = sc.bytes_to_limbs(s_bytes, 22)
        w_limbs = sc.mul_mod_l(k_limbs, z_limbs)
        c_local = sc.sum_mod_l(sc.mul_mod_l(s_limbs, z_limbs), axis=0)

        w_windows = sc.limbs_to_windows(w_limbs)
        z_windows = sc.limbs_to_windows(
            jnp.concatenate([z_limbs, jnp.zeros_like(z_limbs[:11])], axis=0))

        # shard-local MSM partials: Q_local = -Σ[w]A - Σ[z]R
        acc_a = cv.msm(w_windows, cv.neg(a_pt), m=m, nwin=64)
        acc_r = cv.msm(z_windows[:32], cv.neg(r_pt), m=m, nwin=32)
        q_local = cv.add(acc_a, acc_r)

        # fold partial points around the ICI ring
        q = _ring_fold_local(q_local, axis, mesh.shape[axis])

        # c = Σ c_local mod L: limb-wise psum then one canonical reduce
        c_sum = jax.lax.psum(c_local, axis)
        pad = jnp.zeros((2, *c_sum.shape[1:]), dtype=c_sum.dtype)
        c = sc._cond_sub_l(jnp.concatenate([c_sum, pad], axis=0), times=8)

        base = cv.scalar_mul_base(sc.limbs_to_windows(c)[:, None])
        q = cv.add(q, cv.Point(*(t[:, 0] for t in base)))
        is_id = fe.is_zero(q.X) & fe.eq(q.Y, q.Z)
        all_pre = jax.lax.psum(
            jnp.sum((~pre).astype(jnp.uint32)), axis) == 0
        # the verdict is value-replicated (every chip folded the same
        # totals) but rides ppermute, which shard_map cannot statically
        # prove replicated — emit one copy per device instead
        return (all_pre & is_id)[None], pre

    shard = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(axis, None), P(axis), P(axis, None), P(axis, None),
                  P(axis, None)),
        out_specs=(P(axis), P(axis)),
    )

    fn = jax.jit(shard)
    n = mesh.shape[axis]

    def run(*args):
        batch = args[2].shape[0]
        # serving-path guard (SigVerifier routes rlc mode through here
        # when its mesh is active): a clean error beats shard_map's
        # shape-mismatch traceback, and the per-shard MSM needs its
        # local lanes divisible by the combination width m
        if batch % n or (batch // n) % m:
            raise ValueError(
                f"rlc batch {batch} must split {n} ways into "
                f"m={m}-divisible shards")
        per_dev, pre = fn(*args)
        return per_dev.all(), pre

    return run
