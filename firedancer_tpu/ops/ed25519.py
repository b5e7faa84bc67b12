"""Batched ed25519 signature verification on TPU.

The TPU analogue of fd_ed25519_verify / fd_ed25519_verify_batch_single_msg
(reference: src/ballet/ed25519/fd_ed25519_user.c:135-311), with two
deliberate interface upgrades for the batched pipeline:

  * per-item pass/fail BITS instead of the reference's fail-fast batch
    return (the verify tile needs per-txn outcomes; SURVEY.md §7.3)
  * batch width is the array's leading axis (thousands), not MAX=16

Acceptance rules are consensus-identical to the reference (and to Agave's
dalek 2.x + verify_strict usage):

  1. S canonical: 0 <= S < L, else reject          (fd_ed25519_user.c:158-161)
  2. A', R decompress per RFC; non-canonical y accepted
  3. A' or R of small order (<= 8): reject          (fd_ed25519_user.c:200-206)
  4. k = SHA-512(R || A || M) reduced mod L
  5. accept iff [S]B + [k](-A') == R (projective eq, no cofactor mul)
"""

import hashlib
import os
import time
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from . import curve25519 as cv
from . import f25519 as fe
from . import scalar25519 as sc
from . import sha512 as sh
from ..utils import log

L = sc.L
P = fe.P

_PALLAS_BLK = 128  # best-measured block for the signed/T-skip kernel
# (tools/exp_r3_dsm.py: blk=128 beats 256 by ~25% — the smaller live set
# pipelines better through VMEM)


def _pallas_ok(batch: int) -> bool:
    """Use the Pallas kernels when lowering for a TPU and the batch tiles
    evenly.  The CPU (tests, virtual-device meshes) keeps the XLA path:
    Mosaic has no CPU backend and interpret mode is orders slower.  Which
    kernels each compiled shape got is logged once (_log_kernels)."""
    if os.environ.get("FDTPU_NO_PALLAS") or batch % 128:
        return False
    return jax.devices()[0].platform == "tpu"


_KERNELS_LOGGED: set = set()


def _log_kernels(graph: str, batch: int, maxlen: int, curve: str,
                 sha: str) -> None:
    """Log once per traced shape which kernels a verify graph lowers to.
    On a TPU a shape that falls to the XLA path is a warning: it verifies
    correctly but far slower, and nothing else would show it."""
    key = (graph, batch, maxlen, curve, sha)
    if key in _KERNELS_LOGGED:
        return
    _KERNELS_LOGGED.add(key)
    slow = "xla" in (curve, sha) and jax.devices()[0].platform == "tpu"
    (log.warning if slow else log.info)(
        "%s (%d, %d): curve %s, sha512 %s", graph, batch, maxlen, curve,
        sha)


def _decompress_checked(b, use_pallas: bool, blk: int):
    """(ok, point): decompress + small-order rejection on the selected
    backend (shared by the strict and rlc paths)."""
    if use_pallas:
        from . import curve_pallas as cpal

        ok, small, pt = cpal.decompress(b, blk=blk)
        return ok & ~small, pt
    ok, pt = cv.decompress(b)
    return ok & ~cv.is_small_order_affine(pt), pt


def _sha_pallas(batch: int, use_pallas: bool) -> bool:
    """The Pallas SHA-512 kernel needs batch % (8*128) == 0 for its
    sublane packing."""
    return use_pallas and batch % (8 * 128) == 0


def _sha512_k(pre, lens, batch: int, use_pallas: bool):
    """k = SHA-512 digest on the selected backend."""
    if _sha_pallas(batch, use_pallas):
        from . import sha512_pallas as shp

        return shp.sha512(pre, lens)
    return sh.sha512(pre, lens)


def _compressed_r_check(qx, qy, qz, r_bytes, ok_y=None, parsed_r=None):
    """Accept iff Q == the point R's bytes encode, with fd_ed25519's
    R-side semantics, WITHOUT decompressing R (round 4: the R sqrt chain
    was ~27 ms of the 92 ms strict budget at 32k).

    Equivalences to the reference's decompress-then-compare, case by case:
      * non-canonical y (>= p): accepted — comparison is mod p
        (fe.eq canonicalizes), matching frombytes
      * R not on the curve (u/v non-residue): NO curve point has that y,
        and Q is a curve point, so the y compare fails — same reject
      * x = 0 with sign bit set: sgn(0) = 0 != 1 — same reject
      * small-order R: the 8-torsion points have exactly 5 distinct y
        values {0, 1, -1, +-y8}; y membership (mod p) == smallness, since
        y determines x up to sign and both signs stay in the subgroup
      * otherwise: curve points are equal iff same y and same x-parity
        (x != 0 ensured above: x and p-x differ in parity for odd p)
    Verified bit-exact against the real Wycheproof/CCTV/malleability
    corpora (tests/test_ed25519_real_corpora.py).

    The affine conversion uses ONE tree-shaped batch inversion (~3 muls
    per lane + one pow chain amortized over the batch).  When the
    projective y-compare already ran in-kernel (the Pallas tail), pass
    ok_y and qy=None; otherwise qy is compared here.  parsed_r reuses a
    caller's (y_r, sign_r, small) triple instead of re-deriving it
    (ADVICE r4: the Pallas path parsed R twice)."""
    y_r, sign_r, small = (parsed_r if parsed_r is not None
                          else _parse_r_bytes(r_bytes))
    z_ok = ~fe.is_zero(qz)
    one = jnp.zeros_like(qz).at[0].set(1)
    zi = fe.batch_inv(jnp.where(z_ok[None, :], qz, one))
    x_aff = fe.mul(qx, zi)
    if ok_y is None:
        ok_y = fe.eq(fe.mul(qy, zi), y_r)
    return (z_ok & ~small & ok_y & (fe.sgn(x_aff) == sign_r))


def _parse_r_bytes(r_bytes):
    """R's encoded y (canonical limbs), sign bit, and the 8-torsion
    y-membership smallness bit — one canonicalization pass."""
    yc = fe.canonical(fe.from_bytes(r_bytes))   # sign bit masked, mod p
    sign_r = (r_bytes[:, 31] >> 7).astype(jnp.uint32)
    small = jnp.all(yc == 0, axis=0)
    for v in (1, fe.P - 1, cv._ORDER8_Y0 % fe.P, cv._ORDER8_Y1 % fe.P):
        limbs = fe.const(v, yc.ndim)
        small = small | jnp.all(yc == limbs.astype(yc.dtype), axis=0)
    return yc, sign_r, small


def verify_batch(msgs, msg_len, sigs, pubkeys):
    """Verify a batch of detached ed25519 signatures.

    Args:
      msgs:    uint8 (batch, maxlen) — messages, zero-padded
      msg_len: int32 (batch,)        — true message lengths
      sigs:    uint8 (batch, 64)     — R || S
      pubkeys: uint8 (batch, 32)

    Returns: bool (batch,) pass/fail bits.
    """
    r_bytes = sigs[:, :32]
    s_bytes = sigs[:, 32:]
    batch, maxlen = msgs.shape

    use_pallas = _pallas_ok(batch)
    blk = _PALLAS_BLK
    fused = use_pallas and not os.environ.get("FDTPU_NO_FUSED")
    _log_kernels("verify_batch", batch, maxlen,
                 "pallas-fused" if fused else
                 "pallas-split" if use_pallas else "xla",
                 "pallas" if _sha_pallas(batch, use_pallas) else "xla")

    if fused:
        from . import curve_pallas as cpal

        # FUSED tail (round 5): decompress(A) + reduce/recode + dsm +
        # y-compare in ONE kernel — A's planes and the scalar windows
        # never round-trip HBM between stages, one launch instead of
        # three.  ok already folds ok_a/small_a/ok_s/ok_y; the XLA tail
        # adds z!=0, small-order R and the x-parity bit.
        pre = jnp.concatenate([r_bytes, pubkeys, msgs], axis=1)
        k_digest = _sha512_k(
            pre, msg_len.astype(jnp.int32) + 64, batch, use_pallas)
        parsed_r = _parse_r_bytes(r_bytes)
        ok_k, qx, qz = cpal.verify_tail_fused(
            pubkeys, s_bytes, k_digest, parsed_r[0], blk=blk)
        return _compressed_r_check(qx, None, qz, r_bytes, ok_y=ok_k,
                                   parsed_r=parsed_r)

    ok_a, a_pt = _decompress_checked(pubkeys, use_pallas, blk)

    # k = SHA-512(R || A || M) mod L
    pre = jnp.concatenate([r_bytes, pubkeys, msgs], axis=1)
    k_digest = _sha512_k(
        pre, msg_len.astype(jnp.int32) + 64, batch, use_pallas)

    if use_pallas:
        from . import curve_pallas as cpal

        # split-kernel path (FDTPU_NO_FUSED: the round-4 layout, kept for
        # A/B measurement): one VMEM-resident pass does S-canonicity +
        # digest mod L + signed window recode for both scalars
        ok_s, wins = cpal.reduce_recode(s_bytes, k_digest, blk=blk)
        parsed_r = _parse_r_bytes(r_bytes)
        ok_y, qx, qz = cpal.dsm_tail_q(wins, a_pt, parsed_r[0], blk=blk)
        ok_eq = _compressed_r_check(qx, None, qz, r_bytes, ok_y=ok_y,
                                    parsed_r=parsed_r)
    else:
        ok_s = sc.is_canonical(s_bytes)
        k_limbs = sc.reduce_512(k_digest)
        s_windows = cv.scalar_windows(s_bytes)
        k_windows = sc.limbs_to_windows(k_limbs)
        q = cv.double_scalar_mul_base(s_windows, k_windows, cv.neg(a_pt))
        ok_eq = _compressed_r_check(q.X, q.Y, q.Z, r_bytes)

    return ok_s & ok_a & ok_eq


def verify_batch_rlc(msgs, msg_len, sigs, pubkeys, z_bytes, m: int = 8):
    """Random-linear-combination batch verification (one bit for the whole
    batch) — the high-throughput path.

    Checks  [Σ z_i s_i]B == Σ [z_i]R_i + Σ [z_i k_i]A_i  with host-supplied
    random 128-bit z_i, via one lane-parallel MSM (cv.msm).  If every
    per-sig equation holds the combined one does; a forged sig survives only
    if the z draw lands in a ~2^-125 bad set (the standard batch-verify
    soundness argument, as in ed25519-dalek's verify_batch).

    Consensus semantics: the check is COFACTORLESS, exactly like the per-sig
    path (no [8] multiply), so a batch containing only honestly-valid sigs
    passes; any batch this rejects must be re-checked per-sig to get exact
    consensus-identical bits (SigVerifier does that fallback).  A True from
    here implies every sig passes fd_ed25519_verify semantics (w.h.p.).

    Args are as verify_batch plus z_bytes: uint8 (batch, 16) — fresh
    unpredictable randomness per call (host CSPRNG).

    Returns (all_ok: bool scalar, prechecks: bool (batch,)).
    """
    r_bytes = sigs[:, :32]
    s_bytes = sigs[:, 32:]
    batch = msgs.shape[0]

    use_pallas = _pallas_ok(batch) and batch % (m * 128) == 0
    blk = _PALLAS_BLK
    _log_kernels("verify_batch_rlc", batch, msgs.shape[1],
                 "pallas" if use_pallas else "xla",
                 "pallas" if _sha_pallas(batch, use_pallas) else "xla")
    ok_a, a_pt = _decompress_checked(pubkeys, use_pallas, blk)
    ok_r, r_pt = _decompress_checked(r_bytes, use_pallas, blk)

    # k_i = SHA-512(R||A||M) mod L;  w_i = z_i * k_i;  c = Σ z_i * s_i
    pre_img = jnp.concatenate([r_bytes, pubkeys, msgs], axis=1)
    digest = _sha512_k(pre_img, msg_len.astype(jnp.int32) + 64, batch,
                       use_pallas)

    # scalar chain stays XLA on BOTH backends: the Pallas transcription
    # (cpal.rlc_recode) measured SLOWER at 32k (106 vs 60 ms) — its
    # per-(1,blk)-row list ops waste 7/8 of each VPU tile, while XLA
    # vectorizes the same chain across the full batch (r4 finding,
    # docs/perf_ceiling.md)
    ok_s = sc.is_canonical(s_bytes)
    k_limbs = sc.reduce_512(digest)
    z_limbs = sc.bytes_to_limbs(z_bytes, 11)          # 128-bit -> 11 limbs
    s_limbs = sc.bytes_to_limbs(s_bytes, 22)
    w_limbs = sc.mul_mod_l(k_limbs, z_limbs)           # (22, batch)
    c_limbs = sc.sum_mod_l(sc.mul_mod_l(s_limbs, z_limbs), axis=0)
    w_windows = sc.limbs_to_windows(w_limbs)           # (64, batch)
    z_windows = sc.limbs_to_windows(
        jnp.concatenate([z_limbs, jnp.zeros_like(z_limbs[:11])],
                        axis=0))[:32]
    if use_pallas:
        from . import curve_pallas as cpal

        # round-6 select-redesign lever (signed digits + packed 16-bit
        # limb planes); default stays legacy pending the on-chip A/B
        # verdict (docs/perf_ceiling.md round 6, tools/exp_r6_rlc_select)
        sel = os.environ.get("FDTPU_RLC_SELECT", "legacy")
        acc_a = cpal.msm(w_windows, cv.neg(a_pt), m=m, nwin=64, select=sel)
        acc_r = cpal.msm(z_windows, cv.neg(r_pt), m=m, nwin=32, select=sel)
    else:
        acc_a = cv.msm(w_windows, cv.neg(a_pt), m=m, nwin=64)
        acc_r = cv.msm(z_windows, cv.neg(r_pt), m=m, nwin=32)

    pre = ok_s & ok_a & ok_r
    # Q = [c]B - Σ[w_i]A_i - Σ[z_i]R_i ; all sigs valid => Q == identity
    base = cv.scalar_mul_base(sc.limbs_to_windows(c_limbs)[:, None])
    q = cv.add(cv.add(acc_a, acc_r),
               cv.Point(*(t[:, 0] for t in base)))
    is_id = fe.is_zero(q.X) & fe.eq(q.Y, q.Z)
    return jnp.all(pre) & is_id, pre


def _halve_scalar_host(k: int) -> tuple[int, int]:
    """Antipa-style rational decomposition of a mod-L scalar (host
    python-int half-gcd): returns (u, v) with  u == k*v (mod L),
    0 <= u < 2^127, 0 < |v| <= ~2^126.  The extended Euclidean chain on
    (L, k) stopped at the first remainder below sqrt(L); the invariant
    r_i == k*t_i (mod L) holds at every step."""
    r0, r1 = sc.L, k % sc.L
    t0, t1 = 0, 1
    while r1 >= (1 << 127):
        q = r0 // r1
        r0, r1 = r1, r0 - q * r1
        t0, t1 = t1, t0 - q * t1
    return r1, t1


def _divstep_halve_host(k: int) -> tuple[int, int]:
    """Host transcription of sc.halve_scalar: the SAME (u, v) pair the
    device divstep emits, step for step (tests/test_scalar_divstep.py
    pins the equivalence).  The euclid pair from _halve_scalar_host is
    equally valid for honest signatures, but antipa acceptance of a
    torsion-defective forgery depends on v's 2-adic valuation — so the
    degraded-mode CPU fallback must reproduce THIS pair, not euclid's,
    to stay bit-identical to the active device graph."""
    n1 = sc.DIVSTEP_ITERS
    f, g = sc.L, (pow(2, n1, sc.L) * (k % sc.L)) % sc.L
    bf, bg, delta = 0, 1, 1
    for _ in range(n1):
        if delta > 0 and g & 1:
            delta, f, g, bf, bg = 1 - delta, g, (g - f) >> 1, 2 * bg, bg - bf
        else:
            b = g & 1
            delta, f, g, bf, bg = (1 + delta, f, (g + b * f) >> 1,
                                   2 * bf, bg + b * bf)

    def nrm(a, b):
        return max(abs(a), abs(b))

    F, G = (f, bf), (g, bg)
    for _ in range(sc.LAGRANGE_ITERS):
        if nrm(*F) < nrm(*G):
            F, G = G, F
        t = min(max(0, nrm(*F).bit_length() - nrm(*G).bit_length()), 31)
        sG = (G[0] << t, G[1] << t)
        Pc = (F[0] - sG[0], F[1] - sG[1])
        Mc = (F[0] + sG[0], F[1] + sG[1])
        C = Pc if nrm(*Pc) <= nrm(*Mc) else Mc
        if nrm(*C) < nrm(*F):
            F = C
    u, v = F if nrm(*F) <= nrm(*G) else G
    if u < 0:
        u, v = -u, -v
    return u, v


def _int_windows(vals, nwin: int) -> np.ndarray:
    """Python ints -> uint32 (nwin, batch) 4-bit windows, low first."""
    out = np.zeros((nwin, len(vals)), np.uint32)
    for b, v in enumerate(vals):
        for i in range(nwin):
            out[i, b] = (v >> (4 * i)) & 0xF
    return out


def verify_batch_antipa(msgs, msg_len, sigs, pubkeys):
    """Strict per-sig verify via Antipa halved scalars, fully device
    resident (round 9; flag-selectable via [verify] mode = antipa).

    k = H(R,A,M) mod L is decomposed ON DEVICE as k == u/v (mod L) with
    u, |v| < 2^128 by sc.halve_scalar (a fixed 250-iteration
    Bernstein-Yang divstep plus a 24-round branchless binary-Lagrange
    polish — no host round-trip, zero per-signature host work).  The
    check  [S]B - [k]A - R == 0  times v becomes
    [vS mod L]B + [u](-A) + [|v|](R~) == identity   (R~ = -R if v > 0
    else R) — the variable chain runs 32 windows (128 doubles) instead
    of 64 (256), at the cost of decompressing R (eliminated in round 4
    for the strict path) and a second var table.

    Semantics vs verify_batch: multiplying the equation by v is
    TORSION-LAX — a forged sig whose defect is an 8-torsion point of
    order dividing v passes here but fails strict (cofactorless
    semantics are already lax there, but the bits are not guaranteed
    identical on adversarial torsion cases; the enumerated cases live
    in tests/test_ed25519_antipa.py).  Honest-signature and
    corrupted-signature bits match verify_batch."""
    r_bytes = sigs[:, :32]
    s_bytes = sigs[:, 32:]
    batch = int(msgs.shape[0])
    _log_kernels("verify_batch_antipa", batch, msgs.shape[1], "xla", "xla")

    ok_a, a_pt = cv.decompress(pubkeys)
    ok_a = ok_a & ~cv.is_small_order_affine(a_pt)
    ok_r, r_pt = cv.decompress(r_bytes)          # the Antipa payback cost
    _, _, small_r = _parse_r_bytes(r_bytes)
    ok_s = sc.is_canonical(s_bytes)

    pre = jnp.concatenate([r_bytes, pubkeys, msgs], axis=1)
    k_limbs = sc.reduce_512(
        _sha512_k(pre, msg_len.astype(jnp.int32) + 64, batch, False))

    # in-kernel halving: u == v*k (mod L), u and |v| inside 32 windows
    u_limbs, av_limbs, v_pos = sc.halve_scalar(k_limbs)
    s_limbs = sc.bytes_to_limbs(s_bytes, 22)
    c_limbs = sc.mul_mod_l(s_limbs, av_limbs)    # |v|*S mod L
    c_limbs = jnp.where(v_pos[None, :], c_limbs, sc.neg_mod_l(c_limbs))
    u_wins = sc.limbs_to_windows(u_limbs)[:32]
    av_wins = sc.limbs_to_windows(av_limbs)[:32]
    c_wins = sc.limbs_to_windows(c_limbs)

    r_neg = cv.neg(r_pt)
    r_eff = cv.Point(*(jnp.where(v_pos[None, :], n, p)
                       for n, p in zip(r_neg, r_pt)))
    chain = cv.double_scalar_mul_halved(
        u_wins, av_wins, cv.neg(a_pt), r_eff, nwin=32)
    base = cv.scalar_mul_base(c_wins)
    q = cv.add(chain, base)
    return ok_s & ok_a & ok_r & ~small_r & cv.is_identity(q)


# Packed-blob row layout — THE single definition (the native parser's
# fd_txn_parse_batch_packed, the pipeline's packed buckets, SigVerifier's
# packed dispatch and the AOT store all build against this):
# one uint8 row per lane = msgs[0:ml] | sig 64 | pubkey 32 | msg_len
# le-int32 4, row width ml + PACKED_EXTRA.
PACKED_EXTRA = 100


# The high 16 bits of the len word mark the row's signature index and its
# transaction's signature count (tango/ring.py PACKED_LEN_MASK): a length
# is the word's low 16 bits.
PACKED_LEN_MASK = 0xFFFF


def _unpack_blob(blob, maxlen: int, ml: int | None):
    """(msgs, lens, sigs, pubs) of a packed blob; messages re-pad to maxlen
    on device when the packed width ml is narrower."""
    ml = maxlen if ml is None else ml
    b = blob.shape[0]
    m = blob[:, :ml]
    if ml < maxlen:
        m = jnp.pad(m, ((0, 0), (0, maxlen - ml)))
    s = blob[:, ml:ml + 64]
    p = blob[:, ml + 64:ml + 96]
    ln = jax.lax.bitcast_convert_type(
        blob[:, ml + 96:ml + 100], jnp.int32).reshape(b) & PACKED_LEN_MASK
    return m, ln, s, p


def verify_blob(blob, maxlen: int, ml: int | None = None):
    """verify_batch over a packed row-interleaved blob (ml = packed
    message width)."""
    return verify_batch(*_unpack_blob(blob, maxlen, ml))


def verify_blob_antipa(blob, maxlen: int, ml: int | None = None):
    """verify_batch_antipa over the same packed row layout as
    verify_blob — the antipa-mode packed dispatch / AOT graph."""
    return verify_batch_antipa(*_unpack_blob(blob, maxlen, ml))


def verify_batch_single_msg(msg, sigs, pubkeys):
    """All signatures over one shared message (the reference's batch shape,
    fd_ed25519_user.c:231: a Solana txn's sigs all cover the same payload)."""
    batch = sigs.shape[0]
    msgs = jnp.broadcast_to(msg[None, :], (batch, msg.shape[0]))
    lens = jnp.full((batch,), msg.shape[0], dtype=jnp.int32)
    return verify_batch(msgs, lens, sigs, pubkeys)


_VERIFY_ONE = None
_VERIFY_ONE_MAXLEN = 1280  # covers every signed control-plane payload:
                           # crds values (41 + body <= 1232), repair
                           # requests (49), vote txn messages (<= 1232)


def verify_one(sig: bytes, msg: bytes, pub: bytes) -> bool:
    """Single-item verify for control-plane protocols (gossip crds values,
    repair requests, precompile instructions): one shared jitted
    (1, 1280) verifier compiled lazily per process (the persistent xla
    cache makes later processes instant)."""
    global _VERIFY_ONE
    if len(msg) > _VERIFY_ONE_MAXLEN or len(sig) != 64 or len(pub) != 32:
        return False
    first_call = _VERIFY_ONE is None
    if first_call:
        from ..utils import xla_cache
        xla_cache.enable()
        _VERIFY_ONE = jax.jit(verify_batch)
        t0 = time.perf_counter_ns()
    out = _VERIFY_ONE(
        jnp.asarray(np.frombuffer(
            msg.ljust(_VERIFY_ONE_MAXLEN, b"\0"), np.uint8)[None, :]),
        jnp.asarray(np.array([len(msg)], dtype=np.int32)),
        jnp.asarray(np.frombuffer(sig, np.uint8)[None, :]),
        jnp.asarray(np.frombuffer(pub, np.uint8)[None, :]))
    res = bool(np.asarray(out)[0])
    if first_call:
        # the first dispatch pays the jit trace+compile (or xla-cache
        # load); surface it in the shared compile-event registry
        from ..disco import trace as _trace
        _trace.record_compile(("verify_one", 1, _VERIFY_ONE_MAXLEN),
                              time.perf_counter_ns() - t0)
    return res


# ------------------------------------------------------------------ host side
# Key generation and signing are control-plane operations (the validator signs
# through the isolated sign tile, one item at a time — ref src/disco/keyguard);
# python-int host code is the right tool, device batching buys nothing.


def keypair_from_seed(seed: bytes):
    """seed (32B) -> (public_key bytes, secret scalar int, prefix bytes).
    (ref fd_ed25519_public_from_private)"""
    assert len(seed) == 32
    h = hashlib.sha512(seed).digest()
    a = int.from_bytes(h[:32], "little")
    a &= (1 << 254) - 8
    a |= 1 << 254
    pub = _scalar_mul_base_host(a)
    return _compress_host(pub), a, h[32:]


def sign(seed: bytes, msg: bytes) -> bytes:
    """Single-item host signer (ref fd_ed25519_sign)."""
    pub, a, prefix = keypair_from_seed(seed)
    r = int.from_bytes(hashlib.sha512(prefix + msg).digest(), "little") % L
    R = _compress_host(_scalar_mul_base_host(r))
    k = int.from_bytes(hashlib.sha512(R + pub + msg).digest(), "little") % L
    s = (r + k * a) % L
    return R + s.to_bytes(32, "little")


def _decompress_host(b: bytes):
    """Host point decompress; returns extended coords or None (ref
    fd_ed25519_point_frombytes semantics: non-canonical y accepted)."""
    enc = int.from_bytes(b, "little")
    sign = enc >> 255
    y = enc & ((1 << 255) - 1)
    y %= P
    u = (y * y - 1) % P
    v = (cv.D * y * y + 1) % P
    x = u * pow(v, 3, P) * pow(u * pow(v, 7, P), (P - 5) // 8, P)
    # candidate root of x^2 = u/v; fix up by sqrt(-1) if needed
    if (v * x * x - u) % P != 0:
        x = x * pow(2, (P - 1) // 4, P) % P
        if (v * x * x - u) % P != 0:
            return None
    x %= P
    if x == 0 and sign:
        return None
    if x & 1 != sign:
        x = P - x
    return (x, y, 1, x * y % P)


def _is_small_order_host(p) -> bool:
    q = p
    for _ in range(3):
        q = _pt_add_host(q, q)  # [8]P
    X, Y, Z, _ = q
    return X % P == 0  # identity or the order-2 point


def verify_one_host(sig: bytes, msg: bytes, pub: bytes) -> bool:
    """Single-item host verify (python ints) for control-plane checks where
    spinning up the jitted verifier isn't worth it (x509 self-signatures,
    TLS CertificateVerify).  Same acceptance rules — and same (sig, msg,
    pub) argument order — as verify_one."""
    if len(sig) != 64 or len(pub) != 32:
        return False
    s = int.from_bytes(sig[32:], "little")
    if s >= L:
        return False
    a = _decompress_host(pub)
    r = _decompress_host(sig[:32])
    if a is None or r is None:
        return False
    if _is_small_order_host(a) or _is_small_order_host(r):
        return False
    k = int.from_bytes(hashlib.sha512(sig[:32] + pub + msg).digest(), "little") % L
    neg_a = (P - a[0], a[1], a[2], P - a[3])
    q = _pt_add_host(_scalar_mul_base_host(s), _scalar_mul_host(k, neg_a))
    # q == r in projective coords (r has Z=1)
    Xq, Yq, Zq, _ = q
    Xr, Yr, _, _ = r
    return (Xq - Xr * Zq) % P == 0 and (Yq - Yr * Zq) % P == 0


def verify_one_host_antipa(sig: bytes, msg: bytes, pub: bytes) -> bool:
    """Host twin of the verify_batch_antipa device graph, bit for bit:
    same prechecks as verify_one_host, then the halved equation
    [vS mod L]B + [u](-A) + [|v|](R~) == identity with (u, v) from the
    divstep host model — including its torsion laxity.  This is the
    degraded-mode fallback for antipa-mode verifiers (GuardedVerifier's
    contract is fidelity to the ACTIVE device graph, not to strict)."""
    if len(sig) != 64 or len(pub) != 32:
        return False
    s = int.from_bytes(sig[32:], "little")
    if s >= L:
        return False
    a = _decompress_host(pub)
    r = _decompress_host(sig[:32])
    if a is None or r is None:
        return False
    if _is_small_order_host(a) or _is_small_order_host(r):
        return False
    k = int.from_bytes(hashlib.sha512(sig[:32] + pub + msg).digest(), "little") % L
    u, v = _divstep_halve_host(k)
    c = (v * s) % L
    neg_a = (P - a[0], a[1], a[2], P - a[3])
    r_eff = r if v < 0 else (P - r[0], r[1], r[2], P - r[3])
    q = _pt_add_host(
        _scalar_mul_base_host(c),
        _pt_add_host(_scalar_mul_host(u, neg_a),
                     _scalar_mul_host(abs(v), r_eff)))
    X, Y, Z, _ = q
    return X % P == 0 and (Y - Z) % P == 0


def _scalar_mul_host(s: int, p):
    q = (0, 1, 1, 0)
    while s > 0:
        if s & 1:
            q = _pt_add_host(q, p)
        p = _pt_add_host(p, p)
        s >>= 1
    return q


def _pt_add_host(p, q):
    X1, Y1, Z1, T1 = p
    X2, Y2, Z2, T2 = q
    A = (Y1 - X1) * (Y2 - X2) % P
    Bv = (Y1 + X1) * (Y2 + X2) % P
    Cc = 2 * T1 * T2 * cv.D % P
    Dd = 2 * Z1 * Z2 % P
    E, F, G, H = (Bv - A) % P, (Dd - Cc) % P, (Dd + Cc) % P, (Bv + A) % P
    return (E * F % P, G * H % P, F * G % P, E * H % P)


def _scalar_mul_base_host(s: int):
    q = (0, 1, 1, 0)
    p = (cv.BASE_X, cv.BASE_Y, 1, cv.BASE_X * cv.BASE_Y % P)
    while s > 0:
        if s & 1:
            q = _pt_add_host(q, p)
        p = _pt_add_host(p, p)
        s >>= 1
    return q


def _compress_host(p) -> bytes:
    X, Y, Z, _ = p
    zi = pow(Z, P - 2, P)
    x, y = X * zi % P, Y * zi % P
    return (y | ((x & 1) << 255)).to_bytes(32, "little")
