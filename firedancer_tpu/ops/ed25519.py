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
import time

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
# (blk=128 beats 256 by ~25% on v5e — the smaller live set pipelines
# better through VMEM; docs/perf_ceiling.md)


def _pallas_ok(batch: int) -> bool:
    """Use the Pallas kernels when lowering for a TPU and the batch tiles
    evenly.  The CPU (tests, virtual-device meshes) keeps the XLA path:
    Mosaic has no CPU backend and interpret mode is orders slower.  Which
    kernels each compiled shape got is logged once (_log_kernels)."""
    if batch % 128:
        return False
    return jax.devices()[0].platform == "tpu"


_KERNELS_LOGGED: set = set()


def _log_kernels(batch: int, maxlen: int, curve: str, sha: str) -> None:
    """Log once per traced shape which kernels the verify graph lowers to.
    On a TPU a shape that falls to the XLA path is a warning: it verifies
    correctly but far slower, and nothing else would show it."""
    key = (batch, maxlen, curve, sha)
    if key in _KERNELS_LOGGED:
        return
    _KERNELS_LOGGED.add(key)
    slow = "xla" in (curve, sha) and jax.devices()[0].platform == "tpu"
    (log.warning if slow else log.info)(
        "verify_batch (%d, %d): curve %s, sha512 %s", batch, maxlen, curve,
        sha)


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
    _log_kernels(batch, maxlen, "pallas-fused" if use_pallas else "xla",
                 "pallas" if _sha_pallas(batch, use_pallas) else "xla")

    # k = SHA-512(R || A || M) mod L
    pre = jnp.concatenate([r_bytes, pubkeys, msgs], axis=1)
    k_digest = _sha512_k(
        pre, msg_len.astype(jnp.int32) + 64, batch, use_pallas)

    if use_pallas:
        from . import curve_pallas as cpal

        # fused tail: decompress(A) + reduce/recode + dsm + y-compare in
        # ONE kernel (A's planes and the scalar windows never round-trip
        # HBM between stages).  ok already folds ok_a/small_a/ok_s/ok_y;
        # the XLA tail adds z!=0, small-order R and the x-parity bit.
        parsed_r = _parse_r_bytes(r_bytes)
        ok_k, qx, qz = cpal.verify_tail_fused(
            pubkeys, s_bytes, k_digest, parsed_r[0], blk=_PALLAS_BLK)
        return _compressed_r_check(qx, None, qz, r_bytes, ok_y=ok_k,
                                   parsed_r=parsed_r)

    ok_a, a_pt = cv.decompress(pubkeys)
    ok_a = ok_a & ~cv.is_small_order_affine(a_pt)
    ok_s = sc.is_canonical(s_bytes)
    k_windows = sc.limbs_to_windows(sc.reduce_512(k_digest))
    q = cv.double_scalar_mul_base(cv.scalar_windows(s_bytes), k_windows,
                                  cv.neg(a_pt))
    return ok_s & ok_a & _compressed_r_check(q.X, q.Y, q.Z, r_bytes)


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
