"""Pallas TPU kernel for the ed25519 verify hot loop.

Why this exists: the XLA-compiled double-scalar-multiply is bounded by
HBM round-trips between fusion islands — measured 21.7 ns/double/lane vs
1-5 ns for the same arithmetic inside one Pallas kernel whose limb planes
stay resident in VMEM (tools/exp_pallas_dbl.py, v5e).

Two design points differ from the XLA path (ops/f25519.py, curve25519.py):

1. **Shared-chain (Shamir/Straus) double-scalar-mul** instead of
   var-half + fixed-base comb: 64 windows of (4 doubles + two table
   adds).  The comb exists to avoid doublings for the base half, but in
   a shared chain the base half rides the variable half's doublings for
   free — and (decisively, for Mosaic) the only static table it needs is
   [0..15]B, expressible as scalar-literal vector constants.  Mosaic
   rejects captured array constants and cannot relayout dynamic
   window-indexed slices of a table input into limb-plane form, so the
   comb's 64 distinct window tables are unlowerable.

2. **Sublane-packed field geometry.** The XLA path's per-column
   convolution builds (1, batch) rows; on Mosaic every such row pads to
   a full (8, 128) tile — 8x the VMEM and 8x the ALU waste, which blew
   the 16 MB scoped-VMEM budget and spilled (measured 30 K/s).  Here a
   field element is (22, blk) with limbs on SUBLANES, and the 22x22
   limb convolution is 22 shifted whole-array multiply-accumulates into
   a (44, blk) column space: every op is a dense multi-tile vector op.
   Radix/magnitude discipline is identical to f25519.py (12-bit limbs,
   lazy adds < 8212, u32-exact 44-column accumulation < 2^32); the
   reduction is _reduce_wide/weak_reduce transcribed to this geometry.

Reference semantic contract: fd_ed25519_double_scalar_mul_base
(src/ballet/ed25519/fd_curve25519.c:123-160).

Grid is over the batch; each block owns `blk` lanes end-to-end, so the
only HBM traffic is the kernel's inputs/outputs.
"""

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import curve25519 as cv
from . import f25519 as fe

NWIN = 64
NL = fe.NLIMB          # 22
MASK = fe.MASK
B12 = fe.B             # 12 bits/limb
F264 = fe.FOLD264


def vma_of(*arrays) -> frozenset:
    """Mesh axes a kernel's inputs vary over (empty outside shard_map).
    Every pallas_call out_shape carries it: under jax.shard_map, whose
    check_vma is on by default, an out_shape without a vma is refused."""
    return frozenset().union(*(jax.typeof(a).vma for a in arrays))


def _constw(v: int):
    """Kernel-safe (22, 1) field constant (scalar literals; see
    fe._limb_const)."""
    return fe._limb_const(fe._to_limbs_py(v % fe.P), 2)


# ------------------------------------------------- field ops, (22, blk) geom


def _wr(x, passes=2):
    """weak_reduce on (22, blk): parallel shifted-carry passes + >=2^255
    fold.  Same magnitude contract as fe.weak_reduce."""
    for _ in range(passes):
        lo = x & MASK
        hi = x >> B12
        x = jnp.concatenate(
            [lo[:1] + hi[NL - 1 :] * F264, lo[1:] + hi[: NL - 1]], axis=0)
    t = x[NL - 1 :] >> 3
    x0 = x[:1] + t * 19
    c0 = x0 >> B12
    return jnp.concatenate(
        [x0 & MASK, x[1:2] + c0, x[2 : NL - 1], x[NL - 1 :] & 7], axis=0)


def _reduce44(c):
    """(44, blk) column accumulator -> NORMAL (22, blk).

    Two in-space carry passes bring every column <= ~4184, then the
    2^264 fold is DECOMPOSED: e_i = c_hi_i * 19 (<= 79496) splits into
    its 2^9-shifted limb contributions lo_i = (e_i << 9) & MASK (limb i)
    and hi_i = e_i >> 3 (limb i+1); the >=2^255 fold runs on the top
    limb first and ONE parallel carry pass finishes.  Bounds: r_i <=
    4184 + 4095 + 9937 = 18216; after top-fold limb0 <= 61479; the final
    pass leaves every limb <= ~4110 (NORMAL).  This replaces the 3-pass
    weak_reduce tail (the naive fold's limb-21-carry-times-9728 blowup
    is what forced 3 passes); measured as part of the round-3 lever set
    (tools/exp_r3_dsm.py)."""
    for _ in range(2):
        lo = c & MASK
        hi = c >> B12
        c = jnp.concatenate([lo[:1], lo[1:] + hi[:-1]], axis=0)
    d, ch = c[:NL], c[NL:]
    e = ch * 19                                     # <= 79496 (17 bits)
    lo = (e << 9) & MASK                            # contribution to limb i
    hi = e >> 3                                     # to limb i+1
    # c[43] is structurally zero so hi[21] (-> limb 22) carries nothing
    r = d + lo + jnp.concatenate([jnp.zeros_like(hi[:1]), hi[:-1]], axis=0)
    t = r[NL - 1 :] >> 3
    r = jnp.concatenate([r[:1] + t * 19, r[1 : NL - 1], r[NL - 1 :] & 7],
                        axis=0)
    lo = r & MASK
    hi = r >> B12
    return jnp.concatenate(
        [lo[:1] + hi[NL - 1 :] * F264, lo[1:] + hi[: NL - 1]], axis=0)


def _cat(parts):
    parts = [p for p in parts if p.shape[0]]
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=0)


def _mulw(a, b):
    """Field mul: 22 shifted whole-array MACs accumulated into TWO
    (22, blk) planes (columns 0..21 / 22..43) — each MAC row lands as two
    22-row adds instead of one concat-to-44-row add, the shape Mosaic
    schedules best of the measured ladder variants (tools/exp_r3_dsm.py).

    Exactness: inputs LAZY (limbs <= 8212 after one unreduced add), each
    product <= 8212^2 = 6.75e7, 22 accumulated terms <= 1.49e9 < 2^32."""
    z = jnp.zeros_like(a)
    acc_lo = jnp.zeros_like(a)
    acc_hi = jnp.zeros_like(a)
    for i in range(NL):
        t = b * a[i : i + 1]                      # (22, blk) broadcast mul
        if i == 0:
            acc_lo = acc_lo + t
        else:
            acc_lo = acc_lo + _cat([z[:i], t[: NL - i]])
            acc_hi = acc_hi + _cat([t[NL - i :], z[: NL - i]])
    return _reduce44(jnp.concatenate([acc_lo, acc_hi], axis=0))


def _sqrw(a):
    """Field square: the cross-term doubling trick (c_k = 2*sum_{i<k-i}
    a_i a_{k-i} + [k even] a_{k/2}^2) on the same split accumulator.

    Magnitudes: per-column cross-term count <= 11; 11 * 6.75e7 = 7.4e8
    < 2^31, doubled = 1.49e9, + diagonal 6.75e7 < 2^32 exact."""
    z = jnp.zeros_like(a)
    acc_lo = jnp.zeros_like(a)
    acc_hi = jnp.zeros_like(a)
    for i in range(NL - 1):
        t = a[i + 1 :] * a[i : i + 1]   # rows i+1..21 -> cols 2i+1..i+21
        lo = 2 * i + 1
        ln = NL - 1 - i
        n_lo = max(0, min(ln, NL - lo))
        if n_lo:
            acc_lo = acc_lo + _cat([z[:lo], t[:n_lo], z[: NL - lo - n_lo]])
        if ln - n_lo:
            start = max(lo, NL) - NL
            acc_hi = acc_hi + _cat(
                [z[:start], t[n_lo:], z[: NL - start - (ln - n_lo)]])
    acc = jnp.concatenate([acc_lo, acc_hi], axis=0)
    acc = acc + acc                                # double cross terms
    diag = a * a                                   # a_i^2 at column 2i
    de = jnp.stack([diag, jnp.zeros_like(diag)], axis=1).reshape(
        2 * NL, *diag.shape[1:])
    return _reduce44(acc + de)


def _addw(a, b):
    return _wr(a + b, passes=1)


def _subw(a, b, bias):
    return _wr(a + bias - b, passes=1)


# --------------------------------------------------- point ops, (22, blk)
# Formulas are cv.double / cv.add / cv.add_niels / cv.add_affine_niels /
# cv.to_niels restated in this geometry (dbl-2008-hwcd, add-2008-hwcd-3).


class _Pt(NamedTuple):
    X: jnp.ndarray
    Y: jnp.ndarray
    Z: jnp.ndarray
    T: jnp.ndarray


def _doublew(p: _Pt, bias, want_t: bool = True) -> _Pt:
    """dbl-2008-hwcd.  The INPUT T is never read, so inside a 4-double
    run only the last double (whose output feeds a table add) needs to
    produce T — want_t=False skips that mul (256 windows x 3 skipped
    muls; measured ~27%% off the chain, tools/exp_r3_dsm.py)."""
    XX = _sqrw(p.X)
    YY = _sqrw(p.Y)
    ZZ = _sqrw(p.Z)
    ZZ2 = _addw(ZZ, ZZ)
    XpY2 = _sqrw(p.X + p.Y)                        # lazy add, mul-safe
    Yp = _addw(YY, XX)
    Ym = _subw(YY, XX, bias)
    Ec = _subw(XpY2, Yp, bias)
    Tc = _subw(ZZ2, Ym, bias)
    return _Pt(_mulw(Ec, Tc), _mulw(Yp, Ym), _mulw(Ym, Tc),
               _mulw(Ec, Yp) if want_t else p.T)


def _addfull(p: _Pt, q: _Pt, bias, d2) -> _Pt:
    A = _mulw(_subw(p.Y, p.X, bias), _subw(q.Y, q.X, bias))
    Bv = _mulw(p.Y + p.X, q.Y + q.X)               # lazy adds
    C = _mulw(_mulw(p.T, q.T), d2)
    ZZ = _mulw(p.Z, q.Z)
    Dv = _addw(ZZ, ZZ)
    E = _subw(Bv, A, bias)
    F = _subw(Dv, C, bias)
    G = _addw(Dv, C)
    H = _addw(Bv, A)
    return _Pt(_mulw(E, F), _mulw(G, H), _mulw(F, G), _mulw(E, H))


class _Niels(NamedTuple):
    Ym: jnp.ndarray
    Yp: jnp.ndarray
    Z: jnp.ndarray
    T2d: jnp.ndarray


def _to_nielsw(p: _Pt, bias, d2) -> _Niels:
    return _Niels(_subw(p.Y, p.X, bias), _addw(p.Y, p.X), p.Z,
                  _mulw(p.T, d2))


def _add_nielsw(p: _Pt, q: _Niels, bias) -> _Pt:
    A = _mulw(_subw(p.Y, p.X, bias), q.Ym)
    Bv = _mulw(p.Y + p.X, q.Yp)
    C = _mulw(p.T, q.T2d)
    ZZ = _mulw(p.Z, q.Z)
    Dv = _addw(ZZ, ZZ)
    E = _subw(Bv, A, bias)
    F = _subw(Dv, C, bias)
    G = _addw(Dv, C)
    H = _addw(Bv, A)
    return _Pt(_mulw(E, F), _mulw(G, H), _mulw(F, G), _mulw(E, H))


def _add_affine_nielsw(p: _Pt, ym, yp, t2d, bias, want_t: bool = True) -> _Pt:
    """want_t=False: the affine add that CLOSES a window feeds the next
    window's first double, which ignores T — skip its mul."""
    A = _mulw(_subw(p.Y, p.X, bias), ym)
    Bv = _mulw(p.Y + p.X, yp)
    C = _mulw(p.T, t2d)
    Dv = _addw(p.Z, p.Z)
    E = _subw(Bv, A, bias)
    F = _subw(Dv, C, bias)
    G = _addw(Dv, C)
    H = _addw(Bv, A)
    return _Pt(_mulw(E, F), _mulw(G, H), _mulw(F, G),
               _mulw(E, H) if want_t else p.T)


# --------------------------------------------------------------- kernel


def _ones_k(blk):
    return jnp.concatenate(
        [jnp.full((1, blk), 1, jnp.int32),
         jnp.zeros((NL - 1, blk), jnp.int32)], axis=0)


def _identity_k(blk):
    z = jnp.zeros((NL, blk), jnp.int32)
    one = _ones_k(blk)
    return _Pt(z, one, one, z)


def _select_list(entries, idx, nbits=4):
    """entries: list of 2^nbits pytrees of (22, blk) planes; idx: (1, blk)
    u32.  Binary where-tree; (1, blk) masks broadcast over sublanes."""
    bits = [((idx >> k) & 1).astype(bool) for k in range(nbits)]
    cur = list(entries)
    for k in range(nbits):
        m = bits[k]
        cur = [
            jax.tree_util.tree_map(
                lambda hi, lo: jnp.where(m, hi, lo), cur[2 * i + 1], cur[2 * i]
            )
            for i in range(len(cur) // 2)
        ]
    return cur[0]


def _base_digit_table():
    """[i]B for i in 0..15 as affine-Niels scalar-literal constants
    (window 0 of the fixed-base tables — the only static table the
    shared-chain form needs)."""
    t = cv._BASE_TABS
    return [
        (fe._limb_const(t["Ym"][0, i], 2),
         fe._limb_const(t["Yp"][0, i], 2),
         fe._limb_const(t["T2d"][0, i], 2))
        for i in range(16)
    ]


# ------------------------------------------------------- signed windows
# 4-bit digits recoded to [-8, 8]: the variable table shrinks to
# [0..8]A (7 builder adds instead of 14), selects go 15-where -> 8-where
# + a cheap conditional negate, and kernel VMEM falls ~40% (larger blk
# headroom).  Negation of a Niels entry is (Ym,Yp) swap + T2d negate.


def signed_windows(w):
    """(64, *batch) u32 digits 0..15 -> (mag 0..8, sgn 0/1), value-
    preserving (sum mag*(-1)^sgn * 16^i == sum w_i 16^i).  Jittable
    low-to-high carry ripple; both ed25519 scalars are < L < 2^253 so
    the top window (<= 1) never overflows with the incoming carry."""
    def step(carry, wi):
        d = wi + carry
        over = d > 8
        mag = jnp.where(over, 16 - d, d)
        carry = over.astype(w.dtype)
        return carry, (mag, over.astype(w.dtype))
    _, (mags, sgns) = jax.lax.scan(
        step, jnp.zeros_like(w[0]), w)
    return mags, sgns


def signed_windows_ext(w):
    """signed_windows with the carry-out appended as an EXTRA top window
    (nwin -> nwin+1): value-preserving for scalars of ANY width relative
    to the window count.  Needed by the MSM p16 path — the RLC z scalars
    are full 128-bit values over nwin=32, so the in-place top window can
    overflow to 16 under the recode carry (unlike the < 2^253 ed25519
    scalars signed_windows was written for)."""
    def step(carry, wi):
        d = wi + carry
        over = d > 8
        mag = jnp.where(over, 16 - d, d)
        carry = over.astype(w.dtype)
        return carry, (mag, over.astype(w.dtype))
    carry, (mags, sgns) = jax.lax.scan(
        step, jnp.zeros_like(w[0]), w)
    mags = jnp.concatenate([mags, carry[None]], axis=0)
    sgns = jnp.concatenate([sgns, jnp.zeros_like(carry)[None]], axis=0)
    return mags, sgns


def _sel_signed_niels(tab9, mag, sgn, bias):
    """tab9: [0..8] Niels entries; mag (1, blk) in 0..8, sgn (1, blk)."""
    e8 = _select_list(tab9[:8], mag, nbits=3)
    is8 = mag == 8
    pick = jax.tree_util.tree_map(
        lambda a, b: jnp.where(is8, a, b), tab9[8], e8)
    neg = sgn == 1
    return _Niels(
        jnp.where(neg, pick.Yp, pick.Ym),
        jnp.where(neg, pick.Ym, pick.Yp),
        pick.Z,
        jnp.where(neg, _wr(bias - pick.T2d, passes=1), pick.T2d))


def _base_digit_table_signed():
    """[0..8]B affine-Niels constants plus precomputed NEGATED T2d (sign
    application is then three wheres, no in-kernel negation)."""
    t = cv._BASE_TABS
    one = fe._to_limbs_py(1)
    zero = fe._to_limbs_py(0)
    out = []
    for i in range(9):
        if i == 0:
            ym = yp = one
            t2 = nt2 = zero
        else:
            ym, yp, t2 = (t["Ym"][0, i], t["Yp"][0, i], t["T2d"][0, i])
            nt2 = fe._to_limbs_py(
                (fe.P - fe._from_limbs_py(t["T2d"][0, i])) % fe.P)
        out.append(tuple(fe._limb_const(v, 2) for v in (ym, yp, t2, nt2)))
    return out


def _sel_signed_base(tab9, mag, sgn):
    e8 = _select_list(tab9[:8], mag, nbits=3)
    is8 = mag == 8
    ym, yp, t2, nt2 = (jnp.where(is8, a, b) for a, b in zip(tab9[8], e8))
    neg = sgn == 1
    return (jnp.where(neg, yp, ym), jnp.where(neg, ym, yp),
            jnp.where(neg, nt2, t2))


def _dsm_chain(sm_ref, ss_ref, km_ref, ks_ref, a: _Pt, blk: int) -> _Pt:
    """Shared-chain [s]B + [k]A accumulation over SIGNED windows (kernel
    body helper).  s/k mag+sign refs are (64, blk) u32."""
    bias = fe._limb_const(fe._BIAS_PY, 2)           # (22, 1)
    d2 = _constw(cv.D2)

    # per-lane variable-point Niels table: [0]A .. [8]A
    pts = [_identity_k(blk), a]
    for _ in range(7):
        pts.append(_addfull(pts[-1], a, bias, d2))
    tab_a = [_to_nielsw(p, bias, d2) for p in pts]
    tab_b = _base_digit_table_signed()

    def body(i, acc):
        w = NWIN - 1 - i
        for j in range(4):
            acc = _doublew(acc, bias, want_t=(j == 3))
        km = km_ref[pl.ds(w, 1), :]                  # (1, blk)
        ks = ks_ref[pl.ds(w, 1), :]
        acc = _add_nielsw(acc, _sel_signed_niels(tab_a, km, ks, bias), bias)
        sm = sm_ref[pl.ds(w, 1), :]
        ss = ss_ref[pl.ds(w, 1), :]
        ym, yp, t2d = _sel_signed_base(tab_b, sm, ss)
        return _add_affine_nielsw(acc, ym, yp, t2d, bias, want_t=False)

    return jax.lax.fori_loop(0, NWIN, body, _identity_k(blk))


def _dsm_kernel(blk: int):
    """out = [s]B + [k]A for one block of `blk` lanes, shared-chain."""

    def kernel(sm_ref, ss_ref, km_ref, ks_ref,
               ax_ref, ay_ref, az_ref, at_ref,
               xo_ref, yo_ref, zo_ref, to_ref):
        a = _Pt(ax_ref[...], ay_ref[...], az_ref[...], at_ref[...])
        acc = _dsm_chain(sm_ref, ss_ref, km_ref, ks_ref, a, blk)
        # the T-skip chain leaves the final T stale; one identity-add
        # rescales to (4XZ, 4YZ, 4Z^2, 4XY) — same point, valid T
        bias = fe._limb_const(fe._BIAS_PY, 2)
        one = _ones_k(blk)
        acc = _add_nielsw(acc, _Niels(one, one, one, _identity_k(blk).X),
                          bias)
        xo_ref[...] = acc.X
        yo_ref[...] = acc.Y
        zo_ref[...] = acc.Z
        to_ref[...] = acc.T

    return kernel


def _dsm_tail_q_kernel(blk: int):
    """Q = [s]B + [k](-A) for one block — the compressed-R verify
    (round 4): the y-compare against R's encoded y runs IN-KERNEL
    (one mul + canon), only Q's X/Z planes leave VMEM for the XLA-side
    x-parity check (batch inversion).  Eliminates the R decompress sqrt
    chain (~half of the 53.6 ms decompress stage at 32k)."""

    def kernel(sm_ref, ss_ref, km_ref, ks_ref,
               ax_ref, ay_ref, az_ref, at_ref, yr_ref,
               oky_ref, xo_ref, zo_ref):
        bias = fe._limb_const(fe._BIAS_PY, 2)
        neg_a = _Pt(
            _wr(bias - ax_ref[...], passes=1), ay_ref[...], az_ref[...],
            _wr(bias - at_ref[...], passes=1))
        acc = _dsm_chain(sm_ref, ss_ref, km_ref, ks_ref, neg_a, blk)
        ok_y = _canon_is_zero(
            _subw(acc.Y, _mulw(yr_ref[...], acc.Z), bias))
        oky_ref[...] = ok_y.astype(jnp.uint32)
        xo_ref[...] = acc.X
        zo_ref[...] = acc.Z

    return kernel


def dsm_tail_q(wins, a: cv.Point, y_r, blk: int = 128,
               interpret: bool = False):
    """Q = [s]B + [k](-A) with precomputed signed windows; returns
    (ok_y bool (batch,), X, Z planes) where ok_y is the projective
    y-compare Y == y_r * Z."""
    sm, ss, km, ks = wins
    batch = sm.shape[1]
    assert batch % blk == 0, (batch, blk)
    win_spec = pl.BlockSpec((NWIN, blk), lambda i: (0, i))
    pt_spec = pl.BlockSpec((NL, blk), lambda i: (0, i))
    bit_spec = pl.BlockSpec((1, blk), lambda i: (0, i))
    i32 = jnp.int32
    vma = vma_of(sm, ss, km, ks, *a, y_r)
    oky, x, z = pl.pallas_call(
        _dsm_tail_q_kernel(blk),
        out_shape=[jax.ShapeDtypeStruct((1, batch), jnp.uint32, vma=vma)]
        + [jax.ShapeDtypeStruct((NL, batch), jnp.int32, vma=vma)] * 2,
        grid=(batch // blk,),
        in_specs=[win_spec] * 4 + [pt_spec] * 5,
        out_specs=[bit_spec] + [pt_spec] * 2,
        interpret=interpret,
    )(sm, ss, km, ks, a.X.astype(i32), a.Y.astype(i32), a.Z.astype(i32),
      a.T.astype(i32), y_r.astype(i32))
    return oky[0] == 1, x.astype(jnp.uint32), z.astype(jnp.uint32)


def double_scalar_mul_base(s_windows, k_windows, a: cv.Point,
                           blk: int = 128, interpret: bool = False):
    """Drop-in Pallas replacement for cv.double_scalar_mul_base.

    s_windows, k_windows: uint32 (64, batch) unsigned digits; a: Point of
    (22, batch) planes.  batch must be a multiple of `blk`.
    """
    batch = s_windows.shape[1]
    assert batch % blk == 0, (batch, blk)
    sm, ss = signed_windows(s_windows)
    km, ks = signed_windows(k_windows)
    win_spec = pl.BlockSpec((NWIN, blk), lambda i: (0, i))
    pt_spec = pl.BlockSpec((NL, blk), lambda i: (0, i))
    i32 = jnp.int32
    vma = vma_of(sm, ss, km, ks, *a)
    outs = pl.pallas_call(
        _dsm_kernel(blk),
        out_shape=[jax.ShapeDtypeStruct((NL, batch), jnp.int32,
                                        vma=vma)] * 4,
        grid=(batch // blk,),
        in_specs=[win_spec] * 4 + [pt_spec] * 4,
        out_specs=[pt_spec] * 4,
        interpret=interpret,
    )(sm, ss, km, ks, a.X.astype(i32), a.Y.astype(i32), a.Z.astype(i32),
      a.T.astype(i32))
    return cv.Point(*(t.astype(jnp.uint32) for t in outs))


# --------------------------------------------------------- sqrt_ratio kernel


def _serial_carry(d):
    """Two exact serial carry passes + >=2^255 fold: representation unique
    up to {value, value+p} with value < p + 2^12 (fe.canonical's phase 1)."""
    for _ in range(2):
        rows = [d[i : i + 1] for i in range(NL)]
        for i in range(NL - 1):
            rows[i + 1] = rows[i + 1] + (rows[i] >> B12)
            rows[i] = rows[i] & MASK
        t = rows[NL - 1] >> 3
        rows[NL - 1] = rows[NL - 1] & 7
        rows[0] = rows[0] + t * 19
        d = jnp.concatenate(rows, axis=0)
    return d


def _canon_is_zero(d):
    """(22, blk) NORMAL-form -> (1, blk) bool: value ≡ 0 mod p (after the
    serial passes zero is represented as exactly 0 or p)."""
    d = _serial_carry(d)
    p_limbs = fe._limb_const(fe._to_limbs_py(fe.P), 2)
    is0 = jnp.min((d == 0).astype(jnp.int32), axis=0, keepdims=True)
    isp = jnp.min((d == p_limbs).astype(jnp.int32), axis=0, keepdims=True)
    return (is0 | isp) == 1


def _canon(d):
    """Full canonical form (fe.canonical in (22, blk) geometry): serial
    carries then two conditional subtracts of p."""
    d = _serial_carry(d)
    p_rows = [int(v) for v in fe._to_limbs_py(fe.P)]
    for _ in range(2):
        rows = [d[i : i + 1] for i in range(NL)]
        borrow = jnp.zeros_like(rows[0])
        diff = []
        for i in range(NL):
            t = rows[i] + jnp.int32(1 << B12) - jnp.int32(p_rows[i]) - borrow
            diff.append(t & MASK)
            borrow = 1 - (t >> B12)
        ge = borrow == 0
        d = jnp.concatenate(
            [jnp.where(ge, dd, rr) for dd, rr in zip(diff, rows)], axis=0)
    return d


def _eq_const(d_canon, val: int):
    """(22, blk) canonical == python constant -> (1, blk) bool."""
    c = fe._limb_const(fe._to_limbs_py(val), 2)
    return jnp.min((d_canon == c).astype(jnp.int32), axis=0,
                   keepdims=True) == 1


def _sqrt_uv(u, v, bias):
    """x = sqrt(u/v) candidate + ok/flip masks — RFC 8032 5.1.3 recipe
    (semantic contract: fe.sqrt_ratio / ref fd_f25519_sqrt_ratio).  The
    pow chain exploits (p-5)/8 = 2^252 - 3 whose 4-bit digits are
    F,F,...,F,D: every window multiplies by t^15 except the last (t^13) —
    no dynamic table selection at all."""
    v2 = _sqrw(v)
    v3 = _mulw(v2, v)
    v7 = _mulw(_sqrw(v2), v3)
    t0 = _mulw(u, v7)

    t2 = _sqrw(t0)
    t4 = _sqrw(t2)
    t8 = _sqrw(t4)
    t12 = _mulw(t8, t4)
    t13 = _mulw(t12, t0)
    t15 = _mulw(t13, t2)

    def body(i, r):
        for _ in range(4):
            r = _sqrw(r)
        return _mulw(r, t15)

    r = jax.lax.fori_loop(0, 61, body, t15)      # 62 leading F windows
    for _ in range(4):
        r = _sqrw(r)
    r = _mulw(r, t13)                             # trailing D window

    x = _mulw(_mulw(u, v3), r)
    vxx = _mulw(_sqrw(x), v)
    good = _canon_is_zero(_subw(vxx, u, bias))
    flipped = _canon_is_zero(_wr(vxx + u, passes=1))
    x = jnp.where(flipped, _mulw(x, _constw(fe.SQRT_M1)), x)
    return good | flipped, x


def _decompress_kernel(blk: int):
    """Full batch point decompression + small-order test in one kernel
    (semantic contract: fd_ed25519_point_frombytes,
    src/ballet/ed25519/fd_curve25519.c:26-63, plus
    fd_ed25519_affine_is_small_order).  Inputs are y limbs + sign bits
    (byte unpack stays in XLA); outputs ok/small masks, x, t=x*y."""

    def kernel(y_ref, sg_ref, ok_ref, sm_ref, x_ref, t_ref):
        bias = fe._limb_const(fe._BIAS_PY, 2)
        y = y_ref[...]
        sign = sg_ref[...]
        one = _ones_k(blk)
        yy = _sqrw(y)
        u = _subw(yy, one, bias)
        v = _addw(_mulw(yy, _constw(cv.D)), one)
        ok, x = _sqrt_uv(u, v, bias)

        xc = _canon(x)
        flip = (xc[:1] & 1) != sign
        x = jnp.where(flip, _wr(bias - x, passes=1), x)

        # small-order: x == 0 | y canonical in {0, order8_y0, order8_y1}
        yc = _canon(y)
        small = (
            _canon_is_zero(x)
            | _eq_const(yc, 0)
            | _eq_const(yc, cv._ORDER8_Y0 % fe.P)
            | _eq_const(yc, cv._ORDER8_Y1 % fe.P)
        )

        ok_ref[...] = ok.astype(jnp.uint32)
        sm_ref[...] = small.astype(jnp.uint32)
        x_ref[...] = x
        t_ref[...] = _mulw(x, y)

    return kernel


def decompress(b, blk: int = 256, interpret: bool = False):
    """Pallas replacement for cv.decompress + is_small_order_affine.

    b: uint8 (batch, 32).  Returns (ok (batch,), small (batch,), Point)."""
    batch = b.shape[0]
    assert batch % blk == 0, (batch, blk)
    y = fe.from_bytes(b)
    sign = (b[:, 31] >> 7).astype(jnp.uint32)[None, :]
    pt_spec = pl.BlockSpec((NL, blk), lambda i: (0, i))
    bit_spec = pl.BlockSpec((1, blk), lambda i: (0, i))
    vma = vma_of(y, sign)
    ok, small, x, t = pl.pallas_call(
        _decompress_kernel(blk),
        out_shape=[jax.ShapeDtypeStruct((1, batch), jnp.uint32, vma=vma),
                   jax.ShapeDtypeStruct((1, batch), jnp.uint32, vma=vma),
                   jax.ShapeDtypeStruct((NL, batch), jnp.int32, vma=vma),
                   jax.ShapeDtypeStruct((NL, batch), jnp.int32, vma=vma)],
        grid=(batch // blk,),
        in_specs=[pt_spec, bit_spec],
        out_specs=[bit_spec, bit_spec, pt_spec, pt_spec],
        interpret=interpret,
    )(y.astype(jnp.int32), sign.astype(jnp.int32))
    x = x.astype(jnp.uint32)
    t = t.astype(jnp.uint32)
    one = fe.ones((batch,))
    return ok[0] == 1, small[0] == 1, cv.Point(x, y, one, t)


# ------------------------------------------- scalar reduce/recode kernel


def _rows(x):
    return [x[i : i + 1] for i in range(x.shape[0])]


def _b2l_rows(byte_rows, nlimb):
    """Little-endian byte rows -> 12-bit limb rows (scalar25519
    bytes_to_limbs transcribed to row ops)."""
    ngroups = (nlimb + 1) // 2
    need = 3 * ngroups + 1
    z = jnp.zeros_like(byte_rows[0])
    xs = list(byte_rows) + [z] * max(0, need - len(byte_rows))
    limbs = []
    for t in range(ngroups):
        limbs.append(xs[3 * t] | ((xs[3 * t + 1] & 0xF) << 8))
        limbs.append((xs[3 * t + 1] >> 4) | (xs[3 * t + 2] << 4))
    return limbs[:nlimb]


_SC_B = 12
_SC_MASK = (1 << _SC_B) - 1
_SC_L = 2**252 + 27742317777372353535851937790883648493
_SC_C = _SC_L - 2**252
_SC_C_LIMBS = [(_SC_C >> (_SC_B * i)) & _SC_MASK for i in range(11)]
_SC_L_LIMBS = [(_SC_L >> (_SC_B * i)) & _SC_MASK for i in range(22)]
_SC_L2_LIMBS = [((2 * _SC_L) >> (_SC_B * i)) & _SC_MASK for i in range(22)]


def _sc_carry_rows(rows, passes):
    for _ in range(passes):
        lo = [r & _SC_MASK for r in rows]
        hi = [r >> _SC_B for r in rows]          # arithmetic (int32)
        rows = [lo[0]] + [lo[i] + hi[i - 1] for i in range(1, len(rows))]
    return rows


def _sc_fold_rows(rows):
    """scalar25519._fold_once on row lists: lo(21) - C*hi with 2 headroom
    limbs (concat-ladder instead of at[].add — Mosaic has no DUS)."""
    n = len(rows)
    hi = rows[21:]
    m = n - 21
    out_len = max(21, m + 11) + 2
    z = jnp.zeros_like(rows[0])
    out = rows[:21] + [z] * (out_len - 21)
    for i in range(11):
        c = jnp.int32(_SC_C_LIMBS[i])
        for j, h in enumerate(hi):
            out[i + j] = out[i + j] - c * h
    return out


def _sc_cond_sub_rows(rows, times):
    n = len(rows)
    for i in range(n - 1):
        rows[i + 1] = rows[i + 1] + (rows[i] >> _SC_B)
        rows[i] = rows[i] & _SC_MASK
    rows = rows[:22]
    for _ in range(times):
        borrow = jnp.zeros_like(rows[0])
        diff = []
        for i in range(22):
            t = (rows[i] + jnp.int32(1 << _SC_B)
                 - jnp.int32(_SC_L_LIMBS[i]) - borrow)
            diff.append(t & _SC_MASK)
            borrow = 1 - (t >> _SC_B)
        ge = borrow == 0
        rows = [jnp.where(ge, d, r) for d, r in zip(diff, rows)]
    return rows


def _limbs_to_signed_windows(limb_rows):
    """22x12-bit limb rows -> 64 signed 4-bit window rows (mag, sgn).
    Window w covers bits [4w, 4w+4): limb w*4//12, shift (w%3)*4.  The
    recode ripples a carry low->high (same contract as signed_windows);
    the top window of an L-reduced scalar is <= 1 so it never overflows."""
    mags, sgns = [], []
    carry = jnp.zeros_like(limb_rows[0])
    for w in range(64):
        j, sh = divmod(w, 3)
        d = ((limb_rows[j] >> (4 * sh)) & 0xF) + carry
        over = d > 8
        mags.append(jnp.where(over, 16 - d, d).astype(jnp.uint32))
        sgns.append(over.astype(jnp.uint32))
        carry = over.astype(d.dtype)
    return mags, sgns


def _reduce_recode_kernel(blk: int):
    """s bytes + SHA-512 digest -> canonicity bit + signed windows for
    BOTH scalars, in one VMEM-resident pass.  Replaces the XLA chain
    (is_canonical, reduce_512, limbs_to_windows, scalar_windows, signed
    recode) whose ~200 serial (1, batch) row ops cost more at batch 32k
    than the whole dsm kernel (measured: reduce_512+windows ~90 ms vs
    dsm ~34 ms)."""

    def kernel(sb_ref, db_ref, oks_ref, sm_ref, ss_ref, km_ref, ks_ref):
        sb = [r.astype(jnp.int32) for r in _rows(sb_ref[...])]
        db = [r.astype(jnp.int32) for r in _rows(db_ref[...])]

        # ---- k = digest mod L (scalar25519.reduce_512 transcription)
        x = _b2l_rows(db, 44)
        for _ in range(3):
            x = _sc_fold_rows(x)
            x = _sc_carry_rows(x, 2)
        x = [x[i] + jnp.int32(_SC_L2_LIMBS[i]) if i < 22 else x[i]
             for i in range(len(x))]
        x = _sc_carry_rows(x, 3)
        k_limbs = _sc_cond_sub_rows(x, 4)
        km, ks = _limbs_to_signed_windows(k_limbs)

        # ---- s: canonicity (s < L) + windows
        s_limbs = _b2l_rows(sb, 22)
        borrow = jnp.zeros_like(s_limbs[0])
        for i in range(22):
            t = (s_limbs[i] + jnp.int32(1 << _SC_B)
                 - jnp.int32(_SC_L_LIMBS[i]) - borrow)
            borrow = 1 - (t >> _SC_B)
        ok_s = borrow == 1                       # borrow out -> s < L
        sm, ss = _limbs_to_signed_windows(s_limbs)

        oks_ref[...] = ok_s.astype(jnp.uint32)
        sm_ref[...] = jnp.concatenate(sm, axis=0)
        ss_ref[...] = jnp.concatenate(ss, axis=0)
        km_ref[...] = jnp.concatenate(km, axis=0)
        ks_ref[...] = jnp.concatenate(ks, axis=0)

    return kernel


def reduce_recode(s_bytes, digest, blk: int = 128, interpret: bool = False):
    """s_bytes: uint8 (batch, 32); digest: uint8 (batch, 64).
    Returns (ok_s bool (batch,), (smag, ssgn, kmag, ksgn) each uint32
    (64, batch)) — kernel-ready signed windows for dsm_tail_q."""
    batch = s_bytes.shape[0]
    assert batch % blk == 0, (batch, blk)
    sb = s_bytes.T.astype(jnp.uint32)
    db = digest.T.astype(jnp.uint32)
    in_specs = [pl.BlockSpec((32, blk), lambda i: (0, i)),
                pl.BlockSpec((64, blk), lambda i: (0, i))]
    bit_spec = pl.BlockSpec((1, blk), lambda i: (0, i))
    win_spec = pl.BlockSpec((NWIN, blk), lambda i: (0, i))
    vma = vma_of(sb, db)
    ok, sm, ss, km, ks = pl.pallas_call(
        _reduce_recode_kernel(blk),
        out_shape=[jax.ShapeDtypeStruct((1, batch), jnp.uint32, vma=vma)]
        + [jax.ShapeDtypeStruct((NWIN, batch), jnp.uint32, vma=vma)] * 4,
        grid=(batch // blk,),
        in_specs=in_specs,
        out_specs=[bit_spec] + [win_spec] * 4,
        interpret=interpret,
    )(sb, db)
    return ok[0] == 1, (sm, ss, km, ks)


def _sc_mul_rows(a22, b11):
    """Row-list transcription of scalar25519.mul_mod_l for a 22x11 limb
    product (the RLC path's z*k and z*s): convolution (<= 11 products of
    two 12-bit limbs per column < 2^28, exact in int32), then the same
    normalize/fold/canonicalize ladder as the XLA reference."""
    z = jnp.zeros_like(a22[0])
    rows = [z] * (22 + 11)
    for i in range(11):
        c = b11[i]
        for j in range(22):
            rows[i + j] = rows[i + j] + c * a22[j]
    rows = _sc_carry_rows(rows, 3)
    while len(rows) > 23:
        rows = _sc_carry_rows(_sc_fold_rows(rows), 2)
    rows = _sc_carry_rows(_sc_fold_rows(rows), 2)
    rows = [rows[i] + jnp.int32(_SC_L2_LIMBS[i]) if i < 22 else rows[i]
            for i in range(len(rows))]
    rows = _sc_carry_rows(rows, 3)
    return _sc_cond_sub_rows(rows, 4)


def _limbs_to_u4_windows(limb_rows, nwin):
    """22x12-bit limb rows -> nwin unsigned 4-bit window rows (the MSM
    kernel's [0..15] table digits)."""
    return [((limb_rows[j // 3] >> (4 * (j % 3))) & 0xF).astype(jnp.uint32)
            for j in range(nwin)]


def _rlc_recode_kernel(blk: int):
    """RLC batch-verify scalar chain in ONE VMEM-resident pass:
    s canonicity, k = digest mod L, w = z*k mod L, zs = z*s mod L, and
    unsigned 4-bit windows of w (64) and z (32).

    MEASURED NEGATIVE RESULT (r4, kept for the record + parity test):
    106 ms at 32k vs the XLA chain's 60 ms.  The 22x11 mod-L convolutions
    here run as ~500 per-(1,blk)-row ops — 1/8 VPU tile utilization —
    while XLA vectorizes the identical chain across the full batch.
    verify_batch_rlc therefore keeps its scalars in XLA; a future rewrite
    would need _mulw-style whole-(22,blk)-array accumulation to pay off
    (docs/perf_ceiling.md round-4 addendum)."""

    def kernel(sb_ref, db_ref, zb_ref, oks_ref, ww_ref, zw_ref, zs_ref):
        sb = [r.astype(jnp.int32) for r in _rows(sb_ref[...])]
        db = [r.astype(jnp.int32) for r in _rows(db_ref[...])]
        zb = [r.astype(jnp.int32) for r in _rows(zb_ref[...])]

        # ---- k = digest mod L (reduce_512 transcription)
        x = _b2l_rows(db, 44)
        for _ in range(3):
            x = _sc_fold_rows(x)
            x = _sc_carry_rows(x, 2)
        x = [x[i] + jnp.int32(_SC_L2_LIMBS[i]) if i < 22 else x[i]
             for i in range(len(x))]
        x = _sc_carry_rows(x, 3)
        k_limbs = _sc_cond_sub_rows(x, 4)

        # ---- s canonicity (s < L)
        s_limbs = _b2l_rows(sb, 22)
        borrow = jnp.zeros_like(s_limbs[0])
        for i in range(22):
            t = (s_limbs[i] + jnp.int32(1 << _SC_B)
                 - jnp.int32(_SC_L_LIMBS[i]) - borrow)
            borrow = 1 - (t >> _SC_B)
        ok_s = borrow == 1

        # ---- z (128-bit host randomness) -> 11 limbs
        z_limbs = _b2l_rows(zb, 11)

        # ---- w = z*k, zs = z*s (both mod L, canonical limbs)
        w_limbs = _sc_mul_rows(k_limbs, z_limbs)
        zs_limbs = _sc_mul_rows(s_limbs, z_limbs)

        oks_ref[...] = ok_s.astype(jnp.uint32)
        ww_ref[...] = jnp.concatenate(
            _limbs_to_u4_windows(w_limbs, 64), axis=0)
        zw_ref[...] = jnp.concatenate(
            _limbs_to_u4_windows(z_limbs + [jnp.zeros_like(z_limbs[0])] * 11,
                                 32), axis=0)
        zs_ref[...] = jnp.concatenate(zs_limbs, axis=0)

    return kernel


def rlc_recode(s_bytes, digest, z_bytes, blk: int = 128,
               interpret: bool = False):
    """s_bytes: uint8 (batch, 32); digest: uint8 (batch, 64); z_bytes:
    uint8 (batch, 16).  Returns (ok_s bool (batch,), w_wins u32
    (64, batch), z_wins u32 (32, batch), zs_limbs i32 (22, batch))
    — MSM-ready unsigned windows plus per-lane z*s products for the
    XLA-side sum_mod_l reduction."""
    batch = s_bytes.shape[0]
    assert batch % blk == 0, (batch, blk)
    sb = s_bytes.T.astype(jnp.uint32)
    db = digest.T.astype(jnp.uint32)
    zb = z_bytes.T.astype(jnp.uint32)
    in_specs = [pl.BlockSpec((32, blk), lambda i: (0, i)),
                pl.BlockSpec((64, blk), lambda i: (0, i)),
                pl.BlockSpec((16, blk), lambda i: (0, i))]
    bit_spec = pl.BlockSpec((1, blk), lambda i: (0, i))
    vma = vma_of(sb, db, zb)
    ok, ww, zw, zs = pl.pallas_call(
        _rlc_recode_kernel(blk),
        out_shape=[jax.ShapeDtypeStruct((1, batch), jnp.uint32, vma=vma),
                   jax.ShapeDtypeStruct((64, batch), jnp.uint32, vma=vma),
                   jax.ShapeDtypeStruct((32, batch), jnp.uint32, vma=vma),
                   jax.ShapeDtypeStruct((22, batch), jnp.int32, vma=vma)],
        grid=(batch // blk,),
        in_specs=in_specs,
        out_specs=[bit_spec,
                   pl.BlockSpec((64, blk), lambda i: (0, i)),
                   pl.BlockSpec((32, blk), lambda i: (0, i)),
                   pl.BlockSpec((22, blk), lambda i: (0, i))],
        interpret=interpret,
    )(sb, db, zb)
    return ok[0] == 1, ww, zw, zs


# --------------------------------------------------- fused verify tail
# Round-5 structural lever (VERDICT r4 #1): ONE kernel does A-decompress,
# scalar reduce/recode and the dsm tail — the three hot kernels fused so
# A's planes and both scalars' windows never leave VMEM between stages
# (previously: 3 kernel launches with (22, batch) x4 + (64, batch) x4
# HBM round-trips between them, plus a separate negate pass over A).


def _fused_tail_kernel(blk: int):
    """pubkey y/sign + s bytes + SHA digest + R's y -> one combined ok bit
    (A decompresses & not small-order & s canonical & projective y match)
    plus Q's X/Z planes for the XLA-side x-parity tail.

    Body = _decompress_kernel + _reduce_recode_kernel + _dsm_tail_q_kernel
    compositions; windows stage through VMEM scratch refs because the dsm
    chain's window loop indexes a Ref via pl.ds (dynamic sublane slices of
    in-register arrays don't lower)."""

    def kernel(ay_ref, asg_ref, sb_ref, db_ref, yr_ref,
               ok_ref, xo_ref, zo_ref,
               sm_ref, ss_ref, km_ref, ks_ref):
        bias = fe._limb_const(fe._BIAS_PY, 2)
        one = _ones_k(blk)

        # ---- A decompress + small-order test (fd_ed25519_point_frombytes
        # + affine_is_small_order semantics, as _decompress_kernel)
        y = ay_ref[...]
        sign = asg_ref[...]
        yy = _sqrw(y)
        u = _subw(yy, one, bias)
        v = _addw(_mulw(yy, _constw(cv.D)), one)
        ok_a, x = _sqrt_uv(u, v, bias)
        xc = _canon(x)
        flip = (xc[:1] & 1) != sign
        x = jnp.where(flip, _wr(bias - x, passes=1), x)
        yc = _canon(y)
        small = (
            _canon_is_zero(x)
            | _eq_const(yc, 0)
            | _eq_const(yc, cv._ORDER8_Y0 % fe.P)
            | _eq_const(yc, cv._ORDER8_Y1 % fe.P)
        )
        # the chain computes [s]B + [k](-A): negate A in place (one mul
        # for T, where the split path paid a separate negate pass)
        neg_x = _wr(bias - x, passes=1)
        neg_a = _Pt(neg_x, y, one, _mulw(neg_x, y))

        # ---- s canonicity + signed windows for BOTH scalars (the
        # _reduce_recode_kernel body), staged into the scratch refs
        sb = [r.astype(jnp.int32) for r in _rows(sb_ref[...])]
        db = [r.astype(jnp.int32) for r in _rows(db_ref[...])]
        xr = _b2l_rows(db, 44)
        for _ in range(3):
            xr = _sc_fold_rows(xr)
            xr = _sc_carry_rows(xr, 2)
        xr = [xr[i] + jnp.int32(_SC_L2_LIMBS[i]) if i < 22 else xr[i]
              for i in range(len(xr))]
        xr = _sc_carry_rows(xr, 3)
        k_limbs = _sc_cond_sub_rows(xr, 4)
        km, ks = _limbs_to_signed_windows(k_limbs)

        s_limbs = _b2l_rows(sb, 22)
        borrow = jnp.zeros_like(s_limbs[0])
        for i in range(22):
            t = (s_limbs[i] + jnp.int32(1 << _SC_B)
                 - jnp.int32(_SC_L_LIMBS[i]) - borrow)
            borrow = 1 - (t >> _SC_B)
        ok_s = borrow == 1
        sm, ss = _limbs_to_signed_windows(s_limbs)

        sm_ref[...] = jnp.concatenate(sm, axis=0)
        ss_ref[...] = jnp.concatenate(ss, axis=0)
        km_ref[...] = jnp.concatenate(km, axis=0)
        ks_ref[...] = jnp.concatenate(ks, axis=0)

        # ---- shared-chain dsm + in-kernel projective y-compare
        acc = _dsm_chain(sm_ref, ss_ref, km_ref, ks_ref, neg_a, blk)
        ok_y = _canon_is_zero(
            _subw(acc.Y, _mulw(yr_ref[...], acc.Z), bias))

        ok_ref[...] = (ok_a & ~small & ok_s & ok_y).astype(jnp.uint32)
        xo_ref[...] = acc.X
        zo_ref[...] = acc.Z

    return kernel


def verify_tail_fused(pubkeys, s_bytes, digest, y_r, blk: int = 128,
                      interpret: bool = False):
    """Fused strict-verify tail: returns (ok bool (batch,), X, Z) where ok
    already folds A-decompress/small-order, S-canonicity and the
    projective y-compare; callers finish with the XLA x-parity check
    (ed25519._compressed_r_check with ok_y=ok)."""
    batch = pubkeys.shape[0]
    assert batch % blk == 0, (batch, blk)
    y = fe.from_bytes(pubkeys)
    sign = (pubkeys[:, 31] >> 7).astype(jnp.uint32)[None, :]
    sb = s_bytes.T.astype(jnp.uint32)
    db = digest.T.astype(jnp.uint32)
    pt_spec = pl.BlockSpec((NL, blk), lambda i: (0, i))
    bit_spec = pl.BlockSpec((1, blk), lambda i: (0, i))
    vma = vma_of(y, sign, sb, db, y_r)
    ok, x, z = pl.pallas_call(
        _fused_tail_kernel(blk),
        out_shape=[jax.ShapeDtypeStruct((1, batch), jnp.uint32, vma=vma)]
        + [jax.ShapeDtypeStruct((NL, batch), jnp.int32, vma=vma)] * 2,
        grid=(batch // blk,),
        in_specs=[pt_spec, bit_spec,
                  pl.BlockSpec((32, blk), lambda i: (0, i)),
                  pl.BlockSpec((64, blk), lambda i: (0, i)),
                  pt_spec],
        out_specs=[bit_spec] + [pt_spec] * 2,
        scratch_shapes=[pltpu.VMEM((NWIN, blk), jnp.uint32)] * 4,
        interpret=interpret,
    )(y.astype(jnp.int32), sign.astype(jnp.int32), sb, db,
      y_r.astype(jnp.int32))
    return ok[0] == 1, x.astype(jnp.uint32), z.astype(jnp.uint32)


# ------------------------------------------------------------- MSM kernel


def _msm_kernel(m: int, nwin: int, blk: int):
    """Lane-parallel Straus MSM (semantic contract: cv.msm): each lane
    accumulates its m points inside ONE shared 4-bit-window chain, so the
    4 doublings per window are paid once per lane, not once per point —
    per-point cost falls to nwin*4/m doublings + nwin adds.  This is the
    op-count win that makes RLC batch verification pay once the chain
    runs at Pallas (VMEM-resident) speed; under XLA the same structure
    lost to strict (round-1 finding, now obsolete — see
    docs/perf_ceiling.md).

    wins_ref: (nwin*m, blk) u32, row w*m+j = window w of point j's
    scalar.  Point planes: (m*22, blk), rows [22j, 22j+22) = point j.
    """

    def kernel(wins_ref, x_ref, y_ref, z_ref, t_ref,
               xo_ref, yo_ref, zo_ref, to_ref):
        bias = fe._limb_const(fe._BIAS_PY, 2)
        d2 = _constw(cv.D2)

        tabs = []
        for j in range(m):
            pj = _Pt(
                x_ref[22 * j : 22 * j + 22, :],
                y_ref[22 * j : 22 * j + 22, :],
                z_ref[22 * j : 22 * j + 22, :],
                t_ref[22 * j : 22 * j + 22, :])
            pts = [_identity_k(blk), pj]
            for _ in range(14):
                pts.append(_addfull(pts[-1], pj, bias, d2))
            tabs.append([_to_nielsw(p, bias, d2) for p in pts])

        def body(i, acc):
            w = nwin - 1 - i
            acc = jax.lax.fori_loop(
                0, 4, lambda _, q: _doublew(q, bias), acc)
            for j in range(m):
                wv = wins_ref[pl.ds(w * m + j, 1), :]
                acc = _add_nielsw(acc, _select_list(tabs[j], wv), bias)
            return acc

        acc = jax.lax.fori_loop(0, nwin, body, _identity_k(blk))
        xo_ref[...] = acc.X
        yo_ref[...] = acc.Y
        zo_ref[...] = acc.Z
        to_ref[...] = acc.T

    return kernel


# --------------------------------------------- select-redesigned MSM (r6)
# The r4 fused-chain profile pinned ~45% of kernel time on table selects
# (15-where binary trees over 4 planes x (22, blk) per add).  Lever
# measured here (docs/perf_ceiling.md round-5/6): shrink the data volume
# a select moves, not the add count.


def _pack16(x):
    """(22, blk) 12-bit limbs -> (11, blk): limb i | limb i+11 << 16.
    Safe for NORMAL/LAZY magnitudes (every limb < 2^14 << 2^16); the
    packed word stays positive in int32 so arithmetic >> unpacks
    exactly."""
    return x[:11] | (x[11:] << 16)


def _unpack16(p):
    return jnp.concatenate([p & 0xFFFF, (p >> 16) & 0xFFFF], axis=0)


def _sel_signed_p16(tab9, mag, sgn):
    """Two's-complement digit select over packed planes.  tab9: 9 entries
    of (pYm, pYp, pZ, pT2d, pNT2d) packed (11, blk) planes for digits
    0..8; mag (1, blk) 0..8, sgn (1, blk) 0/1.  3-bit where-tree over
    [0..8) + an is8 pick + three sign wheres, ALL on half-height packed
    planes; unpack only the four planes the add consumes."""
    e8 = _select_list(tab9[:8], mag, nbits=3)
    is8 = mag == 8
    ym, yp, z, t2, nt2 = (jnp.where(is8, a, b)
                          for a, b in zip(tab9[8], e8))
    neg = sgn == 1
    return _Niels(
        _unpack16(jnp.where(neg, yp, ym)),
        _unpack16(jnp.where(neg, ym, yp)),
        _unpack16(z),
        _unpack16(jnp.where(neg, nt2, t2)))


def _msm_kernel_p16(m: int, nwin: int, blk: int):
    """Straus MSM with the redesigned table select (semantic contract:
    bit-identical to _msm_kernel).  Three changes:

      * signed digits [-8..8] (signed_windows_ext): 9-entry tables need
        7 builder _addfulls per point instead of 14, and the select tree
        is 3 levels + is8 + sign instead of 4 levels over 16 entries
      * packed 16-bit limb planes: two 12-bit limbs per int32, so every
        where in the tree moves (11, blk) instead of (22, blk) — half
        the select data volume; unpack happens once, after the pick
      * negated T2d precomputed per table entry: applying the digit sign
        costs three wheres, no in-select field negation

    `nwin` here COUNTS the recode carry-out window (callers pass the
    unsigned window count + 1).  mag/sgn refs: (nwin*m, blk) u32, row
    w*m+j = window w of point j, same row convention as _msm_kernel.
    """

    def kernel(mag_ref, sgn_ref, x_ref, y_ref, z_ref, t_ref,
               xo_ref, yo_ref, zo_ref, to_ref):
        bias = fe._limb_const(fe._BIAS_PY, 2)
        d2 = _constw(cv.D2)

        tabs = []
        for j in range(m):
            pj = _Pt(
                x_ref[22 * j : 22 * j + 22, :],
                y_ref[22 * j : 22 * j + 22, :],
                z_ref[22 * j : 22 * j + 22, :],
                t_ref[22 * j : 22 * j + 22, :])
            pts = [_identity_k(blk), pj]
            for _ in range(7):
                pts.append(_addfull(pts[-1], pj, bias, d2))
            ent = []
            for p in pts:
                nl = _to_nielsw(p, bias, d2)
                nt2 = _wr(bias - nl.T2d, passes=1)
                ent.append(tuple(_pack16(v) for v in
                                 (nl.Ym, nl.Yp, nl.Z, nl.T2d, nt2)))
            tabs.append(ent)

        def body(i, acc):
            w = nwin - 1 - i
            acc = jax.lax.fori_loop(
                0, 4, lambda _, q: _doublew(q, bias), acc)
            for j in range(m):
                mg = mag_ref[pl.ds(w * m + j, 1), :]
                sg = sgn_ref[pl.ds(w * m + j, 1), :]
                acc = _add_nielsw(acc, _sel_signed_p16(tabs[j], mg, sg),
                                  bias)
            return acc

        acc = jax.lax.fori_loop(0, nwin, body, _identity_k(blk))
        xo_ref[...] = acc.X
        yo_ref[...] = acc.Y
        zo_ref[...] = acc.Z
        to_ref[...] = acc.T

    return kernel


def msm(windows, points: cv.Point, m: int = 8, nwin: int = 64,
        blk: int = 128, interpret: bool = False,
        select: str = "legacy") -> cv.Point:
    """Pallas replacement for cv.msm: Σ_i [s_i]P_i over a flat batch of n
    points.  windows: uint32 (nwin, n) low-window-first; points: (22, n)
    planes; n % (m*blk) == 0.  Returns one unbatched Point.

    select: "legacy" (unsigned 16-entry tables, 4-level where-tree) or
    "p16" (signed digits + packed 16-bit limb planes, _msm_kernel_p16) —
    same verdict bits either way (tests/test_curve_pallas.py).

    Layout note: cv.msm reshapes n -> (lanes, m) with the batch LAST; we
    keep the same (m, lanes) split so results are bit-identical: lane l
    accumulates points [j*lanes + l for j in range(m)].
    """
    n = windows.shape[1]
    assert n % m == 0, (n, m)
    lanes = n // m
    assert lanes % blk == 0, (lanes, blk)

    pl_planes = [p.reshape(m * NL, lanes) for p in
                 (points.X.reshape(NL, m, lanes).transpose(1, 0, 2),
                  points.Y.reshape(NL, m, lanes).transpose(1, 0, 2),
                  points.Z.reshape(NL, m, lanes).transpose(1, 0, 2),
                  points.T.reshape(NL, m, lanes).transpose(1, 0, 2))]
    pts_spec = pl.BlockSpec((m * NL, blk), lambda i: (0, i))
    out_spec = pl.BlockSpec((NL, blk), lambda i: (0, i))

    vma = vma_of(windows, *pl_planes)

    def rows(a, nw):
        # (nw, n) -> rows w*m+j over (lanes,): point j of lane l is flat
        # index j*lanes + l (cv.msm's reshape(m, lanes) convention)
        return a.reshape(nw, m, lanes).reshape(nw * m, lanes)

    if select == "p16":
        mags, sgns = signed_windows_ext(windows)     # (nwin+1, n)
        nw2 = nwin + 1
        win_spec = pl.BlockSpec((nw2 * m, blk), lambda i: (0, i))
        outs = pl.pallas_call(
            _msm_kernel_p16(m, nw2, blk),
            out_shape=[jax.ShapeDtypeStruct((NL, lanes), jnp.int32,
                                            vma=vma)] * 4,
            grid=(lanes // blk,),
            in_specs=[win_spec] * 2 + [pts_spec] * 4,
            out_specs=[out_spec] * 4,
            interpret=interpret,
        )(rows(mags, nw2), rows(sgns, nw2),
          *(t.astype(jnp.int32) for t in pl_planes))
    else:
        assert select == "legacy", select
        win_spec = pl.BlockSpec((nwin * m, blk), lambda i: (0, i))
        outs = pl.pallas_call(
            _msm_kernel(m, nwin, blk),
            out_shape=[jax.ShapeDtypeStruct((NL, lanes), jnp.int32,
                                            vma=vma)] * 4,
            grid=(lanes // blk,),
            in_specs=[win_spec] + [pts_spec] * 4,
            out_specs=[out_spec] * 4,
            interpret=interpret,
        )(rows(windows, nwin), *(t.astype(jnp.int32) for t in pl_planes))
    acc = cv.Point(*(t.astype(jnp.uint32) for t in outs))

    # tree-fold the lanes to one point (XLA; log2(lanes) adds on
    # shrinking arrays)
    while lanes > 1:
        half = lanes // 2
        lo = cv.Point(*(t[:, :half] for t in acc))
        hi = cv.Point(*(t[:, half : 2 * half] for t in acc))
        s = cv.add(lo, hi)
        if lanes % 2:
            s = cv.Point(*(
                jnp.concatenate([ts, ta[:, 2 * half :]], axis=1)
                for ts, ta in zip(s, acc)))
            lanes = half + 1
        else:
            lanes = half
        acc = s
    return cv.Point(*(t[:, 0] for t in acc))
