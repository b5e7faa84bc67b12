"""Pallas TPU kernel for the ed25519 verify hot loop.

Why this exists: the XLA-compiled double-scalar-multiply is bounded by
HBM round-trips between fusion islands — measured 21.7 ns/double/lane vs
1-5 ns for the same arithmetic inside one Pallas kernel whose limb planes
stay resident in VMEM (v5e; docs/perf_ceiling.md).

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
    (docs/perf_ceiling.md)."""
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
    schedules best of the measured ladder variants (docs/perf_ceiling.md).

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
    muls; measured ~27%% off the chain, docs/perf_ceiling.md)."""
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


# ------------------------------------------------------- signed windows
# 4-bit digits recoded to [-8, 8]: the variable table shrinks to
# [0..8]A (7 builder adds instead of 14), selects go 15-where -> 8-where
# + a cheap conditional negate, and kernel VMEM falls ~40% (larger blk
# headroom).  Negation of a Niels entry is (Ym,Yp) swap + T2d negate.


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


# ----------------------------------------------------- sqrt_ratio, canon


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


# ------------------------------------------------- scalar reduce/recode


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
    recode ripples a carry low->high, value-preserving (sum
    mag*(-1)^sgn * 16^w == the scalar); the top window of an L-reduced
    scalar is <= 1 so it never overflows."""
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


# --------------------------------------------------- fused verify tail
# ONE kernel does A-decompress, scalar reduce/recode and the dsm tail, so
# A's planes and both scalars' windows never leave VMEM between stages
# (three separate kernels paid (22, batch) x4 + (64, batch) x4 HBM
# round-trips between them, plus a separate negate pass over A).


def _fused_tail_kernel(blk: int):
    """pubkey y/sign + s bytes + SHA digest + R's y -> one combined ok bit
    (A decompresses & not small-order & s canonical & projective y match)
    plus Q's X/Z planes for the XLA-side x-parity tail.

    Windows stage through VMEM scratch refs because the dsm chain's
    window loop indexes a Ref via pl.ds (dynamic sublane slices of
    in-register arrays don't lower)."""

    def kernel(ay_ref, asg_ref, sb_ref, db_ref, yr_ref,
               ok_ref, xo_ref, zo_ref,
               sm_ref, ss_ref, km_ref, ks_ref):
        bias = fe._limb_const(fe._BIAS_PY, 2)
        one = _ones_k(blk)

        # ---- A decompress + small-order test (fd_ed25519_point_frombytes
        # + affine_is_small_order semantics, as cv.decompress and
        # cv.is_small_order_affine)
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
        # for T)
        neg_x = _wr(bias - x, passes=1)
        neg_a = _Pt(neg_x, y, one, _mulw(neg_x, y))

        # ---- s canonicity + signed windows for BOTH scalars (is_canonical
        # and reduce_512 of scalar25519 as row ops), staged into the
        # scratch refs
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
