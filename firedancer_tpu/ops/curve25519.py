"""Batched ed25519 curve arithmetic (twisted Edwards, a=-1) on TPU.

Points are NamedTuples of four (22, *batch) limb planes in extended
homogeneous coordinates (X:Y:Z:T), x=X/Z, y=Y/Z, T=XY/Z — the same
representation as the reference's fd_ed25519_point_t (reference:
src/ballet/ed25519/ref/fd_curve25519.h), batched across the trailing axes.

The scalar multiply is NOT a port of the reference's wNAF loop
(src/ballet/ed25519/ref/fd_curve25519.c:123-160): signed digits would need
per-element branches.  Instead we use fixed 4-bit windows with table
selection via one-hot masked accumulation — constant control flow, identical
work for every batch element, which is exactly what the VPU wants (and is
constant-time as a side effect, like the reference's _const_time variants).
"""

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from . import f25519 as fe

P = fe.P
D = (-121665 * pow(121666, P - 2, P)) % P
D2 = (2 * D) % P
SQRT_M1 = fe.SQRT_M1

# order-8 subgroup y coordinates (ref fd_curve25519.h:82-113 small-order table)
_ORDER8_Y0 = int.from_bytes(
    bytes.fromhex("26e8958fc2b227b045c3f489f2ef98f0d5dfac05d3c63339b13802886d53fc05"),
    "little",
) & ((1 << 255) - 1)
_ORDER8_Y1 = int.from_bytes(
    bytes.fromhex("c7176a703d4dd84fba3c0b760d10670f2a2053fa2c39ccc64ec7fd7792ac037a"),
    "little",
) & ((1 << 255) - 1)


class Point(NamedTuple):
    """Extended (X:Y:Z:T) point; each field is a (22, *batch) limb plane."""

    X: jnp.ndarray
    Y: jnp.ndarray
    Z: jnp.ndarray
    T: jnp.ndarray


def identity(batch_shape) -> Point:
    return Point(
        fe.zeros(batch_shape), fe.ones(batch_shape), fe.ones(batch_shape), fe.zeros(batch_shape)
    )


def _identity_like(ref) -> Point:
    """Identity point whose limbs inherit `ref`'s varying manual-mesh axes
    (loop carries must match the loop body's vma under shard_map; a purely
    constant identity carry trips jax's check against varying inputs)."""
    vz = (ref[0] * 0).astype(jnp.uint32)
    return Point(*(f + vz for f in identity(ref.shape[1:])))


def point_const(x: int, y: int, ndim: int) -> Point:
    return Point(
        fe.const(x, ndim), fe.const(y, ndim), fe.const(1, ndim), fe.const(x * y % P, ndim)
    )


# base point
_BASE_Y = 4 * pow(5, P - 2, P) % P
_u, _v = (_BASE_Y * _BASE_Y - 1) % P, (D * _BASE_Y * _BASE_Y + 1) % P
_BASE_X = (_u * pow(_v, 3, P) % P) * pow(_u * pow(_v, 7, P) % P, (P - 5) // 8, P) % P
if (_v * _BASE_X * _BASE_X - _u) % P != 0:
    _BASE_X = _BASE_X * SQRT_M1 % P
if _BASE_X & 1:
    _BASE_X = (-_BASE_X) % P
BASE_X, BASE_Y = _BASE_X, _BASE_Y


def add(p: Point, q: Point) -> Point:
    """Unified addition (add-2008-hwcd-3 for a=-1); complete on the curve,
    identity-safe — the property that makes a branch-free batch loop legal."""
    A = fe.mul(fe.sub(p.Y, p.X), fe.sub(q.Y, q.X))
    Bv = fe.mul(fe.add(p.Y, p.X), fe.add(q.Y, q.X))
    C = fe.mul(fe.mul(p.T, q.T), fe.const(D2, p.T.ndim))
    ZZ = fe.mul(p.Z, q.Z)
    Dv = fe.add(ZZ, ZZ)
    E = fe.sub(Bv, A)
    F = fe.sub(Dv, C)
    G = fe.add(Dv, C)
    H = fe.add(Bv, A)
    return Point(fe.mul(E, F), fe.mul(G, H), fe.mul(F, G), fe.mul(E, H))


def double(p: Point) -> Point:
    """Dedicated doubling (dbl-2008-hwcd, 4M+4S) — the hot op: the 256
    doublings dominate the double-scalar multiply."""
    XX = fe.sqr(p.X)
    YY = fe.sqr(p.Y)
    ZZ2 = fe.add(fe.sqr(p.Z), fe.sqr(p.Z))
    XpY2 = fe.sqr(fe.add_nr(p.X, p.Y))
    Yp = fe.add(YY, XX)       # Y² + X²
    Ym = fe.sub(YY, XX)       # Y² - X²
    Ec = fe.sub(XpY2, Yp)     # 2XY
    Tc = fe.sub(ZZ2, Ym)
    return Point(fe.mul(Ec, Tc), fe.mul(Yp, Ym), fe.mul(Ym, Tc), fe.mul(Ec, Yp))


def neg(p: Point) -> Point:
    return Point(fe.neg(p.X), p.Y, p.Z, fe.neg(p.T))


class Niels(NamedTuple):
    """Precomputed-point form (Y-X, Y+X, Z, 2dT): the reference's
    fd_ed25519_point precomputed tables play the same game (ref
    avx512/fd_r43x6_ge.c precomputation; dalek's ProjectiveNielsPoint).
    Folding the (Y±X) sums and the 2d·T constant multiply into the table
    turns the 9-mul unified add into an 8-mul add (7 when Z==1)."""

    Ym: jnp.ndarray
    Yp: jnp.ndarray
    Z: jnp.ndarray
    T2d: jnp.ndarray


def to_niels(p: Point) -> Niels:
    return Niels(fe.sub(p.Y, p.X), fe.add(p.Y, p.X), p.Z,
                 fe.mul(p.T, fe.const(D2, p.T.ndim)))


def add_niels(p: Point, q: Niels) -> Point:
    """p + q with q in precomputed form: 8 field muls."""
    A = fe.mul(fe.sub(p.Y, p.X), q.Ym)
    Bv = fe.mul(fe.add(p.Y, p.X), q.Yp)
    C = fe.mul(p.T, q.T2d)
    ZZ = fe.mul(p.Z, q.Z)
    Dv = fe.add(ZZ, ZZ)
    E = fe.sub(Bv, A)
    F = fe.sub(Dv, C)
    G = fe.add(Dv, C)
    H = fe.add(Bv, A)
    return Point(fe.mul(E, F), fe.mul(G, H), fe.mul(F, G), fe.mul(E, H))


def add_affine_niels(p: Point, ym, yp, t2d) -> Point:
    """p + q with q affine (Z==1) precomputed: 7 field muls."""
    A = fe.mul(fe.sub(p.Y, p.X), ym)
    Bv = fe.mul(fe.add(p.Y, p.X), yp)
    C = fe.mul(p.T, t2d)
    Dv = fe.add(p.Z, p.Z)
    E = fe.sub(Bv, A)
    F = fe.sub(Dv, C)
    G = fe.add(Dv, C)
    H = fe.add(Bv, A)
    return Point(fe.mul(E, F), fe.mul(G, H), fe.mul(F, G), fe.mul(E, H))


def select(mask, p: Point, q: Point) -> Point:
    """Per-batch-element select: mask ? p : q  (mask: bool (*batch,))."""
    return Point(*(jnp.where(mask, a, b) for a, b in zip(p, q)))


def eq(p: Point, q: Point):
    """Projective equality: X1·Z2 == X2·Z1 and Y1·Z2 == Y2·Z1."""
    return fe.eq(fe.mul(p.X, q.Z), fe.mul(q.X, p.Z)) & fe.eq(
        fe.mul(p.Y, q.Z), fe.mul(q.Y, p.Z)
    )


def eq_z1(p: Point, q: Point):
    """Equality against an affine (Z==1) point, saving two muls
    (ref fd_ed25519_point_eq_z1)."""
    return fe.eq(p.X, fe.mul(q.X, p.Z)) & fe.eq(p.Y, fe.mul(q.Y, p.Z))


def is_small_order_affine(p: Point):
    """Order <= 8 test for affine (Z==1) points: X==0 or Y==0 or Y is an
    order-8 y (ref fd_ed25519_affine_is_small_order, fd_curve25519.h:82-113)."""
    yc = fe.canonical(p.Y)
    y0 = fe.const(_ORDER8_Y0, p.Y.ndim)
    y1 = fe.const(_ORDER8_Y1, p.Y.ndim)
    return (
        fe.is_zero(p.X)
        | jnp.all(yc == 0, axis=0)
        | jnp.all(yc == y0, axis=0)
        | jnp.all(yc == y1, axis=0)
    )


def decompress(b):
    """Batch point decompression.  b: uint8 (*batch, 32).

    Returns (ok, Point) — semantics of fd_ed25519_point_frombytes
    (src/ballet/ed25519/fd_curve25519.c:26-63): non-canonical y accepted,
    x==0-with-sign-set accepted (rejected later as small order).  For ok=False
    lanes the point limbs are unspecified but arithmetic-safe."""
    y = fe.from_bytes(b)
    sign = (b[..., 31] >> 7).astype(jnp.uint32)
    yy = fe.sqr(y)
    u = fe.sub(yy, fe.ones(yy.shape[1:]))
    v = fe.add(fe.mul(yy, fe.const(D, yy.ndim)), fe.ones(yy.shape[1:]))
    ok, x = fe.sqrt_ratio(u, v)
    flip = fe.sgn(x) != sign
    x = jnp.where(flip, fe.neg(x), x)
    t = fe.mul(x, y)
    one = fe.ones(y.shape[1:])
    return ok, Point(x, y, one, t)


def compress(p: Point):
    """Serialize to 32 bytes (*batch, 32); costs one field inversion
    (ref fd_ed25519_point_tobytes)."""
    zi = fe.inv(p.Z)
    x = fe.mul(p.X, zi)
    y = fe.mul(p.Y, zi)
    by = fe.to_bytes(y)
    sign = (fe.sgn(x) << 7).astype(jnp.uint8)
    return by.at[..., 31].add(sign)


# ------------------------------------------------------- scalar multiplication


def _table_select_var(tables, idx):
    """Select tables[idx[b]] per batch element via a 4-level binary
    where-tree over the index bits: 15 selects per plane vs the one-hot
    masked accumulate's 16 mul + 15 add (gathers would scalarize on TPU;
    selects are lane-regular single-op)."""
    n = tables[0].shape[0]
    assert n == 16
    cls = type(tables)
    bits = [((idx >> k) & 1).astype(bool) for k in range(4)]

    def sel(t):
        cur = [t[i] for i in range(n)]
        for k in range(4):
            m = bits[k]
            cur = [jnp.where(m, cur[2 * i + 1], cur[2 * i])
                   for i in range(len(cur) // 2)]
        return cur[0]

    return cls(*(sel(t) for t in tables))


import functools


@functools.partial(jax.jit, static_argnames=("n",))
def _build_var_table(p: Point, n: int = 16) -> Point:
    """[0]P, [1]P, ..., [n-1]P with a leading table axis.

    Built under lax.scan so the add traces ONCE: unrolled, the 14 chained
    adds alone put ~45k multiplies in the graph and dominated the XLA
    path's trace/compile/load time (measured 20.8 MB StableHLO for a
    1-lane verify; scan brings it to a fraction).  The jit wrapper is
    load-bearing: inlined under an outer jit, but EAGER callers (tests,
    host tools) compile the whole build as one cached graph — this
    jaxlib's CPU backend segfaults compiling the scan primitive
    per-op in eager dispatch."""
    def step(carry, _):
        return add(carry, p), carry
    _, tab = jax.lax.scan(step, _identity_like(p.X), None, length=n)
    return tab


@functools.partial(jax.jit, static_argnames=("n",))
def _build_var_niels_table(p: Point, n: int = 16) -> Niels:
    """Precomputed window table in Niels form: each of the 64 window adds
    then saves one mul.  Scanned + jitted — see _build_var_table."""
    def step(carry, _):
        return add(carry, p), to_niels(carry)
    _, ne = jax.lax.scan(step, _identity_like(p.X), None, length=n)
    return ne


def _base_window_tables(num_windows: int = 64, width_bits: int = 4):
    """Precomputed python-int tables T[w][i] = [i * 16^w]B for the fixed-base
    comb: eliminates doublings for the base-point half of the double-scalar
    multiply.  Returns numpy arrays (num_windows, 16, 22) per coordinate."""
    # python-int affine arithmetic (host-side, runs once at import)
    def padd(a, b):
        x1, y1, z1, t1 = a
        x2, y2, z2, t2 = b
        A = (y1 - x1) * (y2 - x2) % P
        Bv = (y1 + x1) * (y2 + x2) % P
        C = 2 * t1 * t2 * D % P
        Dv = 2 * z1 * z2 % P
        E, F, G, H = (Bv - A) % P, (Dv - C) % P, (Dv + C) % P, (Bv + A) % P
        return (E * F % P, G * H % P, F * G % P, E * H % P)

    def paff(a):
        x, y, z, t = a
        zi = pow(z, P - 2, P)
        return (x * zi % P, y * zi % P, 1, x * zi * y * zi % P)

    nent = 1 << width_bits
    base = (BASE_X, BASE_Y, 1, BASE_X * BASE_Y % P)
    # affine-niels entries (y-x, y+x, 2dxy): each comb add is then 7 muls
    tabs = {f: np.zeros((num_windows, nent, fe.NLIMB), dtype=np.uint32)
            for f in ("Ym", "Yp", "T2d")}
    cur = base
    for w in range(num_windows):
        acc = (0, 1, 1, 0)
        for i in range(nent):
            x, y, z, t = paff(acc) if i else acc
            tabs["Ym"][w, i] = fe._to_limbs_py((y - x) % P)
            tabs["Yp"][w, i] = fe._to_limbs_py((y + x) % P)
            tabs["T2d"][w, i] = fe._to_limbs_py(t * D2 % P)
            acc = padd(acc, cur)
        # advance cur by 16x: cur = [16^(w+1)]B
        for _ in range(width_bits):
            cur = padd(cur, cur)
        cur = paff(cur)
    return tabs


_BASE_TABS = _base_window_tables()


def scalar_windows(scalar_bytes):
    """Split little-endian 32-byte scalars into 64 4-bit windows.
    scalar_bytes: uint8 (*batch, 32) -> uint32 (64, *batch)."""
    x = scalar_bytes.astype(jnp.uint32)
    lo = x & 0xF
    hi = x >> 4
    inter = jnp.stack([lo, hi], axis=-1).reshape(*x.shape[:-1], 64)
    return jnp.moveaxis(inter, -1, 0)


def double_scalar_mul_base(s_windows, k_windows, a: Point) -> Point:
    """[s]B + [k]A with 4-bit windows, the analogue of
    fd_ed25519_double_scalar_mul_base (src/ballet/ed25519/fd_curve25519.c:123-160).

    The base-point half uses a fixed-base comb over affine-niels constant
    tables (7-mul adds, no doublings); the variable half uses a per-element
    16-entry niels table (8-mul adds) built with 14 adds.  Loop runs high
    window -> low with 4 doublings per window.
    """
    a_tab = _build_var_niels_table(a)

    # base comb tables as one stacked constant: (64, 16, 22) per plane
    base_tabs = {f: jnp.asarray(_BASE_TABS[f]) for f in ("Ym", "Yp", "T2d")}

    def body(i, acc: Point):
        w = 63 - i
        for _ in range(4):
            acc = double(acc)
        kw = k_windows[w]
        acc = add_niels(acc, _table_select_var(a_tab, kw))
        return acc

    acc = jax.lax.fori_loop(0, 64, body, _identity_like(a.X))

    # fixed-base comb half: sum over windows of T[w][s_w] — no doublings;
    # folded in after the variable half (order irrelevant, group is abelian).
    def comb_body(w, acc: Point):
        oh = _onehot(s_windows[w], 16)
        ym, yp, t2d = (
            jnp.tensordot(base_tabs[f][w].T, oh, axes=([1], [0]))
            .astype(jnp.uint32)
            for f in ("Ym", "Yp", "T2d")
        )
        return add_affine_niels(acc, ym, yp, t2d)

    acc2 = jax.lax.fori_loop(0, 64, comb_body, acc)
    return acc2


def _onehot(idx, n):
    return (
        jnp.arange(n, dtype=jnp.uint32).reshape((n,) + (1,) * idx.ndim) == idx
    ).astype(jnp.uint32)


def scalar_mul(s_windows, p: Point) -> Point:
    """[s]P, variable point, 4-bit windows over a niels table."""
    tab = _build_var_niels_table(p)

    def body(i, acc: Point):
        w = 63 - i
        for _ in range(4):
            acc = double(acc)
        return add_niels(acc, _table_select_var(tab, s_windows[w]))

    return jax.lax.fori_loop(0, 64, body, _identity_like(p.X))
