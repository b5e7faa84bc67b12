"""Curve ops vs the python golden model (extended-coordinate big-int math)."""

import secrets

import jax.numpy as jnp
import numpy as np

import tests.golden.ed25519_golden as g
from firedancer_tpu.ops import curve25519 as cv
from firedancer_tpu.ops import f25519 as fe

P = fe.P
BATCH = 8


def rand_points(n):
    """n random curve points (as golden-model tuples) via [r]B."""
    return [g.pt_mul(secrets.randbits(256), g.BASE) for _ in range(n)]


def pack_points(pts):
    """golden tuples -> batched Point"""
    arrs = {f: [] for f in "XYZT"}
    for X, Y, Z, T in pts:
        arrs["X"].append(fe._to_limbs_py(X))
        arrs["Y"].append(fe._to_limbs_py(Y))
        arrs["Z"].append(fe._to_limbs_py(Z))
        arrs["T"].append(fe._to_limbs_py(T))
    return cv.Point(*(jnp.asarray(np.stack(arrs[f], axis=1)) for f in "XYZT"))


def unpack_points(p: cv.Point):
    n = p.X.shape[1]
    out = []
    for i in range(n):
        out.append(tuple(fe.to_int(np.asarray(getattr(p, f)[:, i])) for f in "XYZT"))
    return out


def assert_points_equal(dev_pts, gold_pts):
    for i, (d, q) in enumerate(zip(dev_pts, gold_pts)):
        assert g.pt_eq(d, q), f"point {i} mismatch"


def test_base_point_matches_golden():
    assert (cv.BASE_X, cv.BASE_Y) == (g.BASE[0], g.BASE[1])


def test_add():
    ps, qs = rand_points(BATCH), rand_points(BATCH)
    got = unpack_points(cv.add(pack_points(ps), pack_points(qs)))
    assert_points_equal(got, [g.pt_add(p, q) for p, q in zip(ps, qs)])


def test_add_identity():
    ps = rand_points(BATCH)
    got = unpack_points(cv.add(pack_points(ps), cv.identity((BATCH,))))
    assert_points_equal(got, ps)


def test_double():
    ps = rand_points(BATCH)
    got = unpack_points(cv.double(pack_points(ps)))
    assert_points_equal(got, [g.pt_double(p) for p in ps])


def test_double_identity():
    got = unpack_points(cv.double(cv.identity((2,))))
    assert_points_equal(got, [g.IDENT, g.IDENT])


def test_neg():
    ps = rand_points(BATCH)
    got = unpack_points(cv.neg(pack_points(ps)))
    assert_points_equal(got, [g.pt_neg(p) for p in ps])


def test_eq_and_eq_z1():
    ps = rand_points(4)
    qs = [ps[0], g.pt_double(ps[1]), ps[2], ps[3]]
    m = cv.eq(pack_points(ps), pack_points(qs))
    assert list(np.asarray(m)) == [True, False, True, True]
    # eq_z1 with affine rhs (all golden points from pt_mul have Z=1? no — use
    # compressed/decompressed to force Z=1)
    affine = [g.pt_decompress(g.pt_compress(p)) for p in ps]
    m2 = cv.eq_z1(pack_points(qs), pack_points(affine))
    assert list(np.asarray(m2)) == [True, False, True, True]


def test_decompress():
    ps = rand_points(BATCH)
    raw = [g.pt_compress(p) for p in ps]
    arr = jnp.asarray(np.frombuffer(b"".join(raw), dtype=np.uint8).reshape(BATCH, 32))
    ok, pts = cv.decompress(arr)
    assert all(np.asarray(ok))
    assert_points_equal(unpack_points(pts), ps)


def test_decompress_invalid():
    # y with no valid x: find one by brute force over small ints
    bad = None
    for y in range(2, 200):
        u, v = (y * y - 1) % P, (g.D * y * y + 1) % P
        if not g.sqrt_ratio(u, v)[0]:
            bad = y
            break
    assert bad is not None
    raw = bad.to_bytes(32, "little")
    arr = jnp.asarray(np.frombuffer(raw, dtype=np.uint8).reshape(1, 32))
    ok, _ = cv.decompress(arr)
    assert not bool(np.asarray(ok)[0])


def test_decompress_noncanonical_accepted():
    # y = p+1 encodes 1 non-canonically; must decompress like y=1 (dalek 2.x)
    raw = (P + 1).to_bytes(32, "little")
    arr = jnp.asarray(np.frombuffer(raw, dtype=np.uint8).reshape(1, 32))
    ok, pts = cv.decompress(arr)
    assert bool(np.asarray(ok)[0])
    assert fe.to_int(np.asarray(pts.Y[:, 0])) == 1


def test_compress_roundtrip():
    ps = rand_points(BATCH)
    dev = pack_points(ps)
    raw = np.asarray(cv.compress(dev))
    for i, p in enumerate(ps):
        assert raw[i].tobytes() == g.pt_compress(p)


def test_small_order_detection():
    # all 8 low-order encodings from the reference table (fd_curve25519.h:84-92)
    enc = [
        "0100000000000000000000000000000000000000000000000000000000000000",
        "ecffffffffffffffffffffffffffffffffffffffffffffffffffffffffffff7f",
        "0000000000000000000000000000000000000000000000000000000000000000",
        "0000000000000000000000000000000000000000000000000000000000000080",
        "26e8958fc2b227b045c3f489f2ef98f0d5dfac05d3c63339b13802886d53fc05",
        "26e8958fc2b227b045c3f489f2ef98f0d5dfac05d3c63339b13802886d53fc85",
        "c7176a703d4dd84fba3c0b760d10670f2a2053fa2c39ccc64ec7fd7792ac037a",
        "c7176a703d4dd84fba3c0b760d10670f2a2053fa2c39ccc64ec7fd7792ac03fa",
    ]
    raw = b"".join(bytes.fromhex(e) for e in enc)
    arr = jnp.asarray(np.frombuffer(raw, dtype=np.uint8).reshape(8, 32))
    ok, pts = cv.decompress(arr)
    assert all(np.asarray(ok))
    assert list(np.asarray(cv.is_small_order_affine(pts))) == [True] * 8
    # and regular points are NOT small order
    ps = rand_points(4)
    assert list(np.asarray(cv.is_small_order_affine(pack_points(ps)))) == [False] * 4


def windows_of(s: int):
    b = jnp.asarray(
        np.frombuffer(s.to_bytes(32, "little"), dtype=np.uint8).reshape(1, 32)
    )
    return cv.scalar_windows(b)


def test_scalar_mul_var():
    p = rand_points(1)[0]
    for s in [0, 1, 7, secrets.randbits(252)]:
        w = windows_of(s)
        got = unpack_points(cv.scalar_mul(w, pack_points([p])))[0]
        assert g.pt_eq(got, g.pt_mul(s, p)), s


def test_double_scalar_mul_base():
    batch = 4
    ss = [secrets.randbits(252) for _ in range(batch)]
    ks = [secrets.randbits(252) for _ in range(batch)]
    pts = rand_points(batch)
    sb = jnp.asarray(
        np.frombuffer(
            b"".join(s.to_bytes(32, "little") for s in ss), dtype=np.uint8
        ).reshape(batch, 32)
    )
    kb = jnp.asarray(
        np.frombuffer(
            b"".join(k.to_bytes(32, "little") for k in ks), dtype=np.uint8
        ).reshape(batch, 32)
    )
    got = unpack_points(
        cv.double_scalar_mul_base(cv.scalar_windows(sb), cv.scalar_windows(kb), pack_points(pts))
    )
    want = [
        g.pt_add(g.pt_mul(s, g.BASE), g.pt_mul(k, p)) for s, k, p in zip(ss, ks, pts)
    ]
    assert_points_equal(got, want)
