"""Pallas fused verify tail: geometry-level unit checks (CPU) + parity.

The (22, blk) sublane-geometry field arithmetic the kernel is built from
— _mulw/_sqrw/_wr/_reduce44 are plain jnp and run anywhere — is checked
against python-int ground truth, including the magnitude edge cases the
in-kernel lazy-add discipline relies on; the whole fused kernel runs in
interpret mode against the XLA path's acceptance bits.
"""

import numpy as np
import jax.numpy as jnp
import pytest

from firedancer_tpu.ops import curve25519 as cv
from firedancer_tpu.ops import curve_pallas as cp
from firedancer_tpu.ops import f25519 as fe


def _to_limbs(vals):
    return jnp.asarray(
        np.stack([fe._to_limbs_py(v) for v in vals], axis=1))


def _from_limbs(arr):
    a = np.asarray(arr)
    return [fe._from_limbs_py(a[:, i]) % fe.P for i in range(a.shape[1])]


@pytest.fixture
def vals():
    rng = np.random.default_rng(7)
    out = [int.from_bytes(rng.bytes(32), "little") % fe.P for _ in range(8)]
    # edge values: 0, 1, p-1, 2^255-20 (max canonical), high-limb-heavy
    out[:4] = [0, 1, fe.P - 1, 2**255 - 20]
    return out


def test_mulw_matches_int(vals):
    a = _to_limbs(vals)
    b = _to_limbs(list(reversed(vals)))
    got = _from_limbs(cp._mulw(a, b))
    want = [(x * y) % fe.P for x, y in zip(vals, reversed(vals))]
    assert got == want


def test_sqrw_matches_int(vals):
    a = _to_limbs(vals)
    got = _from_limbs(cp._sqrw(a))
    assert got == [x * x % fe.P for x in vals]


def test_mulw_lazy_inputs_exact(vals):
    """One unreduced add on each operand (the kernel's lazy-add pattern)
    must stay uint32-exact through the MAC ladder."""
    a = _to_limbs(vals)
    b = _to_limbs(list(reversed(vals)))
    got = _from_limbs(cp._mulw(a + a, b + b))
    want = [(4 * x * y) % fe.P for x, y in zip(vals, reversed(vals))]
    assert got == want


def test_doublew_matches_host(vals):
    from firedancer_tpu.ops import ed25519 as ed

    pts = [ed._scalar_mul_base_host(3 * i + 1) for i in range(4)]
    aff = []
    for p in pts:
        zi = pow(p[2], fe.P - 2, fe.P)
        aff.append((p[0] * zi % fe.P, p[1] * zi % fe.P))
    P4 = cp._Pt(
        _to_limbs([a[0] for a in aff]), _to_limbs([a[1] for a in aff]),
        _to_limbs([1] * 4), _to_limbs([a[0] * a[1] % fe.P for a in aff]))
    bias = fe._limb_const(fe._BIAS_PY, 2)
    got = cp._doublew(P4, bias)
    gz = _from_limbs(got.Z)
    gx = [x * pow(z, fe.P - 2, fe.P) % fe.P
          for x, z in zip(_from_limbs(got.X), gz)]
    gy = [y * pow(z, fe.P - 2, fe.P) % fe.P
          for y, z in zip(_from_limbs(got.Y), gz)]
    for i, p in enumerate(pts):
        d = ed._pt_add_host(p, p)
        zi = pow(d[2], fe.P - 2, fe.P)
        assert gx[i] == d[0] * zi % fe.P
        assert gy[i] == d[1] * zi % fe.P


def test_fused_tail_matches_xla_acceptance():
    """The fused kernel (decompress+recode+dsm+y-compare in one
    pallas_call, interpret mode) must reproduce the XLA path's per-lane
    acceptance bits across adversarial lanes: tampered sig, non-canonical
    S, undecompressable A, small-order A."""
    from firedancer_tpu.models.verifier import make_example_batch
    from firedancer_tpu.ops import ed25519 as ed
    from firedancer_tpu.ops import scalar25519 as sc
    from firedancer_tpu.ops import sha512 as sh

    B = 8
    msgs, lens, sigs, pubs = make_example_batch(B, 64, True, sign_pool=8)
    sigs = np.asarray(sigs).copy()
    pubs = np.asarray(pubs).copy()
    sigs[1, 5] ^= 0xFF                       # tampered R
    sigs[2, 32:] = 0xFF                      # non-canonical S (>= L)
    pubs[3] = np.frombuffer(bytes([0x07] * 32), np.uint8)   # no sqrt
    pubs[4] = np.frombuffer(bytes(31) + bytes([0x80]), np.uint8)  # y=0+sign
    pubs[5] = np.frombuffer(bytes([1]) + bytes(31), np.uint8)  # identity
    sigs, pubs = jnp.asarray(sigs), jnp.asarray(pubs)
    r_bytes, s_bytes = sigs[:, :32], sigs[:, 32:]

    pre = jnp.concatenate([r_bytes, pubs, msgs], axis=1)
    digest = sh.sha512(pre, lens + 64)
    parsed_r = ed._parse_r_bytes(r_bytes)
    ok_k, qx, qz = cp.verify_tail_fused(
        pubs, s_bytes, digest, parsed_r[0], blk=B, interpret=True)
    got = np.asarray(ed._compressed_r_check(
        qx, None, qz, r_bytes, ok_y=ok_k, parsed_r=parsed_r))

    # XLA reference path (exact verify_batch semantics)
    ok_a, a_pt = cv.decompress(pubs)
    ok_a = ok_a & ~cv.is_small_order_affine(a_pt)
    ok_s = sc.is_canonical(s_bytes)
    q = cv.double_scalar_mul_base(
        cv.scalar_windows(s_bytes),
        sc.limbs_to_windows(sc.reduce_512(digest)), cv.neg(a_pt))
    want = np.asarray(
        ok_s & ok_a & ed._compressed_r_check(q.X, q.Y, q.Z, r_bytes))
    assert got.tolist() == want.tolist()
    assert want.tolist() == [True, False, False, False, False, False,
                             True, True]
