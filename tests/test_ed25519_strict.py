"""The strict ed25519 verify graph, one row per rule it enforces.

verify_blob (the packed-row graph the verify tile dispatches) is compiled
ONCE for the module at one small shape; every row's device bit must equal
ops.ed25519.verify_one_host's (the degraded-mode host twin) and the
verdict the reference's rules give (fd_ed25519_user.c:135-311: S < L, A
and R not of small order, cofactorless equation).  The torsion rows are
forgeries whose verification defect [S]B - [k]A - R is a small-torsion
point: a cofactored or halved check would accept some of them, strict
rejects every one.
"""

import functools
import hashlib

import jax
import numpy as np
import pytest

from firedancer_tpu.ops import ed25519 as ed

P = ed.P
L = ed.L
ML = 64
MSG = bytes(range(32))

# identity encoding: y = 1, x-sign 0 — decompresses to the neutral
# element, which is small-order
_ID_ENC = bytes([1] + [0] * 31)

# order-2 and order-4 torsion points in extended coords (X, Y, Z, T)
_T2 = (0, P - 1, 1, 0)
_T4 = (pow(2, (P - 1) // 4, P), 0, 1, 0)   # x = sqrt(-1), y = 0


def _forge_with_torsion(seed: bytes, msg: bytes, t_pt):
    """Build (sig, pub) whose verification defect [S]B - [k]A - R is
    exactly -T:  R = [r]B + T with honest S = r + k*a.  Strict verify
    must reject (T != identity)."""
    pub, a, _ = ed.keypair_from_seed(seed)
    r = int.from_bytes(hashlib.sha512(b"forge" + seed + msg).digest(),
                       "little") % L
    r_pt = ed._pt_add_host(ed._scalar_mul_base_host(r), t_pt)
    rb = ed._compress_host(r_pt)
    k = int.from_bytes(hashlib.sha512(rb + pub + msg).digest(),
                       "little") % L
    s = (r + k * a) % L
    return rb + s.to_bytes(32, "little"), pub


def _honest(tag: int):
    seed = bytes([tag]) * 32
    pub, _, _ = ed.keypair_from_seed(seed)
    return bytearray(ed.sign(seed, MSG)), pub


def _tampered_r():
    sig, pub = _honest(3)
    sig[5] ^= 0xFF
    return bytes(sig), pub


def _tampered_s():
    sig, pub = _honest(4)
    sig[32] ^= 0x01
    return bytes(sig), pub


def _noncanonical_s():
    # S + L satisfies the equation mod L but is not the canonical scalar
    sig, pub = _honest(5)
    s = int.from_bytes(sig[32:], "little") + L
    return bytes(sig[:32]) + s.to_bytes(32, "little"), pub


def _small_order_a():
    # A = identity: [S]B == R + [k]A holds for S = r, R = [r]B, so only
    # the small-order rule rejects it
    r = 0x1234567 % L
    rb = ed._compress_host(ed._scalar_mul_base_host(r))
    return rb + r.to_bytes(32, "little"), _ID_ENC


# (name, builder, expected verdict)
ROWS = [
    ("honest", lambda: tuple(map(bytes, _honest(1))), True),
    ("torsion_order2",
     lambda: _forge_with_torsion(bytes([9]) * 32, MSG, _T2), False),
    ("torsion_order4",
     lambda: _forge_with_torsion(bytes([10]) * 32, MSG, _T4), False),
    ("tampered_r", _tampered_r, False),
    ("tampered_s", _tampered_s, False),
    ("noncanonical_s", _noncanonical_s, False),
    ("small_order_a", _small_order_a, False),
]


@pytest.fixture(scope="module")
def device_bits():
    """(sig, pub) per row and the device verdicts of the whole batch, from
    one verify_blob compile.  One zero padding row makes the batch 8."""
    assert ed._pt_add_host(_T2, _T2)[0] % P == 0          # order 2
    d4 = ed._pt_add_host(_T4, _T4)
    assert d4[0] % P == 0 and (d4[1] + d4[2]) % P == 0    # [2]T4 = T2
    cases = [build() for _, build, _ in ROWS]
    blob = np.zeros((len(cases) + 1, ML + ed.PACKED_EXTRA), np.uint8)
    for i, (sig, pub) in enumerate(cases):
        blob[i, :len(MSG)] = np.frombuffer(MSG, np.uint8)
        blob[i, ML:ML + 64] = np.frombuffer(sig, np.uint8)
        blob[i, ML + 64:ML + 96] = np.frombuffer(pub, np.uint8)
        blob[i, ML + 96:ML + 100] = np.frombuffer(
            np.int32(len(MSG)).tobytes(), np.uint8)
    fn = jax.jit(functools.partial(ed.verify_blob, maxlen=ML))
    bits = np.asarray(fn(blob))
    assert not bits[-1]                       # padding row
    return cases, bits


@pytest.mark.parametrize("row", range(len(ROWS)),
                         ids=[name for name, _, _ in ROWS])
def test_strict_verdict(device_bits, row):
    cases, bits = device_bits
    sig, pub = cases[row]
    want = ROWS[row][2]
    assert ed.verify_one_host(sig, MSG, pub) == want
    assert bool(bits[row]) == want
