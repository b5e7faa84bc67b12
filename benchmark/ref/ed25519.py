"""Plain ed25519 (RFC 8032) in Python integers and hashlib.

This is the benchmark's reference verifier and the signer its traffic
generator uses.  It shares no code with the program under test.

Acceptance rules (`verify`), the strict rules the configurations state:

  1. S is canonical: S < L, else reject.
  2. A and R decode as RFC 8032 section 5.1.3 says (y >= p fails).
  3. A or R of small order (8P is the identity): reject.
  4. k = SHA-512(R || A || M) mod L.
  5. Accept iff [S]B - [k]A encodes to the bytes of R (no cofactor).

`verify(..., canonical_s=False)` drops rule 1 and reduces S mod L: the
lax verifier the control puts in the program's place.
"""

import hashlib

P = 2**255 - 19
L = 2**252 + 27742317777372353535851937790883648493
D = (-121665 * pow(121666, P - 2, P)) % P
D2 = 2 * D % P
SQRT_M1 = pow(2, (P - 1) // 4, P)

# extended coordinates (X, Y, Z, T) with x = X/Z, y = Y/Z, xy = T/Z
IDENTITY = (0, 1, 1, 0)


def _recover_x(y: int, sign: int) -> int | None:
    if y >= P:
        return None
    x2 = (y * y - 1) * pow(D * y * y + 1, P - 2, P) % P
    if x2 == 0:
        return None if sign else 0
    x = pow(x2, (P + 3) // 8, P)
    if (x * x - x2) % P:
        x = x * SQRT_M1 % P
    if (x * x - x2) % P:
        return None
    if x & 1 != sign:
        x = P - x
    return x


_BY = 4 * pow(5, P - 2, P) % P
_BX = _recover_x(_BY, 0)
B = (_BX, _BY, 1, _BX * _BY % P)


def add(p, q):
    """Point addition (add-2008-hwcd-3, a = -1)."""
    x1, y1, z1, t1 = p
    x2, y2, z2, t2 = q
    a = (y1 - x1) * (y2 - x2) % P
    b = (y1 + x1) * (y2 + x2) % P
    c = t1 * D2 * t2 % P
    d = 2 * z1 * z2 % P
    e, f, g, h = b - a, d - c, d + c, b + a
    return (e * f % P, g * h % P, f * g % P, e * h % P)


def mul(s: int, p):
    q = IDENTITY
    while s:
        if s & 1:
            q = add(q, p)
        p = add(p, p)
        s >>= 1
    return q


def same(p, q) -> bool:
    return ((p[0] * q[2] - q[0] * p[2]) % P == 0
            and (p[1] * q[2] - q[1] * p[2]) % P == 0)


def compress(p) -> bytes:
    zi = pow(p[2], P - 2, P)
    x, y = p[0] * zi % P, p[1] * zi % P
    return (y | (x & 1) << 255).to_bytes(32, "little")


def decompress(b: bytes):
    if len(b) != 32:
        return None
    v = int.from_bytes(b, "little")
    y, sign = v & ((1 << 255) - 1), v >> 255
    x = _recover_x(y, sign)
    if x is None:
        return None
    return (x, y, 1, x * y % P)


def small_order(p) -> bool:
    return same(mul(8, p), IDENTITY)


def challenge(r_enc: bytes, a_enc: bytes, msg: bytes) -> int:
    return int.from_bytes(hashlib.sha512(r_enc + a_enc + msg).digest(),
                          "little") % L


def verify(pub: bytes, msg: bytes, sig: bytes,
           canonical_s: bool = True) -> bool:
    if len(sig) != 64 or len(pub) != 32:
        return False
    r_enc, s = sig[:32], int.from_bytes(sig[32:], "little")
    if s >= L:
        if canonical_s:
            return False
        s %= L
    a = decompress(pub)
    r = decompress(r_enc)
    if a is None or r is None or small_order(a) or small_order(r):
        return False
    k = challenge(r_enc, pub, msg)
    neg_a = ((P - a[0]) % P, a[1], a[2], (P - a[3]) % P)
    return compress(add(mul(s, B), mul(k, neg_a))) == r_enc


def secret_scalar(seed: bytes) -> tuple[int, bytes]:
    h = hashlib.sha512(seed).digest()
    a = int.from_bytes(h[:32], "little")
    a &= (1 << 254) - 8
    a |= 1 << 254
    return a, h[32:]


def public_key(seed: bytes) -> bytes:
    return compress(mul(secret_scalar(seed)[0], B))


def sign(seed: bytes, msg: bytes) -> bytes:
    """RFC 8032 deterministic signing (the test vectors' path)."""
    a, prefix = secret_scalar(seed)
    pub = compress(mul(a, B))
    r = int.from_bytes(hashlib.sha512(prefix + msg).digest(), "little") % L
    r_enc = compress(mul(r, B))
    s = (r + challenge(r_enc, pub, msg) * a) % L
    return r_enc + s.to_bytes(32, "little")


def chain_encodings(start, step, n: int) -> list[bytes]:
    """Encodings of start + i*step for i < n: one point addition each and
    one field inversion for the whole run (Montgomery's batch trick).
    With start = r0*B and step = B these are the nonce points of scalars
    r0, r0 + 1, ...; signatures made from them are valid RFC 8032
    signatures that a verifier cannot tell from deterministic ones."""
    pts = []
    p = start
    for _ in range(n):
        pts.append(p)
        p = add(p, step)
    acc = [1] * (n + 1)
    for i, q in enumerate(pts):
        acc[i + 1] = acc[i] * q[2] % P
    inv = pow(acc[n], P - 2, P)
    out = [b""] * n
    for i in range(n - 1, -1, -1):
        q = pts[i]
        zi = inv * acc[i] % P
        inv = inv * q[2] % P
        x, y = q[0] * zi % P, q[1] * zi % P
        out[i] = (y | (x & 1) << 255).to_bytes(32, "little")
    return out
