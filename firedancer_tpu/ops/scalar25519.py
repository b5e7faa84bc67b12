"""Batched arithmetic mod L = 2^252 + 27742...493 (the ed25519 group order).

Plays the role of fd_curve25519_scalar.c (reference:
src/ballet/ed25519/fd_curve25519_scalar.c: scalar_validate, scalar_reduce).

Reduction strategy (TPU-friendly, branch-free): with L = 2^252 + C
(C ~ 2^124.7), fold x = hi*2^252 + lo  ->  lo - C*hi using SIGNED int32
limbs (radix 2^12), which shrinks the value by ~127 bits per fold; three
folds take a 512-bit digest below 2^252 + 2^135, then add 2L and
conditionally subtract L.  Signed carry passes use arithmetic shifts
(x >> 12) and masks (x & 0xFFF), both exact for two's-complement int32.
"""

import jax.numpy as jnp
import numpy as np

B = 12
MASK = (1 << B) - 1
L = 2**252 + 27742317777372353535851937790883648493
C = L - 2**252  # 27742...493, 125 bits -> 11 limbs

_I32 = jnp.int32
_C_NLIMB = 11

_C_LIMBS = np.array([(C >> (B * i)) & MASK for i in range(_C_NLIMB)], dtype=np.int64)
assert sum(int(c) << (B * i) for i, c in enumerate(_C_LIMBS)) == C
_L_LIMBS = np.array([(L >> (B * i)) & MASK for i in range(22)], dtype=np.int64)
_L2_LIMBS = np.array([(2 * L >> (B * i)) & MASK for i in range(22)], dtype=np.int64)


def bytes_to_limbs(b, nlimb: int):
    """uint8 (..., nbytes) -> int32 limbs (nlimb, ...), little-endian."""
    x = b.astype(_I32)
    nbytes = b.shape[-1]
    ngroups = (nlimb + 1) // 2
    need = 3 * ngroups + 1
    xs = [x[..., i] for i in range(nbytes)] + [
        jnp.zeros_like(x[..., 0]) for _ in range(max(0, need - nbytes))
    ]
    limbs = []
    for t in range(ngroups):
        limbs.append(xs[3 * t] | ((xs[3 * t + 1] & 0xF) << 8))
        limbs.append((xs[3 * t + 1] >> 4) | (xs[3 * t + 2] << 4))
    return jnp.stack(limbs[:nlimb], axis=0)


def _carry_signed(x, passes: int):
    """Parallel signed carry passes on (n, ...) int32 limbs; the caller must
    provide zero-padded headroom limbs at the top so no carry is dropped."""
    for _ in range(passes):
        lo = x & MASK
        hi = jnp.right_shift(x, B)  # arithmetic shift on int32
        x = lo + jnp.concatenate([jnp.zeros_like(hi[:1]), hi[:-1]], axis=0)
    return x


def _fold_once(x):
    """x (n>=22 limbs, signed) -> lo(21) - C*hi, with 2 headroom limbs.

    Row-list accumulation (no .at[].add): the scatter-add lowering both
    bloats eager dispatch and has crashed this jaxlib's CPU compiler;
    plain per-row adds sidestep the primitive entirely."""
    n = x.shape[0]
    m = n - 21
    out_len = max(21, m + _C_NLIMB) + 2
    z = jnp.zeros_like(x[0])
    rows = [x[i] if i < 21 else z for i in range(out_len)]
    for i in range(_C_NLIMB):
        c = jnp.int32(int(_C_LIMBS[i]))
        for j in range(m):
            rows[i + j] = rows[i + j] - c * x[21 + j]
    return jnp.stack(rows, axis=0)


def reduce_512(digest_bytes):
    """SHA-512 digest (interpreted little-endian) mod L.

    digest_bytes: uint8 (..., 64) -> int32 limbs (22, ...) canonical in [0, L).
    (ref fd_curve25519_scalar_reduce)"""
    x = bytes_to_limbs(digest_bytes, 44)  # 528 bits, top limbs zero
    # three folds: 516 -> ~390 -> ~263 -> 252+eps bits (each shrinks ~127)
    for _ in range(3):
        x = _fold_once(x)
        x = _carry_signed(x, 2)
    # make positive: add 2L (value > -2^181), then canonical subtract
    l2 = jnp.asarray(_L2_LIMBS.astype(np.int32)).reshape((22,) + (1,) * (x.ndim - 1))
    x = jnp.concatenate([x[:22] + l2, x[22:]], axis=0)
    x = _carry_signed(x, 3)
    return _cond_sub_l(x, times=4)


def _cond_sub_l(x, times: int):
    """Repeated conditional subtract of L.  x: (n>=22, ...) signed limbs of a
    nonneg value < 2^264; returns canonical-carry (22, ...) limbs."""
    n = x.shape[0]
    # serial-exact carry so limbs are canonical 12-bit (top limbs drain to 0)
    rows = [x[i] for i in range(n)]
    for i in range(n - 1):
        rows[i + 1] = rows[i + 1] + jnp.right_shift(rows[i], B)
        rows[i] = rows[i] & MASK
    x = jnp.stack(rows[:22], axis=0)
    for _ in range(times):
        rows = [x[i] for i in range(22)]
        borrow = jnp.zeros_like(rows[0])
        diff = []
        for i in range(22):
            t = rows[i] + jnp.int32(1 << B) - jnp.int32(int(_L_LIMBS[i])) - borrow
            diff.append(t & MASK)
            borrow = 1 - jnp.right_shift(t, B)
        ge = borrow == 0
        x = jnp.stack([jnp.where(ge, d, r) for d, r in zip(diff, rows)], axis=0)
    return x


def is_canonical(scalar_bytes):
    """Batch check s < L (ref fd_curve25519_scalar_validate).
    scalar_bytes: uint8 (..., 32) -> bool (...,)."""
    x = bytes_to_limbs(scalar_bytes, 22)
    borrow = jnp.zeros_like(x[0])
    for i in range(22):
        t = x[i] + jnp.int32(1 << B) - jnp.int32(int(_L_LIMBS[i])) - borrow
        borrow = 1 - jnp.right_shift(t, B)
    return borrow == 1  # final borrow -> s < L


def limbs_to_windows(limbs):
    """(22, ...) 12-bit limbs -> (64, ...) 4-bit windows (3 nibbles/limb)."""
    out = []
    for j in range(64):
        out.append((limbs[j // 3] >> (4 * (j % 3))) & 0xF)
    return jnp.stack(out, axis=0).astype(jnp.uint32)


def to_int(limbs) -> int:
    """Host helper: single (22,) limb vector -> python int."""
    return sum(int(v) << (B * i) for i, v in enumerate(np.asarray(limbs))) % L
