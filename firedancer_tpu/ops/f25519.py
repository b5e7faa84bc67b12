"""GF(2^255-19) field arithmetic, batched, TPU-first.

Design
------
A field element is a planar array of NLIMB=22 radix-2^12 limbs, dtype uint32,
shape ``(22, *batch)`` — the limb axis FIRST so the (large) batch axis maps to
the TPU's 128-wide lane dimension and every op below is a pure elementwise /
shifted-add vector op over the batch.

This plays the role of the reference's field element types: the portable
10x25.5-bit fd_f25519 (reference: src/ballet/ed25519/ref/fd_f25519.h) and the
AVX-512 radix-2^43x6 fd_r43x6 (src/ballet/ed25519/avx512/fd_r43x6.h:8-56).
The radix is chosen by the same range-analysis discipline that file documents,
redone for TPU uint32 vector lanes:

  * products of two 12(+lazy)-bit limbs fit in uint32
  * a 43-column schoolbook product column accumulates <= 22 terms:
    22 * (2^13.2)^2 < 2^32, so whole-product accumulation stays exact in
    uint32 with one level of lazy ("_nr") addition allowed on mul inputs
  * carry propagation is done with PARALLEL shifted-add passes (2-3 passes)
    instead of a serial 22-step chain — a carry-save normalization that keeps
    the VPU busy across the whole (22, B) tile

Magnitude invariants (audited in tests/test_f25519.py):

  NORMAL   limbs <= ~4106, top limb <= ~31; value < 2^255 + eps.
           Produced by every reducing op (add/sub/mul/sqr/neg/weak_reduce).
  LAZY     one add_nr of two NORMALs: limbs <= ~8212.  Valid mul/sqr input
           (the Karatsuba middle product stays uint32-exact up to here —
           see _conv); add_nr MUST NOT be nested twice before a mul.

Functions are shape-polymorphic over trailing batch dims and jit-safe.
"""

import jax
import jax.numpy as jnp
import numpy as np

B = 12                     # bits per limb
NLIMB = 22                 # 22 * 12 = 264 bits
MASK = (1 << B) - 1
P = 2**255 - 19
FOLD264 = 19 * 512         # 2^264 mod p  (2^264 = 2^9 * 2^255 ≡ 19 * 2^9)

_U32 = jnp.uint32


def _to_limbs_py(v: int) -> np.ndarray:
    assert 0 <= v < 1 << (B * NLIMB)
    return np.array([(v >> (B * i)) & MASK for i in range(NLIMB)], dtype=np.uint32)


def _from_limbs_py(l) -> int:
    return sum(int(x) << (B * i) for i, x in enumerate(np.asarray(l, dtype=np.uint64)))


# Subtraction bias: limbs of 4*p rebalanced (each limb 0..20 borrows 3 units
# from the limb above) so that every limb exceeds any LAZY subtrahend limb.
# bias ≡ 0 (mod p), so a + bias - b ≡ a - b with no per-limb underflow.
_w = _to_limbs_py(4 * P).astype(np.int64)
_BIAS_PY = np.concatenate([_w[:1] + 3 * 4096, _w[1:21] + 3 * 4096 - 3, _w[21:] - 3])
assert _from_limbs_py(_BIAS_PY) == 4 * P
assert _BIAS_PY[:21].min() >= 12288 and _BIAS_PY[21] >= 28
_BIAS_PY = _BIAS_PY.astype(np.uint32)


def _limb_const(limbs, ndim: int) -> jnp.ndarray:
    """(22, 1, ...) constant built from per-limb SCALAR literals — the
    Pallas-kernel-safe constructor: scalars are legal jaxpr literals inside
    kernels, while captured array constants are rejected by Mosaic.  ONLY
    for kernel bodies: in plain XLA graphs the 22 stacked broadcasts bloat
    the program (measured: multi-minute CPU compiles) — use const()/
    _bias() there, which emit one array constant."""
    one = (1,) * (ndim - 1)
    # int32, not uint32: TPU VPU int32 multiply measured 22% faster than
    # uint32 (v5e: 83.8 vs 102.6 ns/MAC/block) and every
    # kernel intermediate fits 2^31 (max accumulation 1.56e9) — the whole
    # Pallas field layer runs int32 (round 5)
    return jnp.stack(
        [jnp.full(one, int(v), dtype=jnp.int32) for v in limbs], axis=0)


def const(v: int, ndim: int = 1) -> jnp.ndarray:
    """Field constant as (22, 1, 1, ...) broadcastable against ndim-dim limbs."""
    c = _to_limbs_py(v % P)
    return jnp.asarray(c.reshape((NLIMB,) + (1,) * (ndim - 1)), dtype=_U32)


def _bias(ndim: int) -> jnp.ndarray:
    return jnp.asarray(
        _BIAS_PY.reshape((NLIMB,) + (1,) * (ndim - 1)), dtype=_U32)


def zeros(batch_shape) -> jnp.ndarray:
    return jnp.zeros((NLIMB, *batch_shape), dtype=_U32)


def ones(batch_shape) -> jnp.ndarray:
    return jnp.zeros((NLIMB, *batch_shape), dtype=_U32).at[0].set(1)


# ------------------------------------------------------------------ carries


def _shift_up(x):
    """Shift limbs one position up (toward higher significance); drop top."""
    return jnp.concatenate([jnp.zeros_like(x[:1]), x[:-1]], axis=0)


def weak_reduce(x, passes: int = 2):
    """Carry-normalize a (22, ...) accumulator to NORMAL form.

    Parallel shifted-add passes; the carry out of limb 21 wraps to limb 0
    with weight 2^264 mod p, then bits >= 255 are folded (*19) and a final
    single-limb mini-pass bounds limb 0.  `passes` must be sized to the input
    magnitude: 2 suffices for limbs < 2^20, 3 for limbs < 2^27.

    Built with concatenation (never .at[] scatter) so the same code lowers
    both through XLA and inside Pallas TPU kernels.
    """
    for _ in range(passes):
        lo = x & MASK
        hi = x >> B
        x = jnp.concatenate(
            [(lo[0] + hi[NLIMB - 1] * FOLD264)[None], lo[1:] + hi[:-1]],
            axis=0,
        )
    # fold bits >= 255 (limb 21 holds bits 252..263; keep its low 3 bits)
    t = x[NLIMB - 1] >> 3
    x0 = x[0] + t * 19
    c0 = x0 >> B
    return jnp.concatenate(
        [
            (x0 & MASK)[None],
            (x[1] + c0)[None],
            x[2 : NLIMB - 1],
            (x[NLIMB - 1] & 7)[None],
        ],
        axis=0,
    )


# ------------------------------------------------------------------ add/sub


def add_nr(a, b):
    """Lazy add, no carry ("nr" naming from ref fd_f25519_add_nr).

    Output is LAZY: valid as a mul/sqr input but must not be nested."""
    return a + b


def add(a, b):
    return weak_reduce(a + b, passes=1)


def sub(a, b):
    """a - b via the 4p bias; inputs may be LAZY."""
    return weak_reduce(a + _bias(a.ndim) - b, passes=1)


def neg(a):
    return weak_reduce(_bias(a.ndim) - a, passes=1)


# ------------------------------------------------------------------ mul


def _conv_rows(ar, br):
    """Schoolbook convolution of two equal-length row lists -> column list
    (len 2n-1).  Emitted as explicit per-column sums (producer/consumer
    chains XLA fuses into one kernel) rather than a chain of
    dynamic-update-slice accumulations."""
    n = len(ar)
    cols = []
    for k in range(2 * n - 1):
        lo = max(0, k - n + 1)
        hi = min(k, n - 1)
        c = ar[lo] * br[k - lo]
        for i in range(lo + 1, hi + 1):
            c = c + ar[i] * br[k - i]
        cols.append(c)
    return cols


def _conv(a, b):
    """22x22 limb convolution -> (44, ...) columns via one Karatsuba split:
    3 x (11x11) sub-convolutions = 363 lane-muls vs schoolbook's 484.

    Exactness (worst case LAZY inputs, limbs <= ~8212 after one add_nr):
      * p0/p1 columns <= 11 * 8212^2           = 7.42e8 < 2^30
      * (a0+a1) limbs <= 16424, so m columns   <= 11 * 16424^2
                                               = 2.97e9 < 2^32
      * mid = m - p0 - p1 is >= 0 per column (all product terms are
        non-negative and m's column set is a superset), so u32-exact
      * combined columns equal the schoolbook columns exactly,
        <= 22 * 8212^2 = 1.48e9 < 2^32       -- u32-exact
    A second nested add_nr (limbs ~16k) would push m past 2^32 — hence
    the module invariant that add_nr is never nested before a mul."""
    ar = [a[i] for i in range(NLIMB)]
    br = [b[i] for i in range(NLIMB)]
    h = NLIMB // 2
    p0 = _conv_rows(ar[:h], br[:h])                      # 21 cols
    p1 = _conv_rows(ar[h:], br[h:])
    sa = [x + y for x, y in zip(ar[:h], ar[h:])]
    sb = [x + y for x, y in zip(br[:h], br[h:])]
    m = _conv_rows(sa, sb)
    mid = [mm - x - y for mm, x, y in zip(m, p0, p1)]
    zero = jnp.zeros_like(p0[0])
    cols = []
    for k in range(2 * NLIMB - 1):
        c = p0[k] if k < 2 * h - 1 else None
        if h <= k < h + 2 * h - 1:
            t = mid[k - h]
            c = t if c is None else c + t
        if 2 * h <= k:
            t = p1[k - 2 * h] if k - 2 * h < 2 * h - 1 else None
            if t is not None:
                c = t if c is None else c + t
        cols.append(zero if c is None else c)
    cols.append(zero)  # column 43 is structurally zero
    return jnp.stack(cols, axis=0)


def _reduce_wide(c):
    """Reduce a (44, ...) column accumulator to NORMAL (22, ...) form."""
    # two in-array carry passes (no wrap: limb 43 has headroom by construction)
    for _ in range(2):
        lo = c & MASK
        hi = c >> B
        c = jnp.concatenate([lo[:1], lo[1:] + hi[:-1]], axis=0)
    # fold limbs 22..43 into 0..21: 2^(12(22+i)) ≡ FOLD264 * 2^(12 i)
    r = c[:NLIMB] + c[NLIMB:] * FOLD264
    return weak_reduce(r, passes=3)


def mul(a, b):
    return _reduce_wide(_conv(a, b))


def _conv_sqr_rows(ar):
    """Squaring convolution over a row list: c_k = 2·Σ_{i<k-i} a_i a_{k-i}
    (+ a_{k/2}² for even k) — ~half the limb products of the general conv
    (the classic squaring shortcut; ref fd_f25519_sqr does the same in its
    backends)."""
    n = len(ar)
    cols = []
    for k in range(2 * n - 1):
        lo = max(0, k - n + 1)
        terms = []
        i = lo
        while i < k - i:
            terms.append(ar[i] * ar[k - i])
            i += 1
        c = None
        if terms:
            c = terms[0]
            for t in terms[1:]:
                c = c + t
            c = c + c  # cross terms count twice
        if k % 2 == 0:
            sq = ar[k // 2] * ar[k // 2]
            c = sq if c is None else c + sq
        cols.append(c)
    return cols


def _conv_sqr(a):
    """Karatsuba squaring: 3 x 11-limb squaring sub-convs (~198 lane-muls
    vs 286 schoolbook-squared, 484 general).  mid = (a0+a1)^2 - a0^2 - a1^2
    = 2·a0·a1 >= 0 per column; magnitude analysis as in _conv (LAZY-safe)."""
    ar = [a[i] for i in range(NLIMB)]
    h = NLIMB // 2
    p0 = _conv_sqr_rows(ar[:h])
    p1 = _conv_sqr_rows(ar[h:])
    m = _conv_sqr_rows([x + y for x, y in zip(ar[:h], ar[h:])])
    mid = [mm - x - y for mm, x, y in zip(m, p0, p1)]
    zero = jnp.zeros_like(p0[0])
    cols = []
    for k in range(2 * NLIMB - 1):
        c = p0[k] if k < 2 * h - 1 else None
        if h <= k < h + 2 * h - 1:
            t = mid[k - h]
            c = t if c is None else c + t
        if 2 * h <= k and k - 2 * h < 2 * h - 1:
            t = p1[k - 2 * h]
            c = t if c is None else c + t
        cols.append(zero if c is None else c)
    cols.append(zero)
    return jnp.stack(cols, axis=0)


def sqr(a):
    return _reduce_wide(_conv_sqr(a))


def mul_small(a, c: int):
    """Multiply by a small python constant (c < 2^15)."""
    assert 0 < c < 1 << 15
    return weak_reduce(a * jnp.uint32(c), passes=3)


def mul_const(a, v: int):
    """Multiply by a field constant given as a python int."""
    return mul(a, const(v, a.ndim))


# ------------------------------------------------------------------ canonical


def canonical(x):
    """Fully reduce to the canonical representative in [0, p)."""
    for _ in range(2):
        # serial exact carry
        rows = [x[i] for i in range(NLIMB)]
        for i in range(NLIMB - 1):
            rows[i + 1] = rows[i + 1] + (rows[i] >> B)
            rows[i] = rows[i] & MASK
        # fold bits >= 255
        t = rows[NLIMB - 1] >> 3
        rows[NLIMB - 1] = rows[NLIMB - 1] & 7
        rows[0] = rows[0] + t * 19
        x = jnp.stack(rows, axis=0)
    # conditional subtract p (value < p + 2^12 here, so once is enough; do twice
    # for margin)
    p_limbs = _to_limbs_py(P)
    for _ in range(2):
        rows = [x[i] for i in range(NLIMB)]
        borrow = jnp.zeros_like(rows[0])
        diff = []
        for i in range(NLIMB):
            t = rows[i] + jnp.uint32(1 << B) - jnp.uint32(int(p_limbs[i])) - borrow
            diff.append(t & MASK)
            borrow = 1 - (t >> B)
        ge = borrow == 0  # no final borrow -> x >= p
        x = jnp.stack(
            [jnp.where(ge, d, r) for d, r in zip(diff, rows)], axis=0
        )
    return x


def eq(a, b):
    """Batch equality -> bool (*batch)."""
    return jnp.all(canonical(a) == canonical(b), axis=0)


def is_zero(a):
    return jnp.all(canonical(a) == 0, axis=0)


def sgn(a):
    """Low bit of the canonical representative (ref fd_f25519_sgn)."""
    return canonical(a)[0] & 1


# ------------------------------------------------------------------ pow


import functools as _functools


@_functools.partial(jax.jit, static_argnames=("e",))
def pow_const(a, e: int):
    """a^e for a fixed public exponent: 4-bit fixed windows over a
    16-entry power table.

    Per window the loop pays 4 sqr + 1 mul + a (16, 22)-row table select —
    versus bitwise square-and-multiply's 4 sqr + 4 mul + 4 full-width
    selects; for the 252-bit sqrt/inversion exponents that trades ~175
    field muls per chain for 14 table-build muls.  (The reference uses
    unrolled addition chains, ref/fd_f25519.c pow22523; on TPU the compact
    constant-trip loop compiles fast and keeps the graph small.)"""
    assert e > 0
    digits = []
    v = e
    while v:
        digits.append(v & 0xF)
        v >>= 4
    digits = digits[::-1]  # MSB window first; leading window is nonzero
    ndig = len(digits)
    dig_arr = jnp.asarray(np.array(digits, dtype=np.uint32))

    # table[i] = a^i for i in 0..15 (a^0 = 1), built under lax.scan so the
    # mul traces once (unrolled, 14 muls add ~20k ops to every pow chain's
    # graph — trace/compile/load time, see _build_var_table's note).
    # The carry derives from `a` (zeros_like, not ones()) so it inherits
    # a's varying mesh axes and the scan stays legal under shard_map.
    def _tab_step(carry, _):
        return mul(carry, a), carry
    one = jnp.zeros_like(a).at[0].set(1)
    _, tab = jax.lax.scan(_tab_step, one, None, length=16)

    def _sel(idx):
        # (16, 1, <1 per batch dim>) against tab (16, 22, *batch)
        onehot = (
            jnp.arange(16, dtype=_U32).reshape((16,) + (1,) * a.ndim) == idx
        ).astype(_U32)
        return jnp.sum(tab * onehot, axis=0).astype(_U32)

    def body(i, r):
        for _ in range(4):
            r = sqr(r)
        return mul(r, _sel(dig_arr[i]))

    r = _sel(dig_arr[0])
    return jax.lax.fori_loop(1, ndig, body, r)


def inv(a):
    return pow_const(a, P - 2)


SQRT_M1 = pow(2, (P - 1) // 4, P)


def sqrt_ratio(u, v):
    """(ok, x) with x = sqrt(u/v) when u/v is square (RFC 8032 5.1.3 recipe;
    ref fd_f25519_sqrt_ratio under src/ballet/ed25519).  For non-square
    ratios ok=False and x is unspecified (callers must mask)."""
    v2 = sqr(v)
    v3 = mul(v2, v)
    v7 = mul(mul(v2, v2), v3)
    t = pow_const(mul(u, v7), (P - 5) // 8)
    x = mul(mul(u, v3), t)
    vxx = mul(sqr(x), v)
    good = eq(vxx, u)
    flipped = eq(vxx, neg(u))
    x_alt = mul(x, const(SQRT_M1, x.ndim))
    x = jnp.where(flipped, x_alt, x)
    return good | flipped, x


# ------------------------------------------------------------------ ser/de


def from_bytes(b):
    """Little-endian 32 bytes -> limbs.  b: uint8 (..., 32) -> (22, ...).

    Bit 255 (the point-compression sign bit) is masked off; values >= p are
    NOT rejected (non-canonical encodings are accepted, matching
    fd_f25519_frombytes / dalek 2.x semantics)."""
    x = b.astype(_U32)
    top = x[..., 31] & 0x7F
    xs = [x[..., i] for i in range(31)] + [top, jnp.zeros_like(top)]  # 33 bytes
    limbs = []
    for t in range(11):
        limbs.append(xs[3 * t] | ((xs[3 * t + 1] & 0xF) << 8))
        limbs.append((xs[3 * t + 1] >> 4) | (xs[3 * t + 2] << 4))
    return jnp.stack(limbs, axis=0)


def to_bytes(a):
    """Canonical little-endian serialization -> uint8 (..., 32)."""
    l = canonical(a)
    bs = []
    for t in range(11):
        e, o = l[2 * t], l[2 * t + 1]
        bs.append(e & 0xFF)
        bs.append((e >> 8) | ((o & 0xF) << 4))
        bs.append(o >> 4)
    return jnp.stack(bs[:32], axis=-1).astype(jnp.uint8)


# ------------------------------------------------------------------ helpers


def max_limb(a) -> int:
    """Debug/audit helper: the largest limb magnitude (host int)."""
    return int(jnp.max(a))


def to_int(a) -> int:
    """Host-side: convert a single (22,) element to a python int."""
    return _from_limbs_py(np.asarray(a)) % P


def batch_inv(a, stop: int = 128):
    """Montgomery batch inversion over the batch axis, tree-shaped for
    SIMD: pair-products up (whole-array muls on halving sizes), ONE
    pow-chain inversion at the stop width, pair-unwinds down.  Total
    field-mul work ~= 3 muls per lane + one 250-sqr chain amortized over
    the whole batch — versus one chain per lane.

    stop: tree leaf width for the pow chain.  Do NOT reduce to 1: the
    chain's ~250 serial muls vectorize across `stop` lanes, and running
    them on a (22, 1) array measured ~30 ms of pure small-op overhead at
    32k (the r4 regression that made compressed-R verify slower than the
    decompress it replaced).

    a: (22, n) limbs, all nonzero (callers guard zero lanes and mask
    their results).  Returns (22, n) with out[i] = a[i]^-1."""
    n = a.shape[-1]
    if n <= stop:
        return inv(a)
    levels = []
    cur = a
    while cur.shape[-1] > stop:
        if cur.shape[-1] % 2:
            # pad with 1 (inv(1) = 1) BEFORE storing: every stored level
            # is even and its parent is exactly half its width
            pad = jnp.zeros_like(cur[:, :1]).at[0].set(1)
            cur = jnp.concatenate([cur, pad], axis=-1)
        levels.append(cur)
        cur = mul(cur[:, 0::2], cur[:, 1::2])
    down = inv(cur)
    # unwind: parent p = l*r  =>  inv(l) = inv(p)*r, inv(r) = inv(p)*l.
    # A padded parent level carries one extra inverse (of the pad) —
    # truncate down to this level's true pair count first.
    for lvl in levels[::-1]:
        left, right = lvl[:, 0::2], lvl[:, 1::2]
        down = down[:, : lvl.shape[-1] // 2]
        inv_left = mul(down, right)
        inv_right = mul(down, left)
        down = jnp.stack([inv_left, inv_right], axis=-1).reshape(
            lvl.shape[0], lvl.shape[-1])
    return down[:, :n]
