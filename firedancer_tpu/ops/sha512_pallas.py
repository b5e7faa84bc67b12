"""Pallas TPU SHA-512: fully-unrolled compression in VMEM.

The XLA path (ops/sha512.py) keeps the graph small with lax.scan — but on
device that is 160 sequential scan iterations per digest batch, and the
per-iteration launch/carry overhead dominates: measured 476 ns/lane at
batch 4096 where the raw ALU work is ~10 ns/lane.  Inside one Pallas
kernel the 80 rounds x nb blocks unroll completely (static python loop),
the schedule ring lives in vector registers, and the only HBM traffic is
the packed message words in and the digest state out.

Geometry: batch maps to (8 sublanes) x (blk lanes) — message words are
(8, blk) full tiles, so every 64-bit pair op is a dense 2-op vector op.
The 64-bit pair arithmetic helpers are reused from ops/sha512.py
(shape-polymorphic).  Reference contract: src/ballet/sha512/fd_sha512.c
(fd_sha512_core), batched like the AVX path fd_sha512_batch (widths 4/8 —
here 8 x blk).
"""

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from .sha512 import _H0, _K, _add2, _addk, _rotr, _shr, _xor3, pad_messages

SUB = 8  # batch elements per sublane group


def _compress_unrolled(state, w):
    """One unrolled SHA-512 compression.  state: list of 8 (hi, lo) pairs;
    w: list of 16 (hi, lo) pairs ((8, blk) arrays).  Returns new state."""
    w = list(w)
    for t in range(16, 80):
        w15 = w[t - 15]
        w2 = w[t - 2]
        s0 = _xor3(_rotr(w15, 1), _rotr(w15, 8), _shr(w15, 7))
        s1 = _xor3(_rotr(w2, 19), _rotr(w2, 61), _shr(w2, 6))
        w.append(_addk(w[t - 16], s0, w[t - 7], s1))

    a, b, c, d, e, f, g, h = state
    for t in range(80):
        kt = (jnp.uint32(_K[t] >> 32), jnp.uint32(_K[t] & 0xFFFFFFFF))
        S1 = _xor3(_rotr(e, 14), _rotr(e, 18), _rotr(e, 41))
        ch = ((e[0] & f[0]) ^ (~e[0] & g[0]),
              (e[1] & f[1]) ^ (~e[1] & g[1]))
        t1 = _addk(h, S1, ch, kt, w[t])
        S0 = _xor3(_rotr(a, 28), _rotr(a, 34), _rotr(a, 39))
        maj = ((a[0] & b[0]) ^ (a[0] & c[0]) ^ (b[0] & c[0]),
               (a[1] & b[1]) ^ (a[1] & c[1]) ^ (b[1] & c[1]))
        t2 = _add2(S0, maj)
        h, g, f, e, d, c, b, a = g, f, e, _add2(d, t1), c, b, a, _add2(t1, t2)
    return [_add2(s, n) for s, n in
            zip(state, (a, b, c, d, e, f, g, h))]


def _sha_kernel(nb: int, blk: int):
    """words_ref: (nb*32*SUB, blk) — per block, 16 words x (hi row group,
    lo row group) x SUB sublanes.  nbl_ref: (SUB, blk) block counts.
    out_ref: (16*SUB, blk) final state words (hi, lo interleaved)."""

    def kernel(words_ref, nbl_ref, out_ref):
        nbl = nbl_ref[...]
        state = [
            (jnp.full((SUB, blk), hv >> 32, jnp.uint32),
             jnp.full((SUB, blk), hv & 0xFFFFFFFF, jnp.uint32))
            for hv in _H0
        ]
        for bi in range(nb):
            base = bi * 32 * SUB
            w = [
                (words_ref[base + (2 * t) * SUB : base + (2 * t + 1) * SUB, :],
                 words_ref[base + (2 * t + 1) * SUB
                           : base + (2 * t + 2) * SUB, :])
                for t in range(16)
            ]
            new = _compress_unrolled(state, w)
            active = nbl > bi
            state = [
                (jnp.where(active, n[0], s[0]), jnp.where(active, n[1], s[1]))
                for s, n in zip(state, new)
            ]
        out = []
        for hi, lo in state:
            out.append(hi)
            out.append(lo)
        out_ref[...] = jnp.concatenate(out, axis=0)

    return kernel


def sha512(msgs, lengths, max_blocks: int | None = None, blk: int = 512):
    """Batched SHA-512 via the Pallas kernel.  Same contract as
    ops.sha512.sha512: msgs uint8 (batch, maxlen), lengths (batch,) ->
    digests uint8 (batch, 64).  batch must be divisible by 8*128."""
    batch, maxlen = msgs.shape
    if max_blocks is None:
        max_blocks = (maxlen + 17 + 127) // 128
    nb = max_blocks
    lanes = batch // SUB
    assert batch % (SUB * 128) == 0, batch
    while lanes % blk:          # largest power-of-two block dividing lanes
        blk //= 2
    assert blk >= 128, (batch, blk)

    padded, nblocks = pad_messages(msgs, lengths, nb)
    # big-endian byte quads -> u32 words, laid out (nb, 16 words, hi/lo,
    # SUB, lanes) then flattened to rows
    b = padded.reshape(batch, nb, 16, 2, 4).astype(jnp.uint32)
    wrds = (b[..., 0] << 24) | (b[..., 1] << 16) | (b[..., 2] << 8) | b[..., 3]
    # (batch, nb, 16, 2) -> (nb, 16, 2, batch) -> rows (nb*16*2*SUB, lanes)
    wrds = wrds.transpose(1, 2, 3, 0).reshape(nb * 32, SUB, lanes)
    wrds = wrds.reshape(nb * 32 * SUB, lanes)
    nbl = nblocks.astype(jnp.int32).reshape(SUB, lanes)

    w_spec = pl.BlockSpec((nb * 32 * SUB, blk), lambda i: (0, i))
    n_spec = pl.BlockSpec((SUB, blk), lambda i: (0, i))
    o_spec = pl.BlockSpec((16 * SUB, blk), lambda i: (0, i))
    # under jax.shard_map the output must name the mesh axes it varies
    # over, the same as its inputs'
    vma = jax.typeof(wrds).vma | jax.typeof(nbl).vma
    out = pl.pallas_call(
        _sha_kernel(nb, blk),
        out_shape=jax.ShapeDtypeStruct((16 * SUB, lanes), jnp.uint32,
                                       vma=vma),
        grid=(lanes // blk,),
        in_specs=[w_spec, n_spec],
        out_specs=o_spec,
    )(wrds, nbl)

    # rows (16 words x SUB, lanes) -> (batch, 64) big-endian bytes; batch
    # index was split sub-major (batch = sub * lanes + lane) on the way in
    words = out.reshape(16, SUB, lanes).transpose(1, 2, 0).reshape(batch, 16)
    sh = jnp.array([24, 16, 8, 0], dtype=jnp.uint32)
    by = (words[:, :, None] >> sh[None, None, :]) & 0xFF
    return by.reshape(batch, 64).astype(jnp.uint8)
