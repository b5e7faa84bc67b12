"""Measure per-op floors on the live TPU via SLOPE timing.

Single timings are poisoned by (a) the host round trip and (b)
per-loop-iteration overheads.  Every rate below
is therefore a SLOPE: run the same chained graph at two step counts and
divide the time difference by the step difference — RTT and dispatch
overheads cancel; per-iteration while-loop cost stays in (the real
workload pays it too).  Loop bodies are made fat (several ops per
iteration) so iteration overhead doesn't dominate the quantity measured.

Measurement rules per project memory: np.asarray() is the only true sync;
same process for every comparison.
"""

import time

import jax
import jax.numpy as jnp
import numpy as np

import os as _os, sys as _sys
_sys.path.insert(0, _os.path.dirname(_os.path.abspath(__file__)))
from _bench import DISPATCH, slope, timed  # noqa: E402,F401

from firedancer_tpu.ops import curve25519 as cv
from firedancer_tpu.ops import f25519 as fe

BATCH = 4096








def main():
    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.integers(0, 4096, size=(22, BATCH), dtype=np.uint32))
    b = jnp.asarray(rng.integers(0, 4096, size=(22, BATCH), dtype=np.uint32))

    # --- field ops: per-lane cost -----------------------------------
    def mk_mul(steps):
        @jax.jit
        def f(x, y):
            def body(i, x):
                return fe.mul(x, y)
            return jax.lax.fori_loop(0, steps, body, x)
        return f, (a, b)

    def mk_sqr(steps):
        @jax.jit
        def f(x):
            def body(i, x):
                return fe.sqr(x)
            return jax.lax.fori_loop(0, steps, body, x)
        return f, (a,)

    slope("field mul (22x12b limbs)", mk_mul, 2048, 6144, BATCH, "mul/lane")
    slope("field sqr", mk_sqr, 2048, 6144, BATCH, "sqr/lane")

    p = cv.Point(a, b, fe.ones((BATCH,)), fe.zeros((BATCH,)))

    def mk_dbl(steps):
        @jax.jit
        def f(pt):
            def body(i, q):
                return cv.double(q)
            return jax.lax.fori_loop(0, steps, body, pt)
        return f, (p,)

    slope("point double", mk_dbl, 512, 1536, BATCH, "dbl/lane")

    # --- raw VPU rates: fat body (32 fma per iteration) -------------
    N = 22 * BATCH
    xi = jnp.asarray(rng.integers(1, 1 << 12, size=(N,), dtype=np.uint32))
    xf = xi.astype(jnp.float32)

    def mk_i32(steps):
        @jax.jit
        def f(x):
            def body(i, x):
                for _ in range(32):
                    x = x * x + jnp.uint32(12345)
                return x
            return jax.lax.fori_loop(0, steps, body, x)
        return f, (xi,)

    def mk_f32(steps):
        @jax.jit
        def f(x):
            def body(i, x):
                for _ in range(32):
                    x = x * x + jnp.float32(1.5)
                return x
            return jax.lax.fori_loop(0, steps, body, x)
        return f, (xf,)

    slope("raw i32 fma (32/iter, 90K elems)", mk_i32, 2048, 6144, 32 * N,
          "i32-fma")
    slope("raw f32 fma", mk_f32, 2048, 6144, 32 * N, "f32-fma")

    # --- MXU rates: 8 matmuls per iteration -------------------------
    mi = jnp.asarray(rng.integers(-64, 64, size=(BATCH, 128), dtype=np.int8))
    wi = jnp.asarray(rng.integers(-64, 64, size=(128, 128), dtype=np.int8))

    def mk_mm(steps):
        @jax.jit
        def f(x, w):
            def body(i, acc):
                s = jnp.int32(0)
                for _ in range(8):
                    y = jax.lax.dot_general(
                        x, w, (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.int32)
                    s = s + jnp.sum(y)
                return acc + s
            return jax.lax.fori_loop(0, steps, body, jnp.int32(0))
        return f, (mi, wi)

    slope("int8 matmul (4096x128)@(128x128)", mk_mm, 2048, 8192,
          8 * BATCH * 128 * 128, "MAC")

    mi2 = jnp.asarray(rng.integers(-64, 64, size=(BATCH, 512), dtype=np.int8))
    wi2 = jnp.asarray(rng.integers(-64, 64, size=(512, 512), dtype=np.int8))

    def mk_mm2(steps):
        @jax.jit
        def f(x, w):
            def body(i, acc):
                s = jnp.int32(0)
                for _ in range(8):
                    y = jax.lax.dot_general(
                        x, w, (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.int32)
                    s = s + jnp.sum(y)
                return acc + s
            return jax.lax.fori_loop(0, steps, body, jnp.int32(0))
        return f, (mi2, wi2)

    slope("int8 matmul (4096x512)@(512x512)", mk_mm2, 512, 2048,
          8 * BATCH * 512 * 512, "MAC")

    # --- the VERDICT-suggested mapping: per-lane banded matvec ------
    # c[n] = M_b[n] @ a[n], batched (44x22)@(22).  Measured WITHOUT the
    # band-matrix build cost (generous); 4 matvecs per iteration.
    Mb = jnp.asarray(rng.integers(0, 1 << 12, size=(BATCH, 44, 22),
                                  dtype=np.int32))
    av = jnp.asarray(rng.integers(0, 1 << 12, size=(BATCH, 22),
                                  dtype=np.int32))

    def mk_bmv(steps):
        @jax.jit
        def f(M, v):
            def body(i, acc):
                s = jnp.int32(0)
                for _ in range(4):
                    c = jax.lax.dot_general(
                        M, v, (((2,), (1,)), ((0,), (0,))),
                        preferred_element_type=jnp.int32)
                    s = s + jnp.sum(c)
                return acc + s
            return jax.lax.fori_loop(0, steps, body, jnp.int32(0))
        return f, (Mb, av)

    slope("batched matvec (B,44,22)@(B,22) i32", mk_bmv, 512, 1536,
          4 * BATCH, "fieldmul-equiv")


if __name__ == "__main__":
    main()
