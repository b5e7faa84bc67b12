"""Multi-chip collectives (ref: SURVEY.md §5 "distributed communication
backend" — the reference's cross-host story is the Solana protocol
itself; ours adds the ICI tier the reference never had: XLA collectives
over a chip mesh).

ring_point_fold is an all-reduce whose element is a curve POINT and whose
op is group addition: partials rotate around the ICI ring via ppermute
while every chip accumulates, n-1 steps (the ring-collective shape
ring-attention uses, applied to EC aggregation).  It runs on any jax
mesh — the 8-virtual-CPU-device test mesh compiles the identical SPMD
program a v5e-8 slice executes over ICI.
"""

import jax
from jax.sharding import Mesh, PartitionSpec as P

from firedancer_tpu.ops import curve25519 as cv


def _ring_fold_local(p: cv.Point, axis: str, n: int) -> cv.Point:
    """All-reduce point addition inside shard_map: rotate a carry copy of
    the original partial around the ring, adding at each stop.  n is the
    static axis size (jax < 0.6 has no lax.axis_size; the mesh knows)."""
    perm = [(i, (i + 1) % n) for i in range(n)]

    def body(_, state):
        acc, carry = state
        carry = cv.Point(*(jax.lax.ppermute(t, axis, perm) for t in carry))
        return (cv.add(acc, carry), carry)

    acc, _ = jax.lax.fori_loop(0, n - 1, body, (p, p))
    return acc


def ring_point_fold(mesh: Mesh, axis: str = "dp"):
    """Jitted fn: (22,)-limbed per-device Points (stacked on a leading
    device axis, n × (22,)) -> the group sum, replicated to every device."""

    def local(X, Y, Z, T):
        p = cv.Point(X[0], Y[0], Z[0], T[0])  # this device's partial
        s = _ring_fold_local(p, axis, mesh.shape[axis])
        return tuple(t[None] for t in s)

    shard = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(axis), P(axis), P(axis), P(axis)),
        out_specs=(P(axis), P(axis), P(axis), P(axis)),
    )
    return jax.jit(shard)
