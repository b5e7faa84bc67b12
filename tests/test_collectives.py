"""Multi-chip collectives on the virtual 8-device CPU mesh (SURVEY.md §4.4
pattern: jax sharding semantics are identical between the CPU mesh and a
real pod slice): ring point fold over the mesh axis."""

import numpy as np
import pytest

import jax

from firedancer_tpu.ops import curve25519 as cv
from firedancer_tpu.ops import f25519 as fe
from firedancer_tpu.parallel import collectives as co
from firedancer_tpu.parallel import mesh as pm

N_DEV = 8


@pytest.fixture(scope="module")
def mesh():
    if len(jax.devices()) < N_DEV:
        pytest.skip(f"need {N_DEV} devices")
    return pm.make_mesh(N_DEV)


def _host_point(p, i=None):
    """Device Point -> python affine pair for comparison."""
    X = fe.to_int(np.asarray(p.X) if i is None else np.asarray(p.X)[:, i])
    Y = fe.to_int(np.asarray(p.Y) if i is None else np.asarray(p.Y)[:, i])
    Z = fe.to_int(np.asarray(p.Z) if i is None else np.asarray(p.Z)[:, i])
    zi = pow(Z, fe.P - 2, fe.P)
    return (X * zi % fe.P, Y * zi % fe.P)


def test_ring_point_fold(mesh):
    # 8 partial points: [i+1]B on device i; ring-fold must give [36]B
    from firedancer_tpu.ops.ed25519 import (
        _compress_host,
        _scalar_mul_base_host,
    )

    parts = []
    for i in range(N_DEV):
        x, y, z, t = _scalar_mul_base_host(i + 1)
        zi = pow(z, fe.P - 2, fe.P)
        parts.append((x * zi % fe.P, y * zi % fe.P))
    stack = {
        "X": np.stack([np.asarray(fe.const(x)).reshape(fe.NLIMB)
                       for x, _ in parts]),
        "Y": np.stack([np.asarray(fe.const(y)).reshape(fe.NLIMB)
                       for _, y in parts]),
    }
    ones = np.stack([np.asarray(fe.const(1)).reshape(fe.NLIMB)] * N_DEV)
    ts = np.stack([np.asarray(fe.const(x * y % fe.P)).reshape(fe.NLIMB)
                   for x, y in parts])
    fold = co.ring_point_fold(mesh)
    X, Y, Z, T = fold(stack["X"], stack["Y"], ones, ts)
    total = _scalar_mul_base_host(sum(range(1, N_DEV + 1)))  # [36]B
    zi = pow(total[2], fe.P - 2, fe.P)
    want = (total[0] * zi % fe.P, total[1] * zi % fe.P)
    for i in range(N_DEV):  # replicated on every device
        got = _host_point(
            cv.Point(X[i], Y[i], Z[i], T[i]))
        assert got == want
