"""The dp-mesh serving path (round 7) on the virtual 8-device CPU mesh:
sharded packed dispatch, the masked-padding contract for uneven batches,
and the sharded PackedIngest rotation — every case bit-checked against
the single-chip engine (verify is lane-parallel, so real lanes must
match EXACTLY; "close" is wrong)."""

import numpy as np
import pytest

import jax

from firedancer_tpu.models.verifier import (
    SigVerifier,
    VerifierConfig,
    make_example_batch,
)
from firedancer_tpu.parallel import mesh as pm

N_DEV = 8
B, ML = 64, 96


@pytest.fixture(scope="module")
def mesh():
    if len(jax.devices()) < N_DEV:
        pytest.skip(f"need {N_DEV} devices")
    return pm.make_mesh(N_DEV)


@pytest.fixture(scope="module")
def batch():
    """Mixed-verdict batch: valid sigs with lanes 3, 17, 40 tampered."""
    msgs, lens, sigs, pubs = make_example_batch(B, ML, True, seed=7)
    sigs = np.array(sigs)
    for i in (3, 17, 40):
        sigs[i, 5] ^= 0xFF
    return msgs, lens, sigs, pubs


@pytest.fixture(scope="module")
def single():
    return SigVerifier(VerifierConfig(batch=B, msg_maxlen=ML))


@pytest.fixture(scope="module")
def sharded(mesh):
    return SigVerifier(VerifierConfig(batch=B, msg_maxlen=ML), mesh=mesh)


def test_packed_dispatch_bit_identity(single, sharded, batch):
    ref = np.asarray(single.packed_dispatch(*batch))
    got = np.asarray(sharded.packed_dispatch(*batch))
    assert got.shape == (B,)
    assert (ref == got).all()
    assert not got[3] and not got[17] and not got[40]
    assert got.sum() == B - 3


def test_uneven_batch_pads_and_masks(single, sharded, batch):
    # 36 rows pad to 40 on the 8-mesh; the 4 padding lanes are masked
    # False on device and trimmed from the verdict
    msgs, lens, sigs, pubs = batch
    n = 36
    ref = np.asarray(single._fn(msgs[:n], lens[:n], sigs[:n], pubs[:n]))
    got = np.asarray(sharded.packed_dispatch(
        msgs[:n], lens[:n], sigs[:n], pubs[:n]))
    assert got.shape == (n,)
    assert (ref == got).all()


def test_strict_four_array_mesh(single, sharded, batch):
    ref = np.asarray(single(*batch))
    got = np.asarray(sharded(*batch))
    assert (ref == got).all()


def test_sharded_ingest_rotation(mesh, batch):
    """The multichip fresh-ingest engine: 5 rotations through 3 buffers
    with a different tampered lane per rotation — verdict streams must
    match the single-chip engine batch for batch (the no-torn-buffer
    invariant holding per shard)."""
    msgs, lens, sigs, pubs = batch
    eng = SigVerifier(VerifierConfig(batch=B, msg_maxlen=ML),
                      mesh=mesh).make_ingest(nbuf=3)
    ref = SigVerifier(VerifierConfig(batch=B, msg_maxlen=ML)).make_ingest(
        nbuf=3)
    outs, routs = [], []
    for r in range(5):
        s2 = np.array(sigs)
        s2[(r * 7) % B, 9] ^= 0x55
        outs += eng.submit(msgs, lens, s2, pubs)
        routs += ref.submit(msgs, lens, s2, pubs)
    outs += eng.drain()
    routs += ref.drain()
    assert len(outs) == 5
    for o, r in zip(outs, routs):
        assert o.shape == (B,)
        assert (o == r).all()
    assert eng.dispatches == 5
    assert eng.pack_txns == 5 * B


def test_pad_rows():
    a = np.arange(36 * 4, dtype=np.uint8).reshape(36, 4)
    p = pm.pad_rows(a, 8)
    assert p.shape == (40, 4)
    assert (p[:36] == a).all() and not p[36:].any()
    assert pm.pad_rows(a, 4) is a  # already divisible: no copy


def test_pipeline_dp_shards_validation(mesh):
    from firedancer_tpu.disco.pipeline import VerifyPipeline

    sv = SigVerifier(VerifierConfig(batch=B, msg_maxlen=ML), mesh=mesh)

    def fake(m, l, s, p):
        return np.ones((np.asarray(m).shape[0],), bool)

    # bucket batch not divisible by the mesh
    with pytest.raises(ValueError, match="not divisible"):
        VerifyPipeline(sv, buckets=[(36, ML)], dp_shards=N_DEV)
    # verifier shard count disagrees with the topology's dp_shards
    with pytest.raises(ValueError, match="shards"):
        VerifyPipeline(sv, buckets=[(B, ML)], dp_shards=4)
    # a shardless verify_fn is accepted (n_shards defaults to dp_shards)
    VerifyPipeline(fake, buckets=[(B, ML)], dp_shards=N_DEV)


def test_mesh_requires_dp_axis():
    from jax.sharding import Mesh
    devs = jax.devices()
    if len(devs) < 2:
        pytest.skip("need 2 devices")
    m = Mesh(np.array(devs[:2]), ("tp",))
    with pytest.raises(ValueError, match="dp"):
        SigVerifier(VerifierConfig(batch=B, msg_maxlen=ML), mesh=m)
