"""Double-buffered ingest engine invariants (round 6 tentpole).

Three contracts, each falsifiable on CPU:

  1. NO TORN BUFFER — a rotating packed blob is never repacked while its
     dispatch is inflight (on backends where device_put aliases host
     memory, an early repack would corrupt the batch the device is still
     reading).
  2. BACKPRESSURE — the inflight window is bounded at `depth`: submit()
     retires the oldest verdict(s) rather than running ahead, and when
     every buffer is pinned it blocks on a harvest before repacking.
  3. BIT-IDENTICAL — verdicts through the overlapped engine equal the
     serial packed_dispatch verdicts batch for batch, including across
     buffer reuse (stale-padding regression: reused blobs must be
     re-zeroed or a shorter batch would see the previous batch's bytes).

The pipeline-level pool (disco.pipeline._Bucket) carries the same
invariant: a flushed blob returns to the rotation only after _finish()
materializes its verdict.
"""

import numpy as np
import pytest

from firedancer_tpu.models.verifier import (
    SigVerifier,
    VerifierConfig,
    make_example_batch,
)

BATCH = 64
ML = 96


@pytest.fixture(scope="module")
def verifier():
    return SigVerifier(VerifierConfig(batch=BATCH, msg_maxlen=ML))


@pytest.fixture(scope="module")
def batches(verifier):
    """Three distinct batches with mixed verdicts + per-batch serial
    reference bits."""
    out = []
    for seed, valid in ((1, True), (2, False), (3, True)):
        args = [np.asarray(a) for a in make_example_batch(
            BATCH, ML, valid=valid, sign_pool=8, seed=seed)]
        if valid:  # flip a couple of sig bytes for a mixed verdict
            args[2] = args[2].copy()
            args[2][seed, 0] ^= 0xFF
        ref = np.asarray(verifier.packed_dispatch(*args, ml=ML))
        assert ref.any() != ref.all()  # genuinely mixed
        out.append((args, ref))
    return out


def test_no_repack_while_inflight(verifier, batches):
    """Contract 1: _pack_into never targets a buffer whose dispatch is
    still in the inflight window."""
    eng = verifier.make_ingest(ml=ML, nbuf=2, depth=1)
    orig = eng._pack_into

    def guarded(buf, *a):
        pinned = {id(eng._bufs[b]) for _, b in eng._inflight}
        assert id(buf) not in pinned, "repacked an inflight buffer"
        return orig(buf, *a)

    eng._pack_into = guarded
    for i in range(8):
        eng.submit(*batches[i % 3][0])
    eng.drain()
    assert eng.dispatches == 8


def test_backpressure_bounds_window(verifier, batches):
    """Contract 2a: depth bounds the steady-state window; every submit
    past the window retires exactly the overflow, in dispatch order."""
    eng = verifier.make_ingest(ml=ML, nbuf=4, depth=2)
    retired = []
    for i in range(9):
        out = eng.submit(*batches[i % 3][0])
        assert eng.inflight_depth <= 2
        retired += out
    retired += eng.drain()
    assert len(retired) == 9
    for i, ok in enumerate(retired):  # dispatch order preserved
        np.testing.assert_array_equal(ok, batches[i % 3][1])


def test_backpressure_when_all_buffers_pinned(verifier, batches):
    """Contract 2b: depth >= nbuf exhausts the free ring first; submit
    must then block on the oldest harvest (counted) instead of tearing."""
    eng = verifier.make_ingest(ml=ML, nbuf=2, depth=4)
    eng.submit(*batches[0][0])
    eng.submit(*batches[1][0])
    assert eng.backpressure_waits == 0
    out = eng.submit(*batches[2][0])  # no free buffer: forced harvest
    assert eng.backpressure_waits == 1
    assert len(out) == 1
    np.testing.assert_array_equal(out[0], batches[0][1])
    eng.drain()


def test_overlapped_bit_identical_to_serial(verifier, batches):
    """Contract 3: rotated-buffer verdicts == serial verdicts, batch for
    batch, across enough submissions that every buffer is reused."""
    eng = verifier.make_ingest(ml=ML, nbuf=3, depth=2)
    got = []
    for i in range(9):
        got += eng.submit(*batches[i % 3][0])
    got += eng.drain()
    assert len(got) == 9
    for i, ok in enumerate(got):
        np.testing.assert_array_equal(ok, batches[i % 3][1])


def test_reused_buffer_is_rezeroed(verifier):
    """Stale-padding regression: a long-message batch followed by a
    short-message batch through the SAME rotation must not leak the long
    batch's bytes into the short batch's zero-padded columns."""
    long_args = [np.asarray(a) for a in make_example_batch(
        BATCH, ML, valid=True, sign_pool=8, seed=11)]
    short = [np.asarray(a) for a in make_example_batch(
        BATCH, 32, valid=True, sign_pool=8, seed=12)]
    # widen the short batch's msgs to ML columns with zero padding
    wide = np.zeros((BATCH, ML), np.uint8)
    wide[:, :32] = short[0]
    short_args = [wide, short[1], short[2], short[3]]
    ref = np.asarray(verifier.packed_dispatch(*short_args, ml=ML))
    assert ref.all()
    eng = verifier.make_ingest(ml=ML, nbuf=2, depth=1)
    for _ in range(3):  # cycle both buffers through the long batch
        eng.submit(*long_args)
    eng.drain()
    eng.submit(*short_args)
    (ok,) = eng.drain()
    np.testing.assert_array_equal(ok, ref)


def test_engine_param_validation(verifier):
    with pytest.raises(ValueError):
        verifier.make_ingest(nbuf=1)
    with pytest.raises(ValueError):
        verifier.make_ingest(nbuf=2, depth=0)


def test_bucket_pool_rotation_zeroed():
    """Pipeline-level pool: reset() rotates a FREE blob in (fresh while
    the pool is dry, reused-and-rezeroed after release())."""
    from firedancer_tpu.disco.pipeline import _Bucket

    bk = _Bucket(4, 32, packed=True, n_buffers=2)
    first = bk.arr
    first[:] = 7
    bk.reset()                    # first still pinned under its dispatch
    assert bk.arr is not first
    bk.release(first)             # verdict materialized
    bk.reset()
    assert bk.arr is first        # reused from the pool
    assert not bk.arr.any()       # and re-zeroed
    # views rebind to the active blob
    bk.msgs[0, 0] = 1
    assert bk.arr[0, 0] == 1


def test_pipeline_packed_pool_bounded():
    """The pool never exceeds n_buffers even if more blobs are released
    (age-flush bursts): excess blobs fall to the GC."""
    from firedancer_tpu.disco.pipeline import _Bucket

    bk = _Bucket(4, 32, packed=True, n_buffers=2)
    blobs = []
    for _ in range(4):
        blobs.append(bk.arr)
        bk.reset()
    for b in blobs:
        bk.release(b)
    assert len(bk._pool) == 2
