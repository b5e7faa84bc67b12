"""Packed frames merged into one device call (VerifyPipeline bulk lane).

A bulk-lane frame's live rows are copied into the pipeline's open call
buffer, and its credit returns at the copy; the call goes out whenever
the device queue has room, so frames that arrive while max_inflight
calls are queued merge into the next one.  All CPU, verdicts from a fake
blob verifier or the host ed25519 verifier:

  (a) merged calls give the verdicts, wires and counts of one call per
      frame: single- and multi-signature frames with damaged signatures,
      native and NumPy finish, and real signatures on the host verifier
  (b) a duplicate split across two frames of one call passes once
  (c) release_cb fires at the copy while the queue is at budget, and
      exactly once on a torn copy, which leaves the call's fill as it was
  (d) a frame that does not fit closes the call and is never split
  (e) with room in the queue a lone frame goes out at once
  (f) coalesced_frames, lanes_filled and lanes_dispatched
  (g) call-buffer rows past the fill read as dead after a fuller use
"""

import numpy as np
import pytest

from firedancer_tpu.disco.pipeline import PackedVerdicts, VerifyPipeline
from firedancer_tpu.tango.ring import (PACKED_ROW_EXTRA, packed_row_marks,
                                       packed_row_ml)

ML = packed_row_ml(256)          # 284
STRIDE = ML + PACKED_ROW_EXTRA
B = 16                           # rows per frame and per device call


class _Held:
    """A dispatched verdict that is not ready until the test says so."""

    def __init__(self, ok):
        self.ok, self.ready = ok, False

    def is_ready(self):
        return self.ready

    def __array__(self, dtype=None, copy=None):
        return self.ok


class _Blob:
    """Fake packed verifier: a row passes iff the low two bits of its
    signature's byte 1 are not both clear.  Keeps a copy of every blob it
    was handed; hold=True hands back verdicts that stay not-ready until
    ready_all()."""

    mode = "strict"

    def __init__(self, hold=False):
        self.hold = hold
        self.calls = []
        self.held = []

    def __call__(self, m, ln, s, p):
        return np.ones(m.shape[0], bool)

    def dispatch_blob(self, blob, maxlen=None):
        ml = blob.shape[1] - PACKED_ROW_EXTRA
        self.calls.append(blob.copy())
        ok = (blob[:, ml + 1] & 3) != 0
        if not self.hold:
            return ok
        self.held.append(_Held(ok))
        return self.held[-1]

    def ready_all(self):
        for v in self.held:
            v.ready = True


class _Lapped:
    """An mcache whose frag was overrun: every seq re-check fails."""

    def query(self, seq):
        return 1, None


def _txn(rng, k, msg_len, bad=()):
    """The k rows of one k-signature txn: the message on every row,
    signature i and a signer key per row, the len words marked; the
    signatures listed in `bad` fail the fake verifier."""
    rows = np.zeros((k, STRIDE), np.uint8)
    rows[:, :msg_len] = rng.integers(0, 256, msg_len, dtype=np.uint8)
    rows[:, ML:ML + 96] = rng.integers(0, 256, (k, 96), dtype=np.uint8)
    rows[:, ML] |= 1             # a dedup tag is never the dead lane's 0
    rows[:, ML + 1] |= 1         # passes ...
    for j in bad:
        rows[j, ML + 1] &= 0xFC  # ... unless damaged
    rows[:, ML + 96:ML + 100] = (packed_row_marks(k) | np.uint32(msg_len)
                                 ).view(np.uint8).reshape(k, 4)
    return rows


def _frame(txns):
    """One (B, STRIDE) frame of whole txns and its row count; the tail
    rows are the producer's zero padding."""
    rows = np.zeros((B, STRIDE), np.uint8)
    live = np.concatenate(txns) if txns else rows[:0]
    rows[:len(live)] = live
    return rows, len(live)


def _mix(seed, multisig):
    """Frames of 1- (or 1- to 5-) signature txns, some damaged, of 3 to
    11 rows each, with a valid txn repeated in a later frame."""
    rng = np.random.default_rng(seed)
    frames, first = [], None
    for f in range(9):
        txns, n = [], 0
        want = int(rng.integers(3, 12))
        while n < want:
            k = min(int(rng.integers(1, 6)) if multisig else 1, want - n)
            bad = ((int(rng.integers(0, k)),)
                   if rng.random() < 0.25 else ())
            txns.append(_txn(rng, k, int(rng.integers(0, ML + 1)), bad))
            n += k
        if first is None:
            first = txns[0].copy()
            first[:, ML + 1] |= 1    # every signature valid
            txns[0] = first
        if f == 6:
            txns.append(first)       # seen before: a dup
        frames.append(_frame(txns))
    return frames


def _wires(out):
    got = []
    for v in out:
        got += v.wires() if isinstance(v, PackedVerdicts) else [v[0]]
    return got


COUNTS = ("txns_in", "verify_pass", "verify_fail", "dedup_drop",
          "lanes_filled", "msg_bytes", "multisig_txns")


def _per_frame(fn, frames, native, egress_packed=False, shape=(B, ML)):
    """One call per frame: sync mode retires each call at its submit."""
    pipe = VerifyPipeline(fn, buckets=[shape], tcache_depth=1 << 10,
                          max_inflight=0, native_hostpath=native,
                          egress_packed=egress_packed)
    if native and pipe._hp is None:
        pytest.skip("native hostpath library unavailable")
    out = []
    for rows, n in frames:
        out += pipe.submit_packed_rows(rows, n=n)
    return pipe, _wires(out)


def _merged(fn, frames, native, egress_packed=False, shape=(B, ML)):
    """Every frame submitted while the device sits on the first call: the
    rest merge into calls that close only when the next frame does not
    fit.  Then the device finishes and everything is harvested."""
    pipe = VerifyPipeline(fn, buckets=[shape], tcache_depth=1 << 10,
                          max_inflight=1, native_hostpath=native,
                          egress_packed=egress_packed)
    out = []
    for rows, n in frames:
        out += pipe.submit_packed_rows(rows, n=n)
    fn.ready_all()
    out += pipe.harvest(block=True)
    assert not pipe.has_pending
    return pipe, _wires(out)


@pytest.mark.parametrize("multisig", [False, True])
@pytest.mark.parametrize("native,egress_packed", [
    (True, False), (True, True), (False, False), (False, True)])
def test_merged_calls_match_one_call_per_frame(multisig, native,
                                               egress_packed):
    """(a): wires in the same order, and the same counts."""
    frames = _mix(27 + multisig, multisig)
    one, want = _per_frame(_Blob(), frames, native, egress_packed)
    fn = _Blob(hold=True)
    merged, got = _merged(fn, frames, native, egress_packed)
    assert got == want
    assert len(want) > 10
    s1, s2 = one.metrics.snapshot(), merged.metrics.snapshot()
    for k in COUNTS:
        assert s2[k] == s1[k], k
    assert s1["verify_fail"] > 0 and s1["dedup_drop"] == 1
    assert (s1["multisig_txns"] > 0) == multisig
    # fewer calls than frames, each a full (B, ML) blob
    assert len(fn.calls) < len(frames)
    assert s2["coalesced_frames"] == len(frames) - len(fn.calls)
    assert s2["lanes_dispatched"] == B * len(fn.calls)
    assert s2["batches"] == len(fn.calls)


def test_merged_calls_match_one_call_per_frame_real_signatures():
    """(a) with real signatures: frames the quic publisher stamped from a
    1- to 12-signature mix with each kind of damage, verified by the host
    ed25519 verifier, merged or not (the 1-row frame joins the next)."""
    from tests.test_packed_multisig import (B as MB, ML as MML,
                                            _build, _frames,
                                            _HostVerifier)

    class _HeldHost(_HostVerifier):
        def __init__(self):
            self.calls, self.held = [], []

        def dispatch_blob(self, blob, maxlen=None):
            self.calls.append(blob.copy())
            self.held.append(_Held(super().dispatch_blob(blob, maxlen)))
            return self.held[-1]

        def ready_all(self):
            for v in self.held:
                v.ready = True

    specs = [(1, 0, None, 0), (3, 300, "s_bit", 1), (2, 200, "r_bit", 0),
             (1, 0, "msg_byte", 0), (12, 460, None, 0), (5, 300, None, 0),
             (4, 250, "s_plus_l", 3), (1, 0, None, 0), (7, 400, None, 0),
             (2, 150, None, 0), (6, 300, "s_bit", 5), (1, 0, None, 0)]
    wires = _build(2**31 + 27, specs)
    frames = []
    for a, b in ((0, 3), (3, 4), (4, 5), (5, 7), (7, 9), (9, 12)):
        ctx, stamped = _frames(wires[a:b], rows=MB // 2)
        assert len(stamped) == b - a
        frames += ctx.frames
    assert [n for _, n in frames] == [6, 1, 12, 9, 8, 9]
    shape = (MB // 2, MML)
    _, want = _per_frame(_HostVerifier(), frames, True, shape=shape)
    fn = _HeldHost()
    merged, got = _merged(fn, frames, True, shape=shape)
    assert got == want
    assert [w[0] for w in got] == [1, 12, 5, 1, 7, 2, 1]
    assert len(fn.calls) == len(frames) - 1
    assert merged.metrics.coalesced_frames == 1


def test_duplicate_split_across_frames_of_one_call_passes_once():
    """(b): the pre-dedup query of the second frame cannot see the first
    (nothing is inserted before harvest), so the harvest-time insert of
    the merged call drops the second copy."""
    rng = np.random.default_rng(3)
    x = _txn(rng, 3, 200)
    fn = _Blob(hold=True)
    pipe = VerifyPipeline(fn, buckets=[(B, ML)], tcache_depth=1 << 10,
                          max_inflight=1)
    lead = _frame([_txn(rng, 1, 50)])
    a = _frame([_txn(rng, 2, 100), x])
    b = _frame([x, _txn(rng, 1, 80)])
    out = []
    for rows, n in (lead, a, b):
        out += pipe.submit_packed_rows(rows, n=n)
    assert len(fn.calls) == 1 and pipe.has_open
    fn.ready_all()
    out += pipe.harvest(block=True)
    assert len(fn.calls) == 2, "a and b went out as one call"
    wires = _wires(out)
    xs = [w for w in wires if w[0] == 3]
    assert len(xs) == 1
    assert len(wires) == 4
    assert pipe.metrics.dedup_drop == 1
    assert pipe.metrics.verify_pass == 4


def test_release_at_copy_while_queue_full_and_once_on_torn():
    """(c): the queue is at budget, so frames stay in the open call, yet
    each frame's credit is back before any verdict; a torn copy releases
    exactly once and the next frame lands where the torn one would have."""
    rng = np.random.default_rng(4)
    fn = _Blob(hold=True)
    pipe = VerifyPipeline(fn, buckets=[(B, ML)], tcache_depth=1 << 10,
                          max_inflight=1)
    released = []
    f0, f1, f3 = (_frame([_txn(rng, 1, 40), _txn(rng, 2, 60)])
                  for _ in range(3))
    f2 = _frame([_txn(rng, 2, 40), _txn(rng, 3, 60)])   # 5 rows
    pipe.submit_packed_rows(f0[0], n=f0[1],
                            release_cb=lambda: released.append(0))
    assert released == [0] and len(pipe.inflight) == 1
    pipe.submit_packed_rows(f1[0], n=f1[1],
                            release_cb=lambda: released.append(1))
    assert released == [0, 1]
    assert len(fn.calls) == 1 and pipe.has_open
    pipe.submit_packed_rows(f2[0], n=f2[1], guard=(_Lapped(), 7),
                            release_cb=lambda: released.append(2))
    assert released == [0, 1, 2]
    assert pipe.metrics.torn_drop == 1 and pipe.metrics.torn_txns == 2
    pipe.submit_packed_rows(f3[0], n=f3[1],
                            release_cb=lambda: released.append(3))
    assert released == [0, 1, 2, 3]
    assert not any(v.ready for v in fn.held), "no verdict has landed"
    fn.ready_all()
    out = pipe.harvest(block=True)
    assert released == [0, 1, 2, 3], "no release at harvest"
    # the second call holds f1 then f3, back to back; the torn f2 left
    # no row, not even past f3's
    call = fn.calls[1]
    np.testing.assert_array_equal(call[:3], f1[0][:3])
    np.testing.assert_array_equal(call[3:6], f3[0][:3])
    assert not call[6:].any()
    assert len(_wires(out)) == 6
    assert pipe.metrics.txns_in == 6
    assert pipe.metrics.lanes_filled == 9


def test_frame_that_does_not_fit_closes_the_call():
    """(d): 10 rows are open; an 8-row frame does not fit, so the oldest
    call is retired, the open call goes out as it is, and the 8 rows open
    the next call whole."""
    rng = np.random.default_rng(5)
    fn = _Blob(hold=True)
    pipe = VerifyPipeline(fn, buckets=[(B, ML)], tcache_depth=1 << 10,
                          max_inflight=1)
    f0 = _frame([_txn(rng, 1, 30)])
    f1 = _frame([_txn(rng, 5, 200), _txn(rng, 5, 210)])
    f2 = _frame([_txn(rng, 4, 100), _txn(rng, 4, 120)])
    assert (f1[1], f2[1]) == (10, 8)
    out = pipe.submit_packed_rows(f0[0], n=f0[1])
    out += pipe.submit_packed_rows(f1[0], n=f1[1])
    assert len(fn.calls) == 1
    out += pipe.submit_packed_rows(f2[0], n=f2[1])
    # the oldest call was retired (blocking) to make room
    assert len(fn.calls) == 2 and pipe.metrics.batches == 1
    assert len(_wires(out)) == 1
    np.testing.assert_array_equal(fn.calls[1][:10], f1[0][:10])
    assert not fn.calls[1][10:].any()
    fn.ready_all()
    out = pipe.harvest(block=True)
    np.testing.assert_array_equal(fn.calls[2][:8], f2[0][:8])
    assert not fn.calls[2][8:].any()
    assert [w[0] for w in _wires(out)] == [5, 5, 4, 4]
    assert pipe.metrics.coalesced_frames == 0


def test_lone_frame_dispatches_at_once():
    """(e): with room in the device queue nothing waits to merge."""
    rng = np.random.default_rng(6)
    fn = _Blob(hold=True)
    pipe = VerifyPipeline(fn, buckets=[(B, ML)], tcache_depth=1 << 10,
                          max_inflight=4)
    for i in range(3):
        rows, n = _frame([_txn(rng, 2, 90)])
        pipe.submit_packed_rows(rows, n=n)
        assert len(fn.calls) == i + 1 and not pipe.has_open
    assert len(pipe.inflight) == 3
    assert pipe.metrics.coalesced_frames == 0


def test_counts_of_merged_calls():
    """(f): a frame counts as coalesced when it joins a call that already
    held rows; lanes_filled counts live rows, lanes_dispatched the call's
    whole width."""
    rng = np.random.default_rng(7)
    fn = _Blob(hold=True)
    pipe = VerifyPipeline(fn, buckets=[(B, ML)], tcache_depth=1 << 10,
                          max_inflight=1)
    sizes = [2, 3, 4, 5, 6, 1]
    for k in sizes:
        rows, n = _frame([_txn(rng, 1, 20) for _ in range(k)])
        pipe.submit_packed_rows(rows, n=n)
    # call 1: [2]; open: 3+4+5 = 12, then 6 does not fit -> call 2 [3,4,5]
    # and the open call [6, 1]
    m = pipe.metrics
    assert len(fn.calls) == 2
    assert m.coalesced_frames == 3
    assert m.lanes_filled == sum(sizes)
    assert m.lanes_dispatched == 2 * B
    assert m.last_fill_pct == 100 * 12 // B
    fn.ready_all()
    pipe.harvest(block=True)
    assert len(fn.calls) == 3
    assert m.lanes_dispatched == 3 * B
    assert m.verify_pass == sum(sizes)
    snap = m.snapshot()
    assert snap["coalesced_frames"] == 3


@pytest.mark.parametrize("max_inflight", [0, 2])
def test_rows_past_the_fill_read_dead_after_a_fuller_use(max_inflight):
    """(g): a call buffer that carried 14 rows and then 3 dispatches the
    3 and zero rows after them, in sync mode (one buffer) and through
    the rotation."""
    rng = np.random.default_rng(8)
    fn = _Blob()
    pipe = VerifyPipeline(fn, buckets=[(B, ML)], tcache_depth=1 << 10,
                          max_inflight=max_inflight)
    frames = [_frame([_txn(rng, 2, 250) for _ in range(7)])
              for _ in range(max_inflight + 1)]
    frames.append(_frame([_txn(rng, 3, 70)]))
    for rows, n in frames:
        pipe.submit_packed_rows(rows, n=n)
        pipe.harvest(block=True)
    last = fn.calls[-1]
    np.testing.assert_array_equal(last[:3], frames[-1][0][:3])
    assert not last[3:].any()
    # the buffer was reused, not allocated afresh
    call_bufs = {id(a) for fc in pipe._fcalls.values()
                 for a, _ in fc._pool}
    assert len(call_bufs) <= max_inflight + 1
