"""Round-11 native host-path fast lane: one-pass C submit/harvest.

Falsifiable contracts, all CPU, no device verifier (verdicts injected):

  1. BIT IDENTITY — the C kernel (fd_hostpath_submit_rows +
     fd_hostpath_finish_rows), the NumPy fallback, and an independent
     per-txn reference model produce byte-identical wires, identical
     survivor order, and identical metrics across equal-length, ragged,
     all-dup, all-fail, zero-pass, intra-frag-dup, and dead-lane frags.
  2. PACKED EGRESS IDENTITY — egress_packed=True ships the SAME bytes
     (PackedVerdicts.wires()) the legacy per-txn list carries, and the
     DedupTile packed consumer republishes exactly those wires with the
     per-txn path's tags and dup verdicts.
  3. NO-.so FALLBACK — with the native library unloadable the pipeline
     imports, runs, and matches the reference model (pure-Python tcache).
  4. RAGGED MEMORY — the fallback arena build stages at most ~_NP_PAD_CAP
     padded bytes at a time: one long-tail row must not inflate the
     harvest footprint to k * Lmax, and a tiny pad cap is bit-identical.
"""

import tracemalloc

import numpy as np
import pytest

from firedancer_tpu.disco import pipeline as pl
from firedancer_tpu.disco.pipeline import PackedVerdicts, VerifyPipeline
from firedancer_tpu.tango.ring import PACKED_ROW_EXTRA, packed_row_ml

ML = packed_row_ml(256)          # 284
STRIDE = ML + PACKED_ROW_EXTRA   # 384


class _VerdictFn:
    """Packed verifier double: replays a scripted verdict per dispatch
    (row i of dispatch j passes iff script[j][i])."""

    def __init__(self, script):
        self.script = list(script)
        self.calls = 0

    def __call__(self, m, ln, s, p):
        return np.ones(m.shape[0], bool)

    def dispatch_blob(self, blob, maxlen=None):
        ok = np.zeros(blob.shape[0], bool)
        want = self.script[self.calls]
        self.calls += 1
        ok[:len(want)] = want
        return ok


def _mk_rows(n, lens, seed, nrows=None, dup_pairs=(), dead=()):
    """Packed rows with deterministic payload/sig bytes; dup_pairs=(a,b)
    copies a's tag onto b, dead=i zeroes i's tag (padding lane)."""
    rng = np.random.default_rng(seed)
    nrows = n if nrows is None else nrows
    rows = np.zeros((nrows, STRIDE), np.uint8)
    for i in range(n):
        L = int(lens[i])
        rows[i, :L] = rng.integers(0, 256, L, dtype=np.uint8)
        rows[i, ML:ML + 64] = rng.integers(0, 256, 64, dtype=np.uint8)
        # distinct nonzero tags by default (16-bit lane id, LE)
        rows[i, ML:ML + 2] = [(i + 1) & 0xFF, (i + 1) >> 8]
        rows[i, ML + 96:ML + 100] = np.frombuffer(
            L.to_bytes(4, "little"), np.uint8)
    for a, b in dup_pairs:
        rows[b, ML:ML + 8] = rows[a, ML:ML + 8]
    for i in dead:
        rows[i, ML:ML + 8] = 0
    return rows


def _ref_run(frags):
    """Independent reference: the pre-round-11 per-txn assembly and exact
    FD_TCACHE semantics (query-only at submit, insert on pass) over a
    set model — valid while nothing evicts (tag count << depth)."""
    seen = set()
    wires, m = [], dict(txns_in=0, dedup_drop=0, verify_fail=0,
                        verify_pass=0)
    for rows, n, ok in frags:
        tags = [int.from_bytes(bytes(rows[i, ML:ML + 8]), "little")
                for i in range(n)]
        dup = [t != 0 and t in seen for t in tags]
        m["txns_in"] += n
        m["dedup_drop"] += sum(dup)
        out = []
        for i in range(n):
            if tags[i] == 0 or dup[i]:
                continue
            if not ok[i]:
                m["verify_fail"] += 1
                continue
            if tags[i] in seen:          # intra-frag dup (insert-time)
                m["dedup_drop"] += 1
                continue
            seen.add(tags[i])
            m["verify_pass"] += 1
            L = min(max(int.from_bytes(
                bytes(rows[i, ML + 96:ML + 100]), "little", signed=True),
                0), ML)
            out.append(b"\x01" + bytes(rows[i, ML:ML + 64])
                       + bytes(rows[i, :L]))
        wires.append(out)
    return wires, m


def _pipe_run(frags, native, egress_packed=False, allow_fallback=False):
    fn = _VerdictFn([ok for _, _, ok in frags])
    pipe = VerifyPipeline(fn, buckets=[(max(r.shape[0] for r, _, _ in
                                            frags), ML)],
                          tcache_depth=1 << 12, max_inflight=0,
                          native_hostpath=native,
                          egress_packed=egress_packed)
    if native and pipe._hp is None and not allow_fallback:
        pytest.skip("native hostpath library unavailable")
    wires = []
    for rows, n, _ in frags:
        passed = pipe.submit_packed_rows(rows, n=n)
        if egress_packed:
            out = []
            for pv in passed:
                assert isinstance(pv, PackedVerdicts)
                ws = pv.wires()
                assert len(ws) == pv.k == len(pv.tags)
                # tags must be each wire's sig low-64 (what dedup keys on)
                for w, t in zip(ws, pv.tags):
                    assert int.from_bytes(w[1:9], "little") == int(t)
                out += ws
            wires.append(out)
        else:
            wires.append([w for w, _ in passed])
    s = dict(pipe.metrics.snapshot())
    return wires, {k: s[k] for k in ("txns_in", "dedup_drop",
                                     "verify_fail", "verify_pass")}


def _sweep_frags():
    """The property sweep: one frag set exercising every shape class."""
    n = 24
    rng = np.random.default_rng(11)
    eq = _mk_rows(n, [100] * n, seed=1)
    ragged = _mk_rows(n, rng.integers(0, ML + 1, n), seed=2)
    mixed = _mk_rows(n, rng.integers(1, ML, n), seed=3,
                     dup_pairs=((0, 5), (1, 9)), dead=(7,))
    padded = _mk_rows(10, [64] * 10, seed=4, nrows=n)
    ok_all = np.ones(n, bool)
    ok_none = np.zeros(n, bool)
    ok_mix = rng.random(n) < 0.7
    return [
        (eq, n, ok_all),                 # equal-length, all pass
        (ragged, n, ok_mix),             # ragged, mixed verdicts
        (ragged, n, ok_all),             # resubmit: all-dup frag
        (mixed, n, ok_mix),              # intra-frag dups + dead lane
        (eq, n, ok_none),                # all-fail... but eq tags are
        (padded, 10, ok_all),            # n < nrows zero padding
        (padded, 10, ok_none),           # zero-pass resubmit (all dup)
    ]


def test_bit_identity_native_vs_fallback_vs_reference():
    """Contract 1: three independent implementations, one answer."""
    frags = _sweep_frags()
    ref_w, ref_m = _ref_run(frags)
    nat_w, nat_m = _pipe_run(frags, native=True)
    np_w, np_m = _pipe_run(frags, native=False)
    assert nat_w == ref_w
    assert np_w == ref_w
    assert nat_m == ref_m
    assert np_m == ref_m


@pytest.mark.parametrize("native", [True, False])
def test_packed_egress_bit_identity(native):
    """Contract 2 (pipeline half): PackedVerdicts carries the exact bytes
    the legacy per-txn egress would, same order, same tags."""
    frags = _sweep_frags()
    legacy_w, legacy_m = _pipe_run(frags, native=native)
    packed_w, packed_m = _pipe_run(frags, native=native,
                                   egress_packed=True)
    assert packed_w == legacy_w
    assert packed_m == legacy_m


def test_native_lib_unavailable_falls_back(monkeypatch):
    """Contract 3: no .so -> pure-Python tcache + NumPy finish, same
    wires and metrics as the reference model."""
    def _boom():
        raise OSError("native library unavailable")

    monkeypatch.setattr(pl.native_mod, "lib", _boom)
    frags = _sweep_frags()
    # knob on, load fails -> fallback must carry the day
    wires, m = _pipe_run(frags, native=True, allow_fallback=True)
    ref_w, ref_m = _ref_run(frags)
    assert wires == ref_w
    assert m == ref_m


def test_np_finish_long_tail_chunked(monkeypatch):
    """Contract 4: one ml-length row among 2048 short ones must not
    stage a (k, 65+Lmax) padded block — peak stays well under the
    unchunked build's footprint, and a tiny pad cap is bit-identical."""
    n = 2048
    lens = np.full(n, 8)
    lens[-1] = ML                        # the long tail
    rows = _mk_rows(n, lens, seed=9)
    ok = np.ones(n, bool)

    def run(cap=None):
        if cap is not None:
            monkeypatch.setattr(VerifyPipeline, "_NP_PAD_CAP", cap)
        pipe = VerifyPipeline(_VerdictFn([ok]), buckets=[(n, ML)],
                              tcache_depth=1 << 13, max_inflight=0,
                              native_hostpath=False)
        return pipe, pipe.submit_packed_rows(rows, n=n)

    pipe, _ = run()                      # warm shapes/scratch
    pipe2 = VerifyPipeline(_VerdictFn([ok, ok]), buckets=[(n, ML)],
                           tcache_depth=1 << 13, max_inflight=0,
                           native_hostpath=False)
    # the pipeline's call buffer is allocated once, at its first frame
    # (one dead row here), and reused: it is not harvest staging
    pipe2.submit_packed_rows(_mk_rows(1, [8], seed=10, nrows=n, dead=[0]),
                             n=1)
    tracemalloc.start()
    passed = pipe2.submit_packed_rows(rows, n=n)
    _, peak = tracemalloc.get_traced_memory()
    tracemalloc.stop()
    assert len(passed) == n
    # unchunked: padded wires + bool mask + fancy-index row copy, all
    # (k, 65+Lmax)-ish ~ 3 * n * (65 + ML) bytes
    naive = 3 * n * (65 + ML)
    assert peak < naive // 2, \
        f"ragged build staged ~{peak} B (unchunked ~{naive} B)"
    _, tiny = run(cap=4096)
    assert [w for w, _ in tiny] == [w for w, _ in passed]
