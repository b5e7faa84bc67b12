"""CI host-path smoke: the round-8 zero-repack wire->device path plus
the round-11 one-pass native fast lane.

Three gates (packed egress identity is tier-1:
tests/test_hostpath_native.py::test_packed_egress_bit_identity):
  1. verdict parity — `submit_rows` over device-blob-layout rows must be
     BIT-IDENTICAL to the legacy `_pack_into` host repack on a fixed
     seed with mixed valid/tampered lanes (the knob `FDTPU_INGEST_
     LEGACY_PACK=1` keeps the old path alive; both must agree).
  2. native/fallback parity — the round-11 one-pass C submit/harvest
     kernel (FDTPU_INGEST_NATIVE_HOSTPATH) must produce the SAME wires,
     survivor order, and metric counters as the NumPy fallback on fixed
     mixed-verdict, mixed-length, dup-bearing frags.
  3. 2-tile packed mp smoke — the packed-wire verify-bench topology
     (dcache frags ARE device-blob rows) boots with two verify tiles,
     the source's round-robin burst splitter deals work to BOTH, every
     txn arrives, and zero frags are torn-dropped by the post-dispatch
     seq re-check.

A real file (not a ci.sh heredoc) because tile processes use the
multiprocessing 'spawn' start method, which re-imports __main__ from
its path — stdin scripts have none.

Usage:  JAX_PLATFORMS=cpu python tools/hostpath_smoke.py
"""

import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def verdict_parity() -> None:
    from firedancer_tpu.models.verifier import (
        SigVerifier,
        VerifierConfig,
        make_example_batch,
    )
    from firedancer_tpu.tango.ring import PACKED_ROW_EXTRA

    B, ml = 64, 96
    sv = SigVerifier(VerifierConfig(batch=B, msg_maxlen=ml))
    msgs, lens, sigs, pubs = (np.asarray(a) for a in make_example_batch(
        B, ml, valid=True, sign_pool=8, seed=11))
    sigs = sigs.copy()
    sigs[5, 0] ^= 0xFF            # tampered lanes: verdict must be mixed
    sigs[23, 63] ^= 0x01

    os.environ["FDTPU_INGEST_LEGACY_PACK"] = "1"
    try:
        eng = sv.make_ingest(ml=ml, nbuf=2, depth=1)
        eng.submit(msgs, lens, sigs, pubs)
        (ref,) = eng.drain()
    finally:
        os.environ.pop("FDTPU_INGEST_LEGACY_PACK", None)
    assert ref.any() and not ref.all(), "need a mixed verdict"

    rows = np.zeros((B, ml + PACKED_ROW_EXTRA), np.uint8)
    rows[:, :ml] = msgs
    rows[:, ml:ml + 64] = sigs
    rows[:, ml + 64:ml + 96] = pubs
    rows[:, ml + 96:ml + 100] = (
        lens.astype(np.int32).view(np.uint8).reshape(B, 4))
    eng2 = sv.make_ingest(ml=ml, nbuf=2, depth=1)
    eng2.submit_rows(rows)
    (got,) = eng2.drain()
    assert np.array_equal(got, ref), "zero-repack verdicts diverged"
    print("hostpath parity ok: submit_rows bit-identical to legacy "
          f"_pack_into ({int(ref.sum())}/{B} pass)")


def native_fallback_parity() -> None:
    """Round-11 gate: the one-pass C kernel vs the NumPy fallback, wire
    for wire and counter for counter, on mixed-length frags with mixed
    verdicts and cross-frag dups (no device; verdicts injected)."""
    from firedancer_tpu.disco.pipeline import VerifyPipeline
    from firedancer_tpu.tango.ring import PACKED_ROW_EXTRA, packed_row_ml

    ml = packed_row_ml(256)
    stride = ml + PACKED_ROW_EXTRA
    rng = np.random.default_rng(29)
    n = 48
    frags = []
    for _ in range(3):
        rows = np.zeros((n, stride), np.uint8)
        lens = rng.integers(0, ml + 1, n)
        for i in range(n):
            li = int(lens[i])
            rows[i, :li] = rng.integers(0, 256, li, dtype=np.uint8)
            rows[i, ml:ml + 64] = rng.integers(0, 256, 64, dtype=np.uint8)
            rows[i, ml] = 1 + (i % 251)
            rows[i, ml + 96:ml + 100] = np.frombuffer(
                li.to_bytes(4, "little"), np.uint8)
        frags.append(rows)
    frags.append(frags[1])               # cross-frag dups

    class _Mixed:
        def __call__(self, m, l, s, p):
            return np.ones((np.asarray(m).shape[0],), bool)

        def dispatch_blob(self, blob, maxlen=None):
            return (blob[:, blob.shape[1] - 100 + 1] & 3) != 0

    def run(native: bool):
        pipe = VerifyPipeline(_Mixed(), buckets=[(n, ml)],
                              tcache_depth=1 << 12, max_inflight=0,
                              native_hostpath=native)
        wires = []
        for rows in frags:
            wires += [w for w, _ in pipe.submit_packed_rows(rows)]
        s = dict(pipe.metrics.snapshot())
        return wires, {k: s[k] for k in ("txns_in", "dedup_drop",
                                         "verify_fail", "verify_pass")}

    nat_w, nat_m = run(True)
    fb_w, fb_m = run(False)
    assert nat_w == fb_w, "native kernel wires diverged from fallback"
    assert nat_m == fb_m, f"metric divergence: {nat_m} vs {fb_m}"
    assert nat_m["verify_fail"] and nat_m["dedup_drop"], \
        "gate needs mixed verdicts and dups to mean anything"
    print(f"hostpath native parity ok: {len(nat_w)} wires bit-identical "
          f"to the NumPy fallback ({nat_m})")


def packed_mp_smoke() -> None:
    from firedancer_tpu.app import config as config_mod
    from firedancer_tpu.disco.run import TopoRun
    from firedancer_tpu.tango.ring import packed_row_ml
    from firedancer_tpu.utils import aot

    ml = packed_row_ml(256)
    # AOT-first boot: spawn-context children must never cold-compile
    aot_dir = os.environ.get("FDTPU_CI_AOT_DIR", "/tmp/fdtpu_aot_ci")
    if aot.ensure_verify_packed(aot_dir, 64, ml) is None:
        print("hostpath mp smoke SKIPPED: AOT unusable on this backend")
        return

    n_txn = 2048
    cfg = config_mod.load(None)
    cfg["name"] = "fdtpu_ci_hostpath"
    cfg["topology"] = "verify-bench"
    cfg["layout"]["verify_tile_count"] = 2
    cfg["development"]["packed_wire"] = 1
    cfg["development"]["source_count"] = n_txn
    cfg["tiles"]["verify"]["batch"] = 64
    cfg["tiles"]["verify"]["aot_dir"] = aot_dir
    cfg["tiles"]["verify"]["aot_require"] = 1
    spec = config_mod.build_topology(cfg)
    with TopoRun(spec) as run:
        run.wait_ready(timeout=300)
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            if sum(run.metrics(f"verify:{v}")["txn_in_cnt"]
                   for v in range(2)) >= n_txn:
                break
            time.sleep(0.2)
        m0 = run.metrics("verify:0")
        m1 = run.metrics("verify:1")
        assert m0["txn_in_cnt"] + m1["txn_in_cnt"] >= n_txn, (m0, m1)
        assert m0["txn_in_cnt"] > 0 and m1["txn_in_cnt"] > 0, \
            "burst splitter starved a tile"
        assert m0["torn_drop_cnt"] == 0 and m1["torn_drop_cnt"] == 0, \
            "unexpected torn-frag drops"
    print(f"hostpath mp smoke ok: 2 packed tiles split {n_txn} txns "
          f"({m0['txn_in_cnt']}/{m1['txn_in_cnt']}), 0 torn drops")


def main() -> int:
    verdict_parity()
    native_fallback_parity()
    packed_mp_smoke()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
