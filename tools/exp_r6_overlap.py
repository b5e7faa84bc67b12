"""Round-6 ingest-overlap A/B: serial fetch-per-batch fresh ingest vs
the double-buffered PackedIngest engine (nbuf rotating blobs, depth
dispatch-ahead), SAME session, median of reps.

Arms:
  serial     pack -> device_put -> dispatch -> np.asarray PER BATCH
             (upload, verify, and verdict fetch fully serialized — the
             pre-r5 shape of measure_throughput_fresh's failure mode)
  pipelined  pack -> device_put -> dispatch per batch, ONE draining
             fetch at the end (the r5 fresh loop: the in-order queue
             pipelines uploads against compute but the host still packs
             in the gaps)
  overlap    PackedIngest submit() loop + drain(): rotation + bounded
             window + verdict retirement per batch (batch k+1 packs and
             uploads while batch k verifies; verdicts stream back)

The acceptance bar (ISSUE r6) compares overlap vs serial: >= 1.2x.
Run on the driver chip for the recorded verdict; CPU runs are labelled
by the printed backend and measure the architecture, not the chip.

Env: B=batch (32768), ITERS (8), REPS (5), NBUF (3), DEPTH (2).
"""

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def median(xs):
    xs = sorted(xs)
    return xs[len(xs) // 2]


def main():
    from firedancer_tpu.utils import xla_cache
    xla_cache.enable()
    import jax

    from firedancer_tpu.models.verifier import (
        SigVerifier,
        VerifierConfig,
        make_example_batch,
    )

    batch = int(os.environ.get("B", 32768))
    iters = int(os.environ.get("ITERS", 8))
    reps = int(os.environ.get("REPS", 5))
    nbuf = int(os.environ.get("NBUF", 3))
    depth = int(os.environ.get("DEPTH", 2))

    v = SigVerifier(VerifierConfig(batch=batch, msg_maxlen=128))
    args = [np.asarray(a) for a in
            make_example_batch(batch, 128, valid=True, sign_pool=64)]
    ml = int(args[1].max())

    ref = np.asarray(v.packed_dispatch(*args, ml=ml))  # warm + reference
    assert ref.all()

    def run_serial():
        t0 = time.perf_counter()
        for _ in range(iters):
            ok = np.asarray(v.packed_dispatch(*args, ml=ml))
        assert ok.all()
        return batch * iters / (time.perf_counter() - t0)

    def run_pipelined():
        t0 = time.perf_counter()
        ok = None
        for _ in range(iters):
            ok = v.packed_dispatch(*args, ml=ml)
        ok = np.asarray(ok)
        assert ok.all()
        return batch * iters / (time.perf_counter() - t0)

    def run_overlap():
        eng = v.make_ingest(ml=ml, nbuf=nbuf, depth=depth)
        eng.submit(*args)
        eng.drain()                     # warm the engine path
        t0 = time.perf_counter()
        outs = []
        for _ in range(iters):
            outs += eng.submit(*args)
        outs += eng.drain()
        dt = time.perf_counter() - t0
        assert len(outs) == iters and all(o.all() for o in outs)
        return batch * iters / dt

    arms = {"serial": run_serial, "pipelined": run_pipelined,
            "overlap": run_overlap}
    out = {"batch": batch, "iters": iters, "reps": reps,
           "nbuf": nbuf, "depth": depth,
           "backend": jax.devices()[0].platform}
    for name, fn in arms.items():
        fn()  # per-arm warm rep (jit identity is shared; cheap)
        runs = [fn() for _ in range(reps)]
        out[name] = round(median(runs), 1)
        out[name + "_runs"] = [round(r, 1) for r in sorted(runs)]
        print(f"{name}: {out[name]:,.0f} v/s  {out[name + '_runs']}",
              file=sys.stderr)
    out["overlap_vs_serial"] = round(out["overlap"] / out["serial"], 3)
    out["overlap_vs_pipelined"] = round(
        out["overlap"] / out["pipelined"], 3)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
