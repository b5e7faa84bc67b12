"""Shared measurement harness for the TPU experiment scripts.

The methodology IS the result (see project memory / docs/perf_ceiling.md):
  * np.asarray() is the true sync (a fetch waits for the result);
  * DISPATCH back-to-back dispatches amortize the host round trip
    (the in-order device queue drains on the final fetch);
  * rates are SLOPES over two step counts so round trip + dispatch
    overhead cancel;
  * loop bodies must carry data dependence or XLA hoists them.
"""

import sys
import time

import jax
import numpy as np

DISPATCH = 6


def note_wiring(out: dict, pallas_ok: bool) -> dict:
    """Stamp an A/B result dict with whether this run can render a kernel
    verdict.  When the Pallas path is unavailable (wrong platform, ragged
    batch, FDTPU_NO_PALLAS) both arms lower to the same XLA fallback, so
    the measured ratio only proves the WIRING works — mark the JSON and
    warn loudly so a CPU number is never quoted as a perf result."""
    out["pallas"] = bool(pallas_ok)
    out["wiring_only"] = not pallas_ok
    if out["wiring_only"]:
        bar = "!" * 72
        print(f"{bar}\n"
              "! WIRING-ONLY RUN: no Pallas backend for this batch/platform.\n"
              "! Arms measure the XLA fallback; ratios below check plumbing,\n"
              "! they are NOT a kernel verdict.  Rerun on TPU to decide.\n"
              f"{bar}", file=sys.stderr, flush=True)
    return out


def timed(fn, *args):
    out = fn(*args)
    jax.tree_util.tree_map(lambda x: np.asarray(x), out)  # warm + sync
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(DISPATCH):
            out = fn(*args)
        jax.tree_util.tree_map(lambda x: np.asarray(x), out)
        best = min(best, (time.perf_counter() - t0) / DISPATCH)
    return best


def slope(name, make_chain, s1, s2, work_per_step, unit="op"):
    """make_chain(steps) -> (jitted_fn, args).  Prints + returns s/unit."""
    f1, a1 = make_chain(s1)
    f2, a2 = make_chain(s2)
    t1, t2 = timed(f1, *a1), timed(f2, *a2)
    per_unit = (t2 - t1) / (s2 - s1) / work_per_step
    print(f"{name:44s} {t1*1e3:8.1f}/{t2*1e3:8.1f} ms "
          f"-> {per_unit*1e9:9.4f} ns/{unit} "
          f"({1/per_unit/1e6:10.2f} M{unit}/s)", flush=True)
    return per_unit
