#!/usr/bin/env python
"""Prime the persistent XLA cache with the bench configurations.

The RLC verify graph takes several minutes to compile cold on TPU; this
compiles the configs bench.py uses so later runs (the driver's) start hot.
Run detached: `nohup python tools/prime_bench_cache.py > prime.log 2>&1 &`
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from firedancer_tpu.utils import xla_cache  # noqa: E402

xla_cache.enable()

import numpy as np  # noqa: E402


def main():
    from firedancer_tpu.models.verifier import (SigVerifier, VerifierConfig,
                                                make_example_batch)

    for batch in (8192, 16384):
        for mode in ("rlc", "strict"):
            t0 = time.perf_counter()
            v = SigVerifier(VerifierConfig(batch=batch, msg_maxlen=128),
                            mode=mode, msm_m=8)
            args = make_example_batch(batch, 128, sign_pool=16)
            ok = np.asarray(v(*args))
            t1 = time.perf_counter()
            print(f"{mode} b={batch}: compile+run {t1-t0:.1f}s "
                  f"all={ok.all()}", flush=True)
            iters = 5
            t0 = time.perf_counter()
            for _ in range(iters):
                ok = v(*args)
            np.asarray(ok)
            dt = (time.perf_counter() - t0) / iters
            print(f"{mode} b={batch}: {dt*1e3:8.2f} ms -> "
                  f"{batch/dt/1e3:8.1f} K sigs/s", flush=True)


if __name__ == "__main__":
    main()
