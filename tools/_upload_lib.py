"""Multi-stream host->device upload (EXPERIMENT SUPPORT, not wired into
the production path: the packed single-blob dispatch in
models/verifier.py measured better — one transfer beat four chunked
streams; see tools/exp_r5_upload2.py and docs/perf_ceiling).

Role: the ingest DMA path (wiredancer pushes txns into the card over
async DMA, src/wiredancer/c/wd_f1.h:85-113).  On real PCIe a single
device_put moves GB/s and this module is a pass-through; over a slow
host link (~10-33 MB/s per stream, measured round 4/5) several
CONCURRENT streams multiplexed ~2-4x better.  So: split each array into row chunks, issue every chunk's
device_put from a thread pool, reassemble on device with one concat
(device-side copy, negligible next to the link).

The thread pool is per-process and lazy; chunked uploads of the verify
batch shapes are the intended use (bench fresh-ingest tier and the
VerifyPipeline's dispatch path).
"""

import os
from concurrent.futures import ThreadPoolExecutor

_POOL = None
_POOL_STREAMS = 0


def _pool(streams: int) -> ThreadPoolExecutor:
    global _POOL, _POOL_STREAMS
    if _POOL is None or _POOL_STREAMS < streams:
        if _POOL is not None:
            _POOL.shutdown(wait=False)
        _POOL = ThreadPoolExecutor(max_workers=streams,
                                   thread_name_prefix="fdtpu-upload")
        _POOL_STREAMS = streams
    return _POOL


def default_streams() -> int:
    return int(os.environ.get("FDTPU_UPLOAD_STREAMS", 4))


def device_put_chunked(arrays, streams: int | None = None):
    """Upload each array in `arrays` split into `streams` row-chunks
    issued concurrently; returns device arrays (reassembled by an
    on-device concatenate when chunked).

    Arrays too small to benefit (< 256 KB) upload whole.  Order of
    returned arrays matches the input."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    if streams is None:
        streams = default_streams()
    if streams <= 1:
        return [jax.device_put(a) for a in arrays]

    pool = _pool(streams)
    plans = []  # (array, [chunk bounds] or None)
    for a in arrays:
        a = np.asarray(a)
        n = a.shape[0] if a.ndim else 0
        if a.nbytes < (256 << 10) or n < streams:
            plans.append((a, None))
        else:
            step = -(-n // streams)
            plans.append((a, [(i, min(i + step, n))
                              for i in range(0, n, step)]))

    futs = []
    for a, bounds in plans:
        if bounds is None:
            futs.append([pool.submit(jax.device_put, a)])
        else:
            futs.append([pool.submit(jax.device_put, a[lo:hi])
                         for lo, hi in bounds])

    out = []
    for (a, bounds), fs in zip(plans, futs):
        chunks = [f.result() for f in fs]
        out.append(chunks[0] if bounds is None
                   else jnp.concatenate(chunks, axis=0))
    return out
