"""The device a run used, read once its topology has released the chip.

    python3 -m benchmark.probe '<json: {"shapes": [[b, ml], ...], "packed": 0|1}>'

Prints one JSON line: platform, kind and count as JAX reports them, and
memory_peak_bytes, the peak device memory after running the cell's verify
programs once at each of its shapes.  The programs come from the
program's own executable store (utils/aot.py) in <checkout>/.aot, which
the first run of a checkout fills; later runs load them in about a
second instead of tracing them again.  The tile exports no memory gauge
of its own, so this is the memory its programs need, not the tile's own
peak.
"""

import json
import os
import sys


def main(argv=None) -> int:
    spec = json.loads((argv or sys.argv[1:])[0])
    from functools import partial

    import jax
    import jax.numpy as jnp
    import numpy as np

    from firedancer_tpu.ops import ed25519 as ed
    from firedancer_tpu.utils import aot, xla_cache

    xla_cache.enable()
    store = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), ".aot")
    for b, ml in spec["shapes"]:
        if spec["packed"]:
            k = aot.ensure_verify_packed(store, b, ml)
            args = (np.zeros((b, ml + ed.PACKED_EXTRA), np.uint8),)
            fn = partial(ed.verify_blob, maxlen=ml, ml=ml)
        else:
            k = aot.ensure_verify(store, b, ml)
            args = (jnp.zeros((b, ml), jnp.uint8), jnp.zeros((b,), jnp.int32),
                    jnp.zeros((b, 64), jnp.uint8),
                    jnp.zeros((b, 32), jnp.uint8))
            fn = ed.verify_batch
        f = aot.load(store, k) if k else None
        (f if f is not None else jax.jit(fn))(*args).block_until_ready()
    devs = jax.devices()
    stats = [d.memory_stats() or {} for d in devs]
    print(json.dumps({
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs),
        "memory_peak_bytes": max(int(s.get("peak_bytes_in_use", 0))
                                 for s in stats)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
