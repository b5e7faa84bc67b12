"""The cells' device programs compile for a described TPU v5e (no chip).

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_compile_v5e.py

Compiles the verify program at the shape the cell dispatches: the packed
path's blob program at (2048, packed_row_ml(256)).  It must hold the two
Pallas kernels.  Minutes of compile on the CPU; not part of the
repository's tier-1 run.  The verify graph picks its kernels from the
live backend, which is the CPU here, so each test tells it the target is
a TPU.
"""

import os
from functools import partial

import pytest

os.environ.setdefault("TPU_LOG_DIR", "disabled")


@pytest.fixture(scope="module")
def one_chip():
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding
    # a compile for a described chip cannot be read back without one
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _kernels(compiled) -> int:
    return compiled.as_text().count("tpu_custom_call")


@pytest.fixture
def tpu_target(monkeypatch):
    from firedancer_tpu.ops import ed25519 as ed
    monkeypatch.setattr(ed, "_pallas_ok", lambda batch: batch % 128 == 0)


def test_verify_blob_packed(one_chip, tpu_target):
    import jax
    import jax.numpy as jnp

    from firedancer_tpu.ops import ed25519 as ed
    from firedancer_tpu.tango.ring import packed_row_ml
    ml = packed_row_ml(256)
    blob = jax.ShapeDtypeStruct((2048, ml + ed.PACKED_EXTRA), jnp.uint8,
                                sharding=one_chip)
    compiled = jax.jit(partial(ed.verify_blob, maxlen=ml, ml=ml)).lower(
        blob).compile()
    assert _kernels(compiled) >= 2
