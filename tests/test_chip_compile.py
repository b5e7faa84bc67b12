"""The main path's Pallas kernels compile for a TPU v5e at the verify
tile's width, with no chip attached: the TPU compiler is installed and
compiles for a described topology.  This catches what interpret mode
cannot (tiling, VMEM limits, shard_map typing) at no chip time.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and under several test workers the
one that runs this file keeps it.  All such compiles live in this file
for that reason."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

BATCH = 2048  # the verify tile's batch in the served configuration


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            t = topologies.get_topology_desc(platform="tpu",
                                             topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip cannot be read back without one:
        # keep these out of the persistent cache
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        try:
            yield t
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _kernels(fn, *args) -> int:
    return jax.jit(fn).lower(*args).compile().as_text().count(
        "tpu_custom_call")


def test_sha512_kernel_compiles(one_chip):
    from firedancer_tpu.ops import sha512_pallas as shp

    msgs = jax.ShapeDtypeStruct((BATCH, 192), jnp.uint8, sharding=one_chip)
    lens = jax.ShapeDtypeStruct((BATCH,), jnp.int32, sharding=one_chip)
    assert _kernels(shp.sha512, msgs, lens) >= 1


def test_sha512_kernel_compiles_at_mtu_row_width(one_chip):
    """The verify hash R | A | msg at the widest packed row, ml 1180
    (msg_maxlen 1167, the longest message a 1232-byte packet holds):
    ten 128-byte blocks a lane."""
    from firedancer_tpu.ops import sha512_pallas as shp

    msgs = jax.ShapeDtypeStruct((BATCH, 64 + 1180), jnp.uint8,
                                sharding=one_chip)
    lens = jax.ShapeDtypeStruct((BATCH,), jnp.int32, sharding=one_chip)
    assert _kernels(shp.sha512, msgs, lens) >= 1


def test_fused_verify_tail_compiles(one_chip):
    from firedancer_tpu.ops import curve_pallas as cpal

    def s(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    def tail(pub, sb, dig, yr):
        return cpal.verify_tail_fused(pub, sb, dig, yr, blk=128)

    assert _kernels(tail, s((BATCH, 32), jnp.uint8),
                    s((BATCH, 32), jnp.uint8), s((BATCH, 64), jnp.uint8),
                    s((22, BATCH), jnp.uint32)) >= 1


def test_sha512_kernel_compiles_under_shard_map(topo):
    """The dp-sharded verify step runs the kernels inside jax.shard_map,
    which refuses a pallas_call whose out_shape names no varying axes."""
    from firedancer_tpu.ops import sha512_pallas as shp

    mesh = Mesh(np.array(topo.devices[:4]), ("dp",))
    step = jax.shard_map(shp.sha512, mesh=mesh,
                         in_specs=(P("dp", None), P("dp")),
                         out_specs=P("dp", None))
    msgs = jax.ShapeDtypeStruct((4 * BATCH, 192), jnp.uint8,
                                sharding=NamedSharding(mesh, P("dp", None)))
    lens = jax.ShapeDtypeStruct((4 * BATCH,), jnp.int32,
                                sharding=NamedSharding(mesh, P("dp")))
    assert _kernels(step, msgs, lens) >= 1
