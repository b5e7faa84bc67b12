"""Device mesh construction and the data-parallel verify shard.

The reference scales the verify stage by round-robin sharding frags across N
verify tile processes (ref: src/app/fdctl/run/tiles/fd_verify.c:36-47,
round_robin_cnt/idx from the topology).  The TPU-native equivalent is a
1-D 'dp' mesh with the batch axis sharded across chips: each chip verifies
its shard independently (embarrassingly parallel, no cross-chip reduction on
the hot path — matching the reference, where verify tiles never talk to each
other), with a psum only for aggregate metrics (pass counts), riding ICI.
"""

import warnings
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# the sharded packed-blob step donates its input buffer (steady-state
# dispatch reuses the uploaded blob's pages for outputs/intermediates);
# backends that cannot alias (jax CPU) warn per-execution instead of
# failing — silence exactly that warning, donation is best-effort there
warnings.filterwarnings(
    "ignore", message="Some donated buffers were not usable")

from firedancer_tpu.ops import ed25519 as ed


def make_mesh(n_devices: int | None = None, axis: str = "dp") -> Mesh:
    devs = jax.devices()
    n = n_devices or len(devs)
    if len(devs) < n:
        raise ValueError(f"need {n} devices, have {len(devs)}")
    return Mesh(np.array(devs[:n]), (axis,))


def shard_verify_step(mesh: Mesh, mode: str = "strict"):
    """Build the jitted multi-chip verify step.

    Returns fn(msgs, msg_len, sigs, pubkeys) -> (ok_bits, pass_count) with
    batch sharded over 'dp'; pass_count is psum'd across the mesh (the
    monitoring aggregate, ref fd_metrics counters).  `mode` picks the
    per-lane graph: strict (ed.verify_batch) or antipa (the round-9
    halved-scalar chain) — lane parallelism is identical either way."""
    batch_fn = (ed.verify_batch_antipa if mode == "antipa"
                else ed.verify_batch)

    def local_step(msgs, msg_len, sigs, pubkeys):
        ok = batch_fn(msgs, msg_len, sigs, pubkeys)
        passes = jax.lax.psum(jnp.sum(ok.astype(jnp.uint32)), "dp")
        return ok, passes

    shard = jax.shard_map(
        local_step,
        mesh=mesh,
        in_specs=(P("dp", None), P("dp"), P("dp", None), P("dp", None)),
        out_specs=(P("dp"), P()),
    )
    return jax.jit(shard)


def shard_batch(mesh: Mesh, *arrays):
    """Place host arrays with the batch axis sharded over the mesh."""
    out = []
    for a in arrays:
        spec = P("dp", *([None] * (a.ndim - 1)))
        out.append(jax.device_put(a, NamedSharding(mesh, spec)))
    return tuple(out)


def blob_sharding(mesh: Mesh, axis: str = "dp") -> NamedSharding:
    """The packed-blob placement: rows (lanes) sharded over the mesh,
    columns (the msgs|sig|pub|len row layout) replicated per shard.  One
    host `device_put` against this sharding splits the contiguous blob
    into per-device row slices — the multi-chip ingest upload shape."""
    return NamedSharding(mesh, P(axis, None))


def pad_rows(arr: np.ndarray, n: int) -> np.ndarray:
    """Pad a host batch's leading (lane) axis to a multiple of the shard
    count with zero rows.  Zero lanes are additionally masked on device
    by shard_verify_blob's true_rows so a padded dispatch can never
    surface a pass bit for a lane nobody submitted."""
    rem = (-arr.shape[0]) % n
    if not rem:
        return arr
    return np.concatenate(
        [arr, np.zeros((rem,) + arr.shape[1:], dtype=arr.dtype)])


def shard_verify_blob(mesh: Mesh, maxlen: int, ml: int | None = None,
                      true_rows: int | None = None, axis: str = "dp",
                      donate: bool = True, mode: str = "strict"):
    """Build the jitted multi-chip PACKED verify step — the serving-path
    twin of shard_verify_step over the single-blob row layout
    (ops.ed25519.verify_blob): fn(blob sharded P(dp, None)) -> ok bits
    sharded P(dp).

    Each chip verifies its row shard independently (the reference's
    round-robin verify tiles, fd_verify.c:36-47 — no cross-chip traffic
    on the hot path).  `true_rows` statically masks trailing padding
    lanes (a global batch not divisible by the mesh is padded host-side
    by pad_rows; the mask guarantees those lanes read False).  The blob
    argument is DONATED: steady-state dispatch reuses the uploaded
    buffer's device memory for the step's intermediates instead of
    allocating per call."""
    ml = maxlen if ml is None else ml
    n = mesh.shape[axis]
    blob_fn = (ed.verify_blob_antipa if mode == "antipa"
               else ed.verify_blob)

    def local(blob):
        ok = blob_fn(blob, maxlen=maxlen, ml=ml)
        if true_rows is not None:
            rows = blob.shape[0]  # per-shard rows (global // n)
            lane0 = jax.lax.axis_index(axis).astype(jnp.int32) * rows
            ok &= (lane0 + jnp.arange(rows, dtype=jnp.int32)) < true_rows
        return ok

    shard = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(axis, None),), out_specs=P(axis))
    return jax.jit(shard, donate_argnums=(0,) if donate else ())
