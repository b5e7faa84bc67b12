#!/usr/bin/env python
"""Bring-up smoke of the sigverify path on a TPU.

    python chip_smoke.py              one chip: phase A, then phase B
    python chip_smoke.py --chips 4    four chips: the dp-sharded verifier
                                      against the single-chip one, only
    python chip_smoke.py --small      the same steps at tiny sizes, for a
                                      rehearsal on the CPU: every step
                                      runs, then the device checks fail

Phase A drives the served path the way `fdtpudev bench` does: the
verify-bench topology (source -> verify -> dedup -> sink, one process per
tile) with one verify tile at batch 2048, fed 3 x 2048 distinct valid
transactions.  Every one must pass dedup before a deadline with no tile
dying; the verify tile must report a TPU and show no device failure, no
CPU fallback lane and no compile after warmup.  This process starts no
JAX backend meanwhile: the verify tile owns the chip.

Phase B runs in this process once the topology has exited: SigVerifier
at the headline shape (32768, 128) on a batch with every 7th signature
tampered.  The bits must match that pattern everywhere and the host
python-int verifier on 576 lanes, and the compiled program must hold the
Pallas kernels.

The last line of standard output is {"ok": true, "device": {...}} only
when every check passed; a failed check exits non-zero without it.  The
times printed are one smoke run's, not benchmark results.
"""

import argparse
import json
import subprocess
import sys
import time

from firedancer_tpu.app.fdtpudev import run_bench_topology

# (phase A batch, phase B batch, phase B msg width, per-chip batch of the
# four-chip check): full size, and the --small rehearsal size
SIZES = {"full": (2048, 32768, 128, 2048), "small": (128, 256, 128, 64)}


class SmokeFailure(Exception):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def say(msg: str) -> None:
    print(f"chip_smoke: {msg}", flush=True)


def preflight(chips: int) -> None:
    """Fail fast where JAX finds no TPU, before any full-size work: ask
    in a child process, which releases the chip when it exits."""
    out = subprocess.run(
        [sys.executable, "-c",
         "import jax; d = jax.devices(); print(d[0].platform, len(d))"],
        capture_output=True, text=True, timeout=600)
    check(out.returncode == 0,
          f"JAX failed to start: {out.stderr.strip()[-400:]}")
    platform, count = out.stdout.split()[-2:]
    check(platform == "tpu", f"JAX finds no TPU (platform {platform})")
    check(int(count) >= chips, f"{chips} chips needed, JAX finds {count}")


def backend_started() -> bool:
    if "jax" not in sys.modules:
        return False
    from jax._src import xla_bridge
    return xla_bridge.backends_are_initialized()


def tampered_batch(batch: int, ml: int):
    """Valid signatures except every 7th lane, which is tampered; returns
    the four verifier arrays and the expected bits."""
    import numpy as np

    from firedancer_tpu.models.verifier import make_example_batch

    msgs, lens, sigs, pubs = make_example_batch(batch, ml, valid=True,
                                                sign_pool=256)
    sigs = np.array(sigs)
    sigs[::7, 3] ^= 0xA5
    return (msgs, lens, sigs, pubs), np.arange(batch) % 7 != 0


def kernel_count(fn, *args) -> int:
    """Pallas kernels in fn's program compiled for the live backend."""
    import jax
    return jax.jit(fn).lower(*args).compile().as_text().count(
        "tpu_custom_call")


def phase_a(batch: int, device_failures: list) -> None:
    from firedancer_tpu.disco.metrics import device_platform_name

    count = 3 * batch
    say(f"phase A: verify-bench topology, 1 verify tile, batch {batch}, "
        f"{count} txns")
    t0 = time.monotonic()
    run_s, booted, end = run_bench_topology(None, count, batch=batch,
                                            timeout_s=600.0)
    total_s = time.monotonic() - t0
    v0, v = booted["verify:0"], end["verify:0"]
    platform = device_platform_name(v["device_platform"])
    say(f"phase A: boot+run {total_s:.1f} s, RUN to last txn {run_s:.1f} s, "
        f"verify tile on {platform} x{v['device_cnt']}, "
        f"batches {v['batch_cnt']}, pass {v['verify_pass_cnt']}, "
        f"fail {v['verify_fail_cnt']}, compiles at boot {v0['compile_cnt']} "
        f"and after {v['compile_cnt']}")
    check(end["dedup"]["uniq_cnt"] == count,
          f"dedup passed {end['dedup']['uniq_cnt']} txns, sent {count}")
    check(v["verify_pass_cnt"] == count and v["verify_fail_cnt"] == 0,
          f"verify passed {v['verify_pass_cnt']} and failed "
          f"{v['verify_fail_cnt']} of {count} valid txns")
    for k in ("degraded_mode", "device_fail_cnt", "fallback_lane_cnt"):
        check(v[k] == 0, f"verify tile {k} = {v[k]}: the CPU fallback ran")
    check(v["compile_cnt"] == v0["compile_cnt"],
          f"verify tile compiled after warmup ({v0['compile_cnt']} -> "
          f"{v['compile_cnt']})")
    check(not backend_started(),
          "this process started a JAX backend during phase A")
    if platform != "tpu":
        device_failures.append(f"phase A verify tile ran on {platform}")


def phase_b(batch: int, ml: int, device_failures: list) -> dict:
    import jax
    import numpy as np

    from firedancer_tpu.models.verifier import (SigVerifier, VerifierConfig,
                                                host_verify_arrays)
    from firedancer_tpu.ops import ed25519 as ed
    from firedancer_tpu.utils import xla_cache

    xla_cache.enable()
    say(f"phase B: SigVerifier ({batch}, {ml}), every 7th sig tampered")
    args, want = tampered_batch(batch, ml)
    t0 = time.monotonic()
    kernels = kernel_count(ed.verify_batch, *args)
    compile_s = time.monotonic() - t0
    verifier = SigVerifier(VerifierConfig(batch=batch, msg_maxlen=ml))
    t0 = time.monotonic()
    got = np.asarray(verifier(*args))
    first_s = time.monotonic() - t0
    t0 = time.monotonic()
    again = np.asarray(verifier(*args))
    call_s = time.monotonic() - t0
    lanes = np.unique(np.concatenate(
        [np.arange(64), np.arange(0, batch, max(1, batch // 512))]))
    t0 = time.monotonic()
    host = host_verify_arrays(*(np.asarray(a)[lanes] for a in args))
    host_s = time.monotonic() - t0
    say(f"phase B: compile {compile_s:.1f} s ({kernels} tpu_custom_call), "
        f"first call {first_s:.2f} s, second call {call_s * 1e3:.1f} ms, "
        f"host twin {len(lanes)} lanes ({int((~want[lanes]).sum())} "
        f"tampered) {host_s:.1f} s")
    check(got.shape == want.shape and bool((got == want).all()),
          f"{int((got != want).sum())} of {batch} lanes differ from the "
          "expected bits")
    check(bool((again == got).all()), "a second call changed the bits")
    check(bool((host == got[lanes]).all()),
          f"{int((host != got[lanes]).sum())} of {len(lanes)} lanes differ "
          "from the host verifier")
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        device_failures.append(f"phase B ran on {dev.platform}")
    if kernels < 2:
        device_failures.append(
            f"phase B program has {kernels} Pallas kernels, not 2")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}


def four_chips(per_chip: int, ml: int, device_failures: list) -> dict:
    import jax
    import numpy as np

    from firedancer_tpu.models.verifier import SigVerifier, VerifierConfig
    from firedancer_tpu.parallel import mesh as pm
    from firedancer_tpu.utils import xla_cache

    xla_cache.enable()
    n = len(jax.devices())
    check(n == 4, f"--chips 4 needs 4 devices, JAX finds {n}")
    batch = 4 * per_chip
    say(f"four chips: dp mesh SigVerifier vs device 0, ({batch}, {ml}), "
        "every 7th sig tampered")
    args, want = tampered_batch(batch, ml)
    cfg = VerifierConfig(batch=batch, msg_maxlen=ml)
    mesh = pm.make_mesh(4)
    t0 = time.monotonic()
    kernels = pm.shard_verify_step(mesh).lower(
        *pm.shard_batch(mesh, *args)).compile().as_text().count(
            "tpu_custom_call")
    compile_s = time.monotonic() - t0
    single = SigVerifier(cfg)
    sharded = SigVerifier(cfg, mesh=mesh)
    t0 = time.monotonic()
    one = np.asarray(single(*args))
    one_s = time.monotonic() - t0
    t0 = time.monotonic()
    four = np.asarray(sharded(*args))
    four_s = time.monotonic() - t0
    t0 = time.monotonic()
    four_packed = np.asarray(sharded.packed_dispatch(*args))
    packed_s = time.monotonic() - t0
    say(f"four chips: sharded step compile {compile_s:.1f} s ({kernels} "
        f"tpu_custom_call); first calls: device 0 {one_s:.1f} s, mesh "
        f"{four_s:.1f} s, mesh packed {packed_s:.1f} s; pass "
        f"{int(four.sum())} of {batch}")
    check(bool((one == want).all()),
          f"device 0: {int((one != want).sum())} lanes differ from the "
          "expected bits")
    check(bool((four == one).all()),
          f"mesh vs device 0: {int((four != one).sum())} lanes differ")
    check(bool((four_packed == one).all()),
          f"mesh packed vs device 0: {int((four_packed != one).sum())} "
          "lanes differ")
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        device_failures.append(f"four-chip check ran on {dev.platform}")
    if kernels < 2:
        device_failures.append(
            f"sharded program has {kernels} Pallas kernels, not 2")
    return {"platform": dev.platform, "kind": dev.device_kind, "count": n}


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--chips", type=int, choices=(1, 4), default=1)
    p.add_argument("--small", action="store_true",
                   help="tiny sizes, for a rehearsal on the CPU")
    a = p.parse_args(argv)
    a_batch, b_batch, ml, per_chip = SIZES["small" if a.small else "full"]
    device_failures: list[str] = []
    try:
        if not a.small:
            preflight(a.chips)
        if a.chips == 4:
            device = four_chips(per_chip, ml, device_failures)
        else:
            phase_a(a_batch, device_failures)
            device = phase_b(b_batch, ml, device_failures)
        check(not device_failures, "; ".join(device_failures))
    except SmokeFailure as e:
        say(f"FAIL: {e}")
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
