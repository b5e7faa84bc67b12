"""Run one cell of BENCHMARK.json once and print its result line.

    python3 -m benchmark.run --workload <config>.<mix> --seed <n> \\
        --seconds <s> --trace <0|1>

The last line of standard output is one JSON object: correct, attempted,
failed, metrics (the cell's end-to-end metrics with --trace 0, its
per-layer metrics with --trace 1), device and, traced, breakdown; its
last key, checks, holds each number compared beside its limit, which are
also the last lines on standard error.  A run whose verify tile is not on
a TPU, or that finds fewer chips than the cell asks for, prints no result
and exits 3.  JAX's compile cache is kept in <checkout>/.xla_cache.
"""

import argparse
import json
import multiprocessing
import os
import subprocess
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent

# spawn re-imports this module as __mp_main__ in every tile process; in the
# verify tile of a traced run, keep the profiler's Python tracer off
if (__name__ == "__mp_main__" and os.environ.get("FDTPU_JAX_TRACE_DIR")
        and multiprocessing.current_process().name == "fdtpu:verify:0"):
    from benchmark import tracehook
    tracehook.install()


def err(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def probe_device(cfg: dict) -> dict:
    """Platform, kind, count and memory from a process of its own, once
    the topology has released the chip."""
    from benchmark.harness import bucket_shapes
    shapes = bucket_shapes(cfg)
    packed = int(cfg["quic"]["packed_publish"])
    out = subprocess.run(
        [sys.executable, "-m", "benchmark.probe",
         json.dumps({"shapes": shapes, "packed": packed})],
        cwd=ROOT, capture_output=True, text=True, timeout=600)
    if out.returncode != 0:
        raise RuntimeError("device probe failed: " + out.stderr[-2000:])
    return json.loads(out.stdout.strip().splitlines()[-1])


def measure(cell, seed: int, seconds: float, trace: bool,
            t_start: float, require_tpu: bool = True,
            topo_extra: dict | None = None) -> tuple[dict, dict]:
    """One run: (result line without device, the probe's device record or
    {} where require_tpu is off)."""
    from benchmark import check, harness, reduce
    from benchmark.cells import reader

    rec = harness.run(cell, seed, seconds, trace, t_start, require_tpu,
                      topo_extra, log=err)
    checks, deliv, failed = check.decide(rec, seed)
    win = check.window_mask(rec)
    result = {"correct": all(v <= lim for v, lim in checks.values()),
              "attempted": int(win.sum()), "failed": int(failed)}
    device = {}
    cfg = harness.topology_config(cell.config["topology"], topo_extra)
    if require_tpu:
        device = probe_device(cfg)
    metrics = {}
    if not trace:
        e2e = check.end_to_end(rec, deliv, seconds)
        for m in cell.end_to_end:
            if m["name"] in e2e:
                metrics[m["name"]] = {"value": e2e[m["name"]],
                                      "unit": m["unit"]}
    else:
        tr = reduce.load(rec.trace_dir) if rec.trace_dir else None
        view = View(rec, deliv, tr, seconds)
        for m in cell.per_layer:
            v = reader(m["name"])(view)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        if tr is not None:
            c0, c1 = reduce.covered(tr, rec.w0_real, rec.w1_real)
            device["busy_s"] = reduce.busy_s(tr, c0, c1)
            device["window_s"] = (c1 - c0) / 1e9
            ops = sorted(reduce.op_seconds(tr, c0, c1).items(),
                         key=lambda kv: -kv[1])[:10]
            result["breakdown"] = {
                "device_ops": [[k[:120], v] for k, v in ops],
                "idle_gaps": reduce.idle_gaps(tr, c0, c1)}
        if rec.trace_dir:
            import shutil
            shutil.rmtree(rec.trace_dir, ignore_errors=True)
    result["metrics"] = metrics
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in checks.items()}
    return result, device


class View:
    """What a per-layer reader (benchmark/metrics/<name>.py) reads."""

    def __init__(self, rec, deliv, trace, seconds):
        self.rec = rec              # harness.RunRecord
        self.deliv = deliv          # verdict time per send, -1 for none
        self.trace = trace          # reduce.Trace, or None
        self.seconds = seconds


def main(argv=None) -> int:
    t_start = time.monotonic()
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = p.parse_args(argv)
    try:
        from benchmark import cells
        cell = cells.resolve(a.workload)
        import firedancer_tpu  # noqa: F401  (the system under test)
    except (OSError, KeyError, ValueError, ImportError) as e:
        err(f"bench: cannot resolve {a.workload!r}: {e!r}")
        return 2
    from benchmark.harness import NoDevice, use_cache_dir
    use_cache_dir(ROOT)
    try:
        result, device = measure(cell, a.seed, a.seconds, bool(a.trace),
                                 t_start)
    except NoDevice as e:
        err(f"bench: {e}")
        return 3
    if device.get("platform") != "tpu" or device.get("count", 0) < cell.chips:
        err(f"bench: JAX finds {device.get('count')} x "
            f"{device.get('platform')}; the cell needs {cell.chips} TPU")
        return 3
    checks = result.pop("checks")
    result["device"] = {k: device[k] for k in (
        "platform", "kind", "count", "memory_peak_bytes", "busy_s",
        "window_s") if k in device}
    result["checks"] = checks
    for k, c in checks.items():
        err(f"check {k}: {c['value']} (limit {c['limit']})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
