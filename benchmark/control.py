"""The control: the plain reference with its canonical-S rule dropped,
put in the program's place, judged by the same comparison as a run.

    python3 -m benchmark.control --workload <cell> --seeds 1,2,3 \\
        [--seconds 20] [--sends N]

For each seed it makes the cell's traffic at the cell's own size (an open
loop's whole schedule for the window; a closed loop's pool, sent once
round), builds the verdict stream the lax verifier would publish, and
prints the numbers `check.decide` compares.  The control must come out
not correct: `wrong_accept` counts the sends it accepts that the strict
configuration rejects (S + L signatures).  No chip is needed: the
verdicts are the reference's, computed on the host.
"""

import argparse
import json
import math
import sys


def control_checks(cell, seed: int, seconds: float, depth: int,
                   workers: int = 4) -> dict:
    import multiprocessing as mp

    import numpy as np

    from benchmark import check, gen

    mix = cell.mix
    pool_min = math.ceil(float(mix.get("pool_over_tcache", 0)) * depth)
    pool, sched, nkeys = gen.plan(mix, seed, seconds, pool_min)
    pubs = gen.key_pubs(seed, nkeys)
    with mp.get_context("spawn").Pool(workers) as w:
        parts = w.map(gen.build_slice, [
            (pool.slice(lo, hi), pubs)
            for lo, hi in gen.slices(len(pool.nsig), workers * 4)])
    tr = gen.assemble_traffic(pool, sched, parts)
    if sched is not None:
        send_pool, due, outcome = tr.send_pool, tr.send_due, tr.send_outcome
        due = (due * 1e9).astype(np.int64)
    else:
        send_pool = np.arange(len(pool.nsig), dtype=np.int64)
        due = np.arange(len(send_pool), dtype=np.int64) * 10_000
        outcome = np.where(tr.bad >= 0, gen.FAIL, gen.PASS).astype(np.int8)
    obs = check.control_observed(tr, send_pool, outcome, due)
    deliv, bad = check.match(tr, send_pool, outcome, obs)
    missing = int(((outcome == gen.PASS) & (deliv < 0)).sum())
    return {"seed": seed, "sends": len(send_pool), "missing": missing,
            **bad, "correct": missing == 0 and not any(bad.values())}


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--seconds", type=float, default=None)
    a = p.parse_args(argv)
    from benchmark import cells, harness
    bench = cells.load_benchmark()
    cell = cells.resolve(a.workload, bench=bench)
    seconds = a.seconds or float(bench["run_seconds"])
    cfg = harness.topology_config(cell.config["topology"])
    depth = max(int(cfg["tiles"]["dedup"]["tcache_depth"]),
                int(cfg["tiles"]["verify"]["tcache_depth"]))
    for s in a.seeds.split(","):
        print(json.dumps(control_checks(cell, int(s), seconds, depth)),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
