"""Find the highest rate an open-loop cell's system sustains.

    python3 -m benchmark.sweep --workload <cell> --seed <n> \\
        --seconds <s> --rates 10000,20000,30000

One run per rate, each with a fresh topology and the cell's mix at that
rate.  Prints one JSON line per rate: the rate offered, sigs_per_s, p50 and
p99 of the verdict latency, the p50 of the window's first and last
quarters, and the correctness counts.  A rate is sustained when every
check passes and the last quarter's p50 is under twice the first's (the
queue does not grow through the window).  The cell's rate is then set by
hand to four fifths of the highest sustained one.
"""

import argparse
import json
import sys
import time


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, default=8.0)
    p.add_argument("--rates", required=True)
    a = p.parse_args(argv)
    import numpy as np

    from benchmark import cells, check, gen, harness
    from benchmark.run import ROOT
    harness.use_cache_dir(ROOT)
    for i, rate in enumerate(float(r) for r in a.rates.split(",")):
        cell = cells.resolve(a.workload)
        cell.mix = dict(cell.mix, rate_txn_s=rate)
        t0 = time.monotonic()
        rec = harness.run(cell, a.seed + i, a.seconds, False, t0,
                          log=lambda m: print(m, file=sys.stderr))
        checks, deliv, failed = check.decide(rec, a.seed + i)
        e2e = check.end_to_end(rec, deliv, a.seconds)
        win = check.window_mask(rec) & (rec.send_outcome == gen.PASS)
        q = (rec.w1 - rec.w0) // 4
        lat = np.where(deliv >= 0, (deliv - rec.send_due) / 1e6, np.inf)
        first = win & (rec.send_due < rec.w0 + q)
        last = win & (rec.send_due >= rec.w1 - q)
        p50_first = check.percentile(lat[first], 50)
        p50_last = check.percentile(lat[last], 50)
        ok = all(v <= lim for v, lim in checks.values())
        print(json.dumps({
            "rate_txn_s": rate, **e2e, "p50_first_ms": p50_first,
            "p50_last_ms": p50_last, "failed": failed,
            "sustained": bool(ok and p50_last < 2 * p50_first),
            "checks": {k: v for k, (v, _) in checks.items()}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
