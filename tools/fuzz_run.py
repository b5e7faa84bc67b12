#!/usr/bin/env python
"""Open-ended coverage-guided fuzz runner (the local libFuzzer-loop
analogue; CI runs the bounded sweep in tests/test_fuzz_corpus.py).

    python tools/fuzz_run.py [target ...] [--iters N] [--save]

--save writes coverage-growing inputs back into tests/corpus/<target>/ so
the checked-in corpora deepen over time."""

import argparse
import os
import pathlib
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

from firedancer_tpu.utils import fuzz  # noqa: E402
from firedancer_tpu.utils.fuzz_targets import TARGETS  # noqa: E402

CORPUS = pathlib.Path(__file__).parent.parent / "tests" / "corpus"


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("targets", nargs="*", default=None)
    ap.add_argument("--iters", type=int, default=50_000)
    ap.add_argument("--save", action="store_true")
    args = ap.parse_args()
    names = args.targets or sorted(TARGETS)
    rc = 0
    for name in names:
        seeds = [p.read_bytes() for p in sorted((CORPUS / name).iterdir())]
        grown, findings = fuzz.fuzz(TARGETS[name], seeds, iters=args.iters,
                                    seed=int.from_bytes(os.urandom(4),
                                                        "little"))
        print(f"{name}: {args.iters} iters, +{len(grown)} coverage inputs, "
              f"{len(findings)} findings")
        for data, exc in findings[:10]:
            print(f"  FINDING {type(exc).__name__}: {exc} "
                  f"input={data[:48].hex()}")
            rc = 1
        if args.save:
            d = CORPUS / name
            for b in grown:
                (d / fuzz.corpus_name(b)).write_bytes(b)
    return rc


if __name__ == "__main__":
    sys.exit(main())
