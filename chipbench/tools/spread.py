#!/usr/bin/env python3
"""A builder's tool: the spread of each end-to-end metric over the two
sets that ``sets.sh`` left under ``chiprun_out/sets/<cell>/``: quartile
distance over the median (``statistics.quantiles(n=4)``), per set."""

import glob
import json
import os
import statistics
import sys


def main(cell: str) -> int:
    d = os.path.join("chiprun_out", "sets", cell)
    for s in ("A", "B"):
        vals = {}
        correct = []
        for path in sorted(glob.glob(os.path.join(d, f"{s}-*.out"))):
            with open(path) as f:
                lines = f.read().strip().splitlines()
            if not lines:
                continue
            doc = json.loads(lines[-1])
            correct.append(doc["correct"])
            for k, v in doc["metrics"].items():
                vals.setdefault(k, []).append(v["value"])
        for k, xs in vals.items():
            q = statistics.quantiles(xs, n=4)
            med = statistics.median(xs)
            rest = xs[1:] if k == "setup_s" else xs
            print(f"{cell} set {s} {k}: n={len(xs)} median={med:.4f} "
                  f"iqr/median={(q[2] - q[0]) / med:.4%} "
                  f"min={min(xs):.4f} max={max(xs):.4f} "
                  f"(median w/o first run {statistics.median(rest):.4f})")
        print(f"{cell} set {s} correct: {correct}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
