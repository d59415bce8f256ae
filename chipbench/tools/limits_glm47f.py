#!/usr/bin/env python3
"""A builder's tool: ``tools/limits_q3next.py``'s readings for the cells
of ``systems/glm47f_serving.py`` (that tool's ``main`` is tied to the
Qwen3-Next system module; its ``collect``, ``show`` and ``save`` are
used as they are).

    python3 chipbench/tools/limits_glm47f.py --workload NAME --seeds 4 \
        --controls 4 --seconds S --budget-s B

One process, one set-up: for each seed a window at the cell's own load
through the timed path, then the reference over the same sample (the
program's reading); on the first ``--controls`` seeds also, at the same
positions, the fp8 and int8 controls (the reference in a lower
precision, put in the program's place) and every planted fault of
``references/glm4_moe_lite.FAULTS`` (the reference with the mistake a
new part would make, put in the program's place): no second window, the
faults are the reference's. Every reading goes through
``compare.judge`` with the committed limits and is recorded with its
``ok``: the program's have to read true, a control's or a fault's false
on one number at least, or the tool exits with 1. Writes everything to
``chiprun_out/limits/<workload>.json`` after every seed.
"""

import argparse
import os
import sys
import time

T0 = time.time()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)


class WithFaults:
    """The system module, whose every control reading also reads the
    reference's planted faults."""

    def __init__(self, system):
        self._system = system

    def __getattr__(self, name):
        return getattr(self._system, name)

    def served_gaps(self, weights, sample, config, controls=(),
                    keep_gaps=False):
        from chipbench.references import glm4_moe_lite as ref
        more = ref.FAULTS if controls else ()
        return self._system.served_gaps(
            weights, sample, config, controls=tuple(controls) + more,
            keep_gaps=keep_gaps)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=4)
    ap.add_argument("--controls", type=int, default=4)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--budget-s", type=float, default=3000.0)
    args = ap.parse_args(argv)
    args.faults = 0         # none is planted in the engine
    from chipbench import harness
    from chipbench.systems import glm47f_serving as system
    limits = harness.load_module(os.path.join(HERE, "limits.py"),
                                 "chipbench_tools_limits")
    tool = harness.load_module(os.path.join(HERE, "limits_q3next.py"),
                               "chipbench_tools_limits_q3next")
    cell = harness.Cell(args.workload)
    out = {"workload": args.workload,
           "device": harness.require_tpu(cell.chips), "rows": [],
           "faults": [], "verdicts": []}
    try:
        tool.collect(WithFaults(system), cell, args, out,
                     limits.SEEDS[:args.seeds], harness.WORK, limits.judged)
    finally:
        tool.save(out, args.workload)
    print("took", time.time() - T0)
    wrong = [(who, ok) for who, sound, ok in out["verdicts"] if ok != sound]
    for who, ok in wrong:
        print(f"WRONG VERDICT under the committed limits: {who} read "
              f"ok={ok}", file=sys.stderr)
    return 1 if wrong else 0


if __name__ == "__main__":
    sys.exit(main())
