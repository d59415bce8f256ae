#!/usr/bin/env python3
"""The benchmark's command.

    python3 chipbench/run.py --workload NAME --seed N --seconds S --trace 0|1

Runs one cell of ``BENCHMARK.json`` on the machine it is started on and
prints, as the last line of standard output, one JSON object with
``correct``, ``attempted``, ``failed``, ``metrics`` and ``device``
(``--trace 0``: the end-to-end metrics; ``--trace 1``: the per-layer
ones, with ``breakdown``). Without the TPU chips the cell asks for it
exits non-zero and prints no result.
"""

import time

T_PROCESS_START = time.time()

import argparse     # noqa: E402
import os           # noqa: E402
import sys          # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", action="store_true",
                    help="leave the profiler's files under chipbench/work")
    args = ap.parse_args(argv)
    from chipbench import harness
    if not os.path.isdir(os.path.join(ROOT, "paddle_tpu")):
        print("chipbench: the system under test (paddle_tpu/) is not in "
              f"{ROOT}; the benchmark alone measures nothing.",
              file=sys.stderr)
        return 4
    cell = harness.Cell(args.workload)
    device = harness.require_tpu(cell.chips)
    system = harness.load_module(
        os.path.join(harness.HERE, "systems", f"{cell.config['system']}.py"),
        f"chipbench_system_{cell.config['system']}")
    return system.run(cell, seed=args.seed, seconds=args.seconds,
                      trace=bool(args.trace), device=device,
                      t_start=T_PROCESS_START, keep_trace=args.keep_trace)


if __name__ == "__main__":
    sys.exit(main())
