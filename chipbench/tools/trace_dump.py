#!/usr/bin/env python3
"""A builder's tool: what a kept profiler trace holds, by hand.

    python3 chipbench/tools/trace_dump.py [DIR] > summary.txt

Lists every plane and line with its event count, the programs on the
``XLA Modules`` line and the 40 ops that took most device time, and
writes a small recorded slice (``--record FILE``) that the tests reduce.
"""

import argparse
import glob
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("dir", nargs="?",
                    default=os.path.join(ROOT, "chipbench", "work", "trace"))
    ap.add_argument("--record", default=None)
    ap.add_argument("--record-ms", type=float, default=60.0)
    args = ap.parse_args(argv)
    import jax
    from chipbench import trace
    files = glob.glob(os.path.join(args.dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        print(f"no xplane file under {args.dir}")
        return 1
    prof = jax.profiler.ProfileData.from_file(files[0])
    for pl in prof.planes:
        print("PLANE", pl.name)
        for ln in pl.lines:
            evs = list(ln.events)
            print(f"  LINE {ln.name!r}: {len(evs)} events")
            for e in evs[:3]:
                print("     ", e.name[:100], e.start_ns, e.duration_ns,
                      {k: str(v)[:60] for k, v in list(e.stats)[:8]})
    planes = trace.planes_of(prof)
    red = trace.reduce_planes(planes, 0)
    print("busy_s", red["busy_s"], "chips", red["chips_traced"])
    print("MODULES")
    for name, (t, n) in sorted(red["modules"].items(), key=lambda kv: -kv[1][0]):
        print(f"  {t:9.4f}s {n:6d}x  {name}")
    print("OPS (top 40)")
    for name, (t, n) in sorted(red["ops"].items(), key=lambda kv: -kv[1][0])[:40]:
        print(f"  {t:9.4f}s {n:7d}x  {name}")
    for mod, ops in red["ops_by_module"].items():
        print("OPS OF", mod)
        for name, (t, n) in sorted(ops.items(), key=lambda kv: -kv[1][0])[:12]:
            print(f"  {t:9.4f}s {n:7d}x  {name}")
    print("BREAKDOWN", json.dumps(red["breakdown"], indent=1))
    if args.record:
        dev = [p for p in planes if trace.DEVICE_PLANE.match(p["name"])]
        t0 = min(e[1] for p in dev for ln in p["lines"] for e in ln["events"])
        t1 = t0 + args.record_ms * 1e6
        small = []
        for p in planes:
            lines = []
            for ln in p["lines"]:
                evs = [e for e in ln["events"] if t0 <= e[1] < t1]
                if evs:
                    lines.append({"name": ln["name"], "events": evs[:4000]})
            if lines:
                small.append({"name": p["name"], "lines": lines})
        with open(args.record, "w") as f:
            json.dump(small, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
