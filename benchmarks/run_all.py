#!/usr/bin/env python
"""Run the published-baseline benchmark sweep and write BENCHMARKS.md.

Mirrors the reference's benchmark drivers (benchmark/paddle/image/run.sh:
`paddle train --job=time` over alexnet/googlenet/smallnet/vgg/resnet and
benchmark/paddle/rnn/run.sh's LSTM hidden/batch sweep), comparing against
the K40m numbers recorded in BASELINE.md.

Usage:
  python benchmarks/run_all.py                 # full sweep
  python benchmarks/run_all.py --suite=lstm    # one suite
  python benchmarks/run_all.py --quick         # tiny batches, smoke test
  JAX_PLATFORMS=cpu python benchmarks/...      # force a JAX platform

Each (config, batch) measurement runs in a fresh subprocess so one OOM or
hang cannot take down the sweep; results stream to benchmarks/results.json
and BENCHMARKS.md is (re)written at the end.
"""

import argparse
import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIGS = os.path.join(REPO, "benchmarks", "configs")

# (suite, config file, env overrides, baseline ms/batch or None, baseline note)
K40 = "1xK40m (BASELINE.md)"
SWEEP = [
    ("alexnet", {"BENCH_BATCH": "64"}, 195.0, K40),
    ("alexnet", {"BENCH_BATCH": "128"}, 334.0, K40),
    ("alexnet", {"BENCH_BATCH": "256"}, 602.0, K40),
    ("alexnet", {"BENCH_BATCH": "512"}, 1629.0, K40),
    ("googlenet", {"BENCH_BATCH": "64"}, 613.0, K40),
    ("googlenet", {"BENCH_BATCH": "128"}, 1149.0, K40),
    ("googlenet", {"BENCH_BATCH": "256"}, 2348.0, K40),
    ("smallnet", {"BENCH_BATCH": "64"}, 10.463, K40),
    ("smallnet", {"BENCH_BATCH": "128"}, 18.184, K40),
    ("smallnet", {"BENCH_BATCH": "256"}, 33.113, K40),
    ("smallnet", {"BENCH_BATCH": "512"}, 63.039, K40),
    ("vgg19", {"BENCH_BATCH": "64"}, 64000 / 27.69, "2xXeon6148 MKL-DNN"),
    ("vgg19", {"BENCH_BATCH": "128"}, 128000 / 28.8, "2xXeon6148 MKL-DNN"),
    ("vgg19", {"BENCH_BATCH": "256"}, 256000 / 29.27, "2xXeon6148 MKL-DNN"),
    ("resnet50", {"BENCH_BATCH": "128"}, None, "north star 4000 img/s"),
    ("resnet50", {"BENCH_BATCH": "256"}, None, "north star 4000 img/s"),
    ("resnet50", {"BENCH_BATCH": "128", "BENCH_FUSED_BN": "defer"}, None,
     "north star 4000 img/s"),
    ("resnet50", {"BENCH_BATCH": "256", "BENCH_FUSED_BN": "defer"}, None,
     "north star 4000 img/s"),
    ("resnet50", {"BENCH_BATCH": "128", "BENCH_FUSED_BN": "q8"}, None,
     "north star 4000 img/s"),
    ("resnet50", {"BENCH_BATCH": "256", "BENCH_FUSED_BN": "q8"}, None,
     "north star 4000 img/s"),
    ("lstm", {"BENCH_BATCH": "64", "BENCH_HIDDEN": "256"}, 83.0, K40),
    ("lstm", {"BENCH_BATCH": "64", "BENCH_HIDDEN": "512"}, 184.0, K40),
    ("lstm", {"BENCH_BATCH": "64", "BENCH_HIDDEN": "1280"}, 641.0, K40),
    ("lstm", {"BENCH_BATCH": "128", "BENCH_HIDDEN": "256"}, 110.0, K40),
    ("lstm", {"BENCH_BATCH": "128", "BENCH_HIDDEN": "512"}, 261.0, K40),
    ("lstm", {"BENCH_BATCH": "128", "BENCH_HIDDEN": "1280"}, 1007.0, K40),
    ("lstm", {"BENCH_BATCH": "256", "BENCH_HIDDEN": "256"}, 170.0, K40),
    ("lstm", {"BENCH_BATCH": "256", "BENCH_HIDDEN": "512"}, 414.0, K40),
    ("lstm", {"BENCH_BATCH": "256", "BENCH_HIDDEN": "1280"}, 1655.0, K40),
    ("ctr", {"BENCH_BATCH": "256"}, None, "BASELINE config 5"),
]

CHILD = r"""
import json, os, sys
sys.path.insert(0, {repo!r})
import jax
from paddle_tpu import cli
cfg = cli._load_config({config!r})
print("BENCHDEVICE " + jax.devices()[0].device_kind)
r = cli.measure_time(cfg, time_batches={timed}, warmup_batches={warmup})
print("BENCHRESULT " + json.dumps(r))
"""


def run_one(suite, env_over, timed, warmup, timeout):
    config = os.path.join(CONFIGS, f"{suite}.py")
    env = dict(os.environ, **env_over)
    script = CHILD.format(repo=REPO, config=config, timed=timed,
                          warmup=warmup)
    t0 = time.time()
    try:
        r = subprocess.run([sys.executable, "-c", script], env=env,
                           capture_output=True, text=True, timeout=timeout)
    except subprocess.TimeoutExpired:
        return {"error": f"timeout >{timeout}s"}
    out = {}
    for line in r.stdout.splitlines():
        if line.startswith("BENCHDEVICE "):
            out["device_kind"] = line[len("BENCHDEVICE "):].strip()
        if line.startswith("BENCHRESULT "):
            out.update(json.loads(line[len("BENCHRESULT "):]))
    if out.get("ms_per_batch") is not None:
        return out
    tail = (r.stderr or "").strip().splitlines()[-5:]
    return {"error": f"rc={r.returncode} after {time.time()-t0:.0f}s: "
            + " | ".join(tail)}


def write_md(results, path):
    lines = [
        "# BENCHMARKS — measured vs reference baseline",
        "",
        "Protocol: steady-state train-step ms/batch via `cli.measure_time`",
        "(the `--job=time` protocol, benchmark/paddle/image/run.sh:9-17),",
        "synthetic device-resident data, fresh process per point.",
        "",
        f"Platform: {results.get('platform', '?')}, "
        f"device: {results.get('device', '?')}",
        "",
        "| suite | settings | ms/batch | examples/sec | baseline ms/batch "
        "| speedup | baseline hw |",
        "|---|---|---|---|---|---|---|",
    ]
    for rec in results["points"]:
        s = rec.get("settings", {})
        sstr = " ".join(f"{k.replace('BENCH_', '').lower()}={v}"
                        for k, v in s.items())
        r = rec.get("result", {})
        if "error" in r:
            lines.append(f"| {rec['suite']} | {sstr} | ERROR: {r['error']} "
                         f"| | | | {rec['note']} |")
            continue
        base = rec.get("baseline_ms")
        speed = (f"{base / r['ms_per_batch']:.1f}x"
                 if base and r.get("ms_per_batch") else "")
        lines.append(
            f"| {rec['suite']} | {sstr} | {r['ms_per_batch']:.2f} | "
            f"{r['examples_per_sec']:.1f} | "
            f"{f'{base:g}' if base is not None else '—'} | {speed} | "
            f"{rec['note']} |")
    # hand-maintained analysis (MFU, roofline, profile findings) survives
    # regeneration: kept in benchmarks/analysis.md and appended verbatim
    analysis = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "analysis.md")
    if os.path.exists(analysis):
        with open(analysis) as f:
            lines += ["", f.read().rstrip()]
    lines += ["", f"_Generated by benchmarks/run_all.py, "
              f"{time.strftime('%Y-%m-%d %H:%M:%S')}_", ""]
    with open(path, "w") as f:
        f.write("\n".join(lines))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--suite", default=None)
    ap.add_argument("--quick", action="store_true",
                    help="3 timed batches, 600s timeout per point")
    ap.add_argument("--timeout", type=int, default=1800)
    ap.add_argument("--timed", type=int, default=20)
    ap.add_argument("--warmup", type=int, default=3)
    ap.add_argument("--out", default=os.path.join(REPO, "BENCHMARKS.md"))
    ap.add_argument("--merge", action="store_true",
                    help="keep existing results.json points; replace only "
                         "the ones re-measured in this run (safe partial "
                         "sweeps, e.g. --suite vgg19 --merge)")
    ap.add_argument("--from-json", action="store_true",
                    help="rewrite the .md from benchmarks/results.json "
                         "without re-measuring")
    args = ap.parse_args()

    json_path = os.path.join(REPO, "benchmarks", "results.json")
    if args.from_json:
        with open(json_path) as f:
            results = json.load(f)
        write_md(results, args.out)
        print(f"wrote {args.out}")
        return

    timed, warmup, timeout = args.timed, args.warmup, args.timeout
    if args.quick:
        timed, warmup, timeout = 3, 1, 600

    results = {"platform": os.environ.get("JAX_PLATFORMS", "default"),
               "device": "?", "points": []}
    if args.merge and os.path.exists(json_path):
        with open(json_path) as f:
            old = json.load(f)
        cur_platform = os.environ.get("JAX_PLATFORMS", "default")
        if old.get("platform") != cur_platform:
            # never publish this run's numbers under the OLD platform
            # label — a CPU smoke merged into a TPU table would lie
            print(f"--merge refused: existing results are platform="
                  f"{old.get('platform')!r}, this run is "
                  f"{cur_platform!r}; measure on the same platform or "
                  f"drop --merge", file=sys.stderr)
            raise SystemExit(2)
        results = old
        # points re-measured in this run replace their old records
        results["points"] = [
            p for p in results["points"]
            if not (args.suite is None or p["suite"] == args.suite)]
    for suite, env_over, baseline_ms, note in SWEEP:
        if args.suite and suite != args.suite:
            continue
        print(f"== {suite} {env_over}", flush=True)
        r = run_one(suite, env_over, timed, warmup, timeout)
        if "device_kind" in r:
            results["device"] = r.pop("device_kind")
        print(f"   -> {r}", flush=True)
        results["points"].append({"suite": suite, "settings": env_over,
                                  "result": r, "baseline_ms": baseline_ms,
                                  "note": note})
        with open(json_path, "w") as f:
            json.dump(results, f, indent=1)
    write_md(results, args.out)
    print(f"wrote {args.out}")


if __name__ == "__main__":
    from paddle_tpu.utils import compile_cache
    compile_cache.configure()
    main()
