#!/usr/bin/env python
"""Profile the ResNet-50 train step on the real chip and print where the
time goes (top HLO ops / fusions by self-time).

Captures a jax.profiler device trace of a few steady-state steps, then
parses the XSpace with tensorboard_plugin_profile's converters (the same
pipeline `tensorboard --logdir` uses) and prints the hlo_stats table —
per-fusion self time, HBM bytes, and occurrence counts. This is the
measurement loop behind BENCHMARKS.md's MFU analysis: find the fusions
that dominate the bandwidth-bound step, fix, re-measure.

Usage:  python benchmarks/profile_step.py [--steps 5] [--batch 256]
        [--top 40] [--logdir /tmp/pt_profile]

Reference protocol slot: the reference profiles with nvprof
(benchmark/paddle/image/run.sh + cuda profiler); on TPU the equivalent
evidence is the XLA op profile.
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def capture(logdir: str, batch: int, steps: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    import bench  # BENCH_S2D env applies, same default as bench.py

    step_fn, params, opt_state = bench.build_train_step()
    p, o, s = params.values, opt_state, params.state
    rng = np.random.RandomState(0)
    images = jnp.asarray(rng.rand(batch, 224, 224, 3).astype(np.float32))
    labels = jnp.asarray(rng.randint(0, 1000, batch).astype(np.int32))
    from paddle_tpu.utils.sync import host_sync as full_sync

    for i in range(3):  # compile + warm
        loss, p, o, s = step_fn(p, o, s, images, labels,
                                jnp.asarray(i, jnp.int32))
    full_sync(p, loss)
    with jax.profiler.trace(logdir):
        for i in range(steps):
            loss, p, o, s = step_fn(p, o, s, images, labels,
                                    jnp.asarray(i, jnp.int32))
        full_sync(p, loss)
    print(f"trace captured to {logdir}", file=sys.stderr)


def find_xspaces(logdir: str):
    out = []
    for root, _dirs, files in os.walk(logdir):
        for f in files:
            if f.endswith(".xplane.pb"):
                out.append(os.path.join(root, f))
    return sorted(out)


def report(logdir: str, top: int) -> None:
    """Aggregate the device XLA-op timeline per HLO op.

    Parses the XSpace proto directly (the tensorboard converter's native
    pywrap entry point is absent in this TF build): for each event on the
    '/device:TPU:0' → 'XLA Ops' line, accumulate duration against its
    event metadata, whose stats carry hlo_category / bytes_accessed /
    flops / source line."""
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    paths = find_xspaces(logdir)
    if not paths:
        print(json.dumps({"error": f"no .xplane.pb under {logdir}"}))
        return
    xs = xplane_pb2.XSpace()
    xs.ParseFromString(open(paths[-1], "rb").read())
    planes = [p for p in xs.planes if p.name.startswith("/device:TPU")]
    if not planes:
        print(json.dumps({"error": "no TPU device plane in trace"}))
        return
    plane = planes[0]
    smd = plane.stat_metadata

    def md_stats(m):
        out = {}
        for st in m.stats:
            name = smd[st.metadata_id].name
            field = st.WhichOneof("value")
            if field == "ref_value":
                out[name] = smd[st.ref_value].name
            elif field is not None:
                out[name] = getattr(st, field)
        return out

    agg = {}  # metadata_id -> [total_ps, count]
    steps = 0
    for line in plane.lines:
        if line.name == "XLA Modules":
            steps = len(line.events)
        if line.name != "XLA Ops":
            continue
        for ev in line.events:
            a = agg.setdefault(ev.metadata_id, [0, 0])
            a[0] += ev.duration_ps
            a[1] += 1
    rows = []
    for mid, (ps, cnt) in agg.items():
        m = plane.event_metadata[mid]
        st = md_stats(m)
        rows.append({
            "us": ps / 1e6, "count": cnt,
            "cat": str(st.get("hlo_category", "?")),
            "bytes": int(st.get("bytes_accessed", 0) or 0) * cnt,
            "flops": int(st.get("flops", 0) or 0) * cnt,
            "src": str(st.get("source", "")),
            "name": m.name.split(" = ")[0].lstrip("%"),
        })
    rows.sort(key=lambda r: r["us"], reverse=True)
    total_us = sum(r["us"] for r in rows)
    total_bytes = sum(r["bytes"] for r in rows)
    if steps == 0:
        print("WARNING: no 'XLA Modules' line in trace — reporting totals "
              "over the whole capture, not per-execution averages")
    denom = max(steps, 1)
    print(f"{denom} module executions; totals are per-execution averages")
    print(f"total device self time {total_us/denom/1e3:.2f} ms, "
          f"HBM touched {total_bytes/denom/1e9:.1f} GB, "
          f"{total_bytes/1e9/max(total_us/1e6, 1e-9):.0f} GB/s effective")
    print(f"{'us/step':>9} {'%':>6} {'GB/step':>8} {'n':>4} "
          f"{'cat':<18} op  [source]")
    by_cat = {}
    for r in rows:
        c = by_cat.setdefault(r["cat"], [0.0, 0])
        c[0] += r["us"]
        c[1] += r["bytes"]
    for cat, (us, by) in sorted(by_cat.items(), key=lambda kv: -kv[1][0]):
        print(f"{us/denom:9.1f} {100*us/max(total_us,1e-9):6.2f} "
              f"{by/denom/1e9:8.2f} {'':>4} {cat:<18} <category total>")
    print("-" * 78)
    for r in rows[:top]:
        src = r["src"].replace("/root/repo/", "")
        print(f"{r['us']/denom:9.1f} {100*r['us']/max(total_us,1e-9):6.2f} "
              f"{r['bytes']/denom/1e9:8.2f} {r['count']:4d} "
              f"{r['cat']:<18} {r['name'][:60]}  [{src}]")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=5)
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--top", type=int, default=40)
    ap.add_argument("--logdir", default="/tmp/pt_profile")
    ap.add_argument("--report-only", action="store_true",
                    help="skip capture; parse an existing --logdir")
    args = ap.parse_args()
    if not args.report_only:
        capture(args.logdir, args.batch, args.steps)
    report(args.logdir, args.top)


if __name__ == "__main__":
    from paddle_tpu.utils import compile_cache
    compile_cache.configure()
    main()
