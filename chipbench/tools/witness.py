#!/usr/bin/env python3
"""A builder's tool: a second witness for a serving cell's output check.

    python3 chipbench/tools/witness.py --workload NAME --seeds 1,2 \
        [--pallas off] [--seconds 30] [--outstanding N]

Runs the cell's window through the program with ``PADDLE_TPU_PALLAS`` as
given (``off`` = the program's XLA path, every kernel site) and prints,
per sampled request, how far its served tokens lie under the
reference's best. Where the kernels' run and the XLA run disagree on a
seed, the XLA path sides with one of them.
"""

import argparse
import gc
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--pallas", default=None)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--outstanding", type=int, default=None)
    ap.add_argument("--probe-host-params", action="store_true")
    args = ap.parse_args(argv)
    from chipbench import compare, flops, harness, weights
    from chipbench.systems import lm_serving
    cell = harness.Cell(args.workload)
    if args.pallas:
        cell.config["serving"]["pallas"] = args.pallas
        cell.config["serving"]["kernel_paths"] = \
            "xla" if args.pallas == "off" else "pallas"
    if args.outstanding:
        tr = dict(cell.traffic)
        tr["arrival"] = dict(tr["arrival"], outstanding=args.outstanding)
        os.makedirs(harness.WORK, exist_ok=True)
        path = os.path.join(harness.WORK, "witness-traffic.json")
        with open(path, "w") as f:
            json.dump(tr, f)
        cell.traffic, cell.traffic_file = tr, path
    harness.require_tpu(cell.chips)
    dims = flops.lm_dims(cell.config)
    seeds = [int(s) for s in args.seeds.split(",")]
    srv, eng, spans = lm_serving.build(cell, seeds[0], harness.WORK)
    print("WITNESS kernel_paths", json.dumps(eng.kernel_paths)[:300],
          flush=True)
    allocs = []
    inner = eng._alloc_page

    def logged(slot):
        free_before = len(eng.pool._free)
        inner(slot)
        req = eng._slot_req[slot]
        allocs.append({"rid": req.rid, "n_prompt": int(req.prompt.size),
                       "tokens": len(req.tokens), "status": req.status,
                       "page": int(eng._nalloc[slot]) - 1, "slot": int(slot),
                       "block": int(eng._slot_blocks[slot][-1]),
                       "from_free_list": free_before > 0})

    eng._alloc_page = logged
    for i, seed in enumerate(seeds):
        del allocs[:]
        if i:
            eng.params = srv.params = None
            gc.collect()
            eng.params = srv.params = weights.lm_weights(seed, dims)
        box = lm_serving.drive(cell, eng, seed, args.seconds)
        final = box["final"]
        got = compare.served_gaps(eng.params, final["sample"], dims)
        c = lm_serving.delta(box["snaps"]["open"], box["snaps"]["close"])
        for q in got["requests"]:
            # the decode-time page allocations of each compared request
            q["decode_allocs"] = [
                (a["tokens"], a["page"], a["block"], a["slot"],
                 "free" if a["from_free_list"] else "evict")
                for a in allocs if a["n_prompt"] == q["n_prompt"]
                and a["status"] == "running"]
        print("WITNESS", json.dumps({
            "seed": seed, "pallas": args.pallas or "auto",
            "gap_max": got["gap_max"],
            "tokens_compared": got["tokens_compared"],
            "requests": got["requests"],
            "serve_tok_s": lm_serving.end_to_end(final)["serve_tok_s"],
            "evictions": c.get("engine_prefix_cache_evictions_total"),
            "preemptions": c.get("engine_preemptions_total")}), flush=True)
    if args.probe_host_params:
        import time
        import jax
        import numpy as np
        t = time.time()
        eng._precompile_decode()
        t_dev = time.time() - t
        host = jax.tree_util.tree_map(np.asarray, eng.params)
        eng.params = srv.params = None
        gc.collect()
        eng.params = host       # what `serve` hands the engine today
        times = []
        for _ in range(2):
            t = time.time()
            eng._precompile_decode()
            times.append(time.time() - t)
        print("WITNESS PROBE", json.dumps({
            "decode_call_device_params_s": t_dev,
            "decode_call_host_params_s": times}), flush=True)
    print("WITNESS memory_stats", json.dumps(
        {k: int(v) for k, v in (jax_stats() or {}).items()}), flush=True)
    return 0


def jax_stats():
    import jax
    return jax.devices()[0].memory_stats()


if __name__ == "__main__":
    sys.exit(main())
