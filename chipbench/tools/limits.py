#!/usr/bin/env python3
"""A builder's tool: the readings a cell's limits are set from.

    python3 chipbench/tools/limits.py --workload NAME --seeds 12 \
        --controls 3 --seconds S

One process, one set-up: for each seed a short window at the cell's own
load through the timed path, then the reference over the same sample
(the program's reading), and on the first ``--controls`` seeds the
controls (the reference in a lower precision, put in the program's
place; for training also the planted faults). Every reading, the
program's and each control's and fault's, goes through
``compare.judge`` with the committed ``limits/<workload>.json`` and is
recorded with its ``ok``: the program's have to read true, a control's
or a fault's false on one number at least, or the tool exits with 1.
Prints one line a seed and writes everything to
``chiprun_out/limits/<workload>.json``. The benchmark's own runs never
run this.
"""

import argparse
import gc
import json
import os
import sys
import time

T0 = time.time()
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

SEEDS = [2147483693, 3100000037, 1357924681, 4200000011, 975318642,
         2600000027, 3333333341, 1111111121, 2999999929, 4000000063,
         1888888897, 3777777781, 2345678917, 1600000033, 3456789019,
         4111111139]


def judged(cell, values: dict, sound: bool, verdicts: list, who: str):
    """The committed limits over one set of readings; keeps whether the
    verdict is the one that has to come out."""
    from chipbench import compare
    doc = compare.judge(values, cell.limits)
    ok = all(c["ok"] for c in doc.values()) and bool(doc)
    verdicts.append((who, sound, ok))
    return {"ok": ok, "failed": [k for k, c in doc.items() if not c["ok"]]}


def serving(cell, args, out):
    from chipbench import compare, flops, harness, weights
    from chipbench.systems import lm_serving
    dims = flops.lm_dims(cell.config)
    seeds = SEEDS[:args.seeds]
    srv, eng, spans = lm_serving.build(cell, seeds[0], harness.WORK)
    out["spans"] = spans
    for i, seed in enumerate(seeds):
        if i:
            eng.params = srv.params = None
            gc.collect()
            eng.params = srv.params = weights.lm_weights(seed, dims)
        box = lm_serving.drive(cell, eng, seed, args.seconds)
        final = box["final"]
        e2e = lm_serving.end_to_end(final)
        row = {"seed": seed, "e2e": e2e,
               "counts": lm_serving.counts(final)}
        ctrls = ("fp8", "int8") if i < args.controls else (None,)
        for c in ctrls:
            t = time.time()
            got = compare.served_gaps(eng.params, final["sample"], dims,
                                      control=c)
            row["gap_max"] = got["gap_max"]
            row["tokens_compared"] = got["tokens_compared"]
            row["tokens_off_best"] = got["tokens_off_best"]
            row["requests"] = got["requests"]
            if c:
                row[f"control_{c}_gap_max"] = got["control_gap_max"]
                row[f"control_{c}_judged"] = judged(
                    cell, {"gap_max": got["control_gap_max"],
                           "unanswered": final["unanswered"]}, False,
                    out["verdicts"], f"{seed} {c}")
            row[f"check_s_{c}"] = time.time() - t
        row["judged"] = judged(
            cell, {"gap_max": row["gap_max"],
                   "unanswered": final["unanswered"]}, True,
            out["verdicts"], f"{seed} program")
        print("READING", json.dumps(row), flush=True)
        out["rows"].append(row)


def leaf_table(prog: dict, refr: dict, key: str, top: int = 8) -> dict:
    """The look behind a worst-leaf number: the leaves that read worst,
    the median leaf's gap and the worst among weight matrices."""
    import numpy as np
    ref = refr[key]
    med = float(np.median(list(ref.values())))
    gaps = {k: abs(prog[key][k] - ref[k]) / max(ref[k], med) for k in ref}
    worst = sorted(gaps, key=gaps.get, reverse=True)[:top]
    return {"median_ref_norm": med,
            "median_gap": float(np.median(list(gaps.values()))),
            "p90_gap": float(np.percentile(list(gaps.values()), 90)),
            "worst_weight_gap": max(g for k, g in gaps.items()
                                    if k.endswith(".w")),
            "worst": [(k, gaps[k], prog[key][k], ref[k]) for k in worst]}


def training(cell, args, out):
    from chipbench import harness
    from chipbench.systems import trainer as tsys
    seeds = SEEDS[:args.seeds]
    for i, seed in enumerate(seeds):
        paddle, trainer, shapes = tsys.build(cell, seed, False)
        st = tsys.drive(cell, paddle, trainer, shapes, seed, args.seconds)
        prog = {"losses": st["losses"], "grad_norm": st["grad_norm"],
                "change_norm": st["change_norm"],
                "grad_weights": st["grad_weights"]}
        fb = st.pop("first_batches")
        step_ms = 1000 * (st["t_close"] - st["t_open"]) / st["steps"]
        trainer.parameters.values = trainer.opt_state = None
        trainer.parameters.state = None
        del trainer
        gc.collect()
        refr = tsys.reference_readings(cell, seed, shapes, fb)
        row = {"seed": seed, "step_ms": step_ms,
               "program": {k: v for k, v in tsys.readings_gap(
                   prog, refr).items()},
               "losses": prog["losses"], "ref_losses": refr["losses"],
               "grad_table": leaf_table(prog, refr, "grad_norm"),
               "change_table": leaf_table(prog, refr, "change_norm")}
        row["judged"] = judged(cell, row["program"], True, out["verdicts"],
                               f"{seed} program")
        if i < args.controls:
            for name, kw in (("fp8", {"fmt": "fp8"}),
                             ("int8", {"fmt": "int8"}),
                             ("half_batch", {"drop_half": True})):
                bad = tsys.reference_readings(cell, seed, shapes, fb, **kw)
                row[name] = tsys.readings_gap(bad, refr)
                row[name + "_judged"] = judged(
                    cell, row[name], False, out["verdicts"],
                    f"{seed} {name}")
                row[name + "_losses"] = bad["losses"]
                row[name + "_grad_table"] = leaf_table(bad, refr,
                                                       "grad_norm", 3)
                row[name + "_change_table"] = leaf_table(bad, refr,
                                                         "change_norm", 3)
        print("READING", json.dumps(row), flush=True)
        out["rows"].append(row)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=30.0)
    args = ap.parse_args(argv)
    from chipbench import harness
    cell = harness.Cell(args.workload)
    device = harness.require_tpu(cell.chips)
    out = {"workload": args.workload, "device": device, "rows": [],
           "verdicts": []}
    try:
        if cell.config["system"] == "lm_serving":
            serving(cell, args, out)
        else:
            training(cell, args, out)
    finally:
        d = os.path.join(ROOT, "chiprun_out", "limits")
        os.makedirs(d, exist_ok=True)
        with open(os.path.join(d, f"{args.workload}.json"), "w") as f:
            json.dump(out, f, indent=1)
    print("took", time.time() - T0)
    wrong = [(who, ok) for who, sound, ok in out["verdicts"] if ok != sound]
    for who, ok in wrong:
        print(f"WRONG VERDICT under the committed limits: {who} read "
              f"ok={ok}", file=sys.stderr)
    return 1 if wrong else 0


if __name__ == "__main__":
    sys.exit(main())
