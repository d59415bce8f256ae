#!/usr/bin/env python3
"""A builder's tool: ``tools/limits.py``'s serving readings for the cells
of ``systems/q3next_serving.py`` (that tool's own serving half is tied
to the GPT-2 system module).

    python3 chipbench/tools/limits_q3next.py --workload NAME --seeds 12 \
        --controls 3 --faults 2 --seconds S --budget-s B

One process, one set-up: for each seed a window at the cell's own load
through the timed path, then the reference over the same sample (the
program's reading); on the first ``--controls`` seeds also the fp8 and
int8 controls (the reference in a lower precision, put in the program's
place), and on the first ``--faults`` seeds a second window with a fault
planted in the engine (``FAULTS``), on the same weights and traffic.
Every reading goes through ``compare.judge`` with the committed limits
and is recorded with its ``ok``: the program's have to read true, a
control's or a fault's false on one number at least, or the tool exits
with 1. No new seed is started after ``--budget-s`` seconds. Prints one
line a reading and writes everything, with each compared position's
gap, to ``chiprun_out/limits/<workload>.json`` after every seed.
"""

import argparse
import gc
import json
import os
import sys
import time

T0 = time.time()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

NUMBERS = ("gap_max", "gap_mean")


def state_zeroed(eng):
    """The recurrent rows a prefill chunk leaves are lost: the next
    chunk, and the first decode step, start from a zero state (the
    convolution tail and the pages are kept). Returns the undo."""
    inner = eng._prefill_fn

    def lossy(params, cache, tokens, length, pages, slot, *rest):
        out, cache = inner(params, cache, tokens, length, pages, slot, *rest)
        return out, dict(cache, rec_state=cache["rec_state"]
                         .at[:, slot].set(0.0))

    eng._prefill_fn = lossy
    return lambda: setattr(eng, "_prefill_fn", inner)


def experts_dropped(eng):
    """Half of the held experts of ONE layer (the period's attention
    layer) add nothing: their down-projections are zero in the engine's
    weights, not in the reference's."""
    moe = eng.params["periods"]["full"]["moe"]
    whole = moe["w2"]
    moe["w2"] = whole.at[:, whole.shape[1] // 2:].set(0)

    def undo():
        moe["w2"] = whole

    return undo


FAULTS = {"state_zeroed": state_zeroed, "experts_dropped": experts_dropped}


def collect(system, cell, args, out, seeds, work, judged) -> None:
    srv, eng, out["spans"] = system.build(cell, seeds[0], work)
    faults = list(FAULTS)

    def window(seed):
        final = system.drive(system.for_drive(cell), eng, seed,
                             args.seconds)["final"]
        return final, {"seed": seed, "e2e": system.end_to_end(final),
                       "counts": system.counts(final),
                       "unanswered": final["unanswered"]}

    def read(row, final, controls=()):
        t = time.time()
        # the reference reads the seed's weights as they were made: a
        # fault in the engine's copy is undone by now
        got = system.served_gaps(eng.params, final["sample"], cell.config,
                                 controls=controls, keep_gaps=True)
        row.update({k: got[k] for k in NUMBERS + (
            "tokens_compared", "tokens_off_best", "requests", "gaps")})
        row["check_s"] = time.time() - t
        return got

    for i, seed in enumerate(seeds):
        if i and time.time() - T0 > args.budget_s:
            print(f"budget spent before seed {i}", flush=True)
            break
        if i:
            eng.params = srv.params = None
            gc.collect()
            eng.params = srv.params = system.make_weights(seed, cell.config)
        final, row = window(seed)
        got = read(row, final,
                   ("fp8", "int8") if i < args.controls else ())
        row["judged"] = judged(
            cell, {**{k: row[k] for k in NUMBERS},
                   "unanswered": row["unanswered"]}, True, out["verdicts"],
            f"{seed} program")
        for c, doc in got["controls"].items():
            row[f"control_{c}"] = doc
            row[f"control_{c}_judged"] = judged(
                cell, {**{k: doc[k] for k in NUMBERS},
                       "unanswered": row["unanswered"]}, False,
                out["verdicts"], f"{seed} {c}")
        out["rows"].append(row)
        show(row)
        if i < args.faults:
            name = faults[i % len(faults)]
            undo = FAULTS[name](eng)
            try:
                final, bad = window(seed)
            finally:
                undo()
            bad["fault"] = name
            read(bad, final)
            bad["judged"] = judged(
                cell, {**{k: bad[k] for k in NUMBERS},
                       "unanswered": bad["unanswered"]}, False,
                out["verdicts"], f"{seed} {name}")
            out["faults"].append(bad)
            show(bad)
        save(out, args.workload)


def show(row):
    def brief(doc):
        return {k: brief(v) if isinstance(v, dict) else v
                for k, v in doc.items() if k not in ("gaps", "requests")}
    print("READING", json.dumps(brief(row)), flush=True)


def save(out, workload):
    d = os.path.join(ROOT, "chiprun_out", "limits")
    os.makedirs(d, exist_ok=True)
    with open(os.path.join(d, f"{workload}.json"), "w") as f:
        json.dump(out, f)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--faults", type=int, default=2)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--budget-s", type=float, default=3000.0)
    args = ap.parse_args(argv)
    from chipbench import harness
    from chipbench.systems import q3next_serving as system
    limits = harness.load_module(os.path.join(HERE, "limits.py"),
                                 "chipbench_tools_limits")
    cell = harness.Cell(args.workload)
    out = {"workload": args.workload,
           "device": harness.require_tpu(cell.chips), "rows": [],
           "faults": [], "verdicts": []}
    try:
        collect(system, cell, args, out, limits.SEEDS[:args.seeds],
                harness.WORK, limits.judged)
    finally:
        save(out, args.workload)
    print("took", time.time() - T0)
    wrong = [(who, ok) for who, sound, ok in out["verdicts"] if ok != sound]
    for who, ok in wrong:
        print(f"WRONG VERDICT under the committed limits: {who} read "
              f"ok={ok}", file=sys.stderr)
    return 1 if wrong else 0


if __name__ == "__main__":
    sys.exit(main())
