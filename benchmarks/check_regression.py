#!/usr/bin/env python
"""Perf-regression sentinel over ``benchmarks/runs/`` artifacts.

``run_tier1.sh`` used to tail-echo the latest serving/zero artifacts,
leaving the reader to diff figures by eye. This checker compares the
LATEST artifact of each benchmark family against the PREVIOUS one at
that family's figures of merit and prints one PASS/REGRESSED verdict
per figure, with a noise band sized to how jittery the figure is on a
shared host:

- ratios and byte counts are near-deterministic (tight band);
- wall-clock throughput/latency figures breathe with machine load
  (wide band).

A family with fewer than two artifacts reports BASELINE (nothing to
compare — the current run becomes the next run's baseline). Exit code
1 iff any figure REGRESSED, so CI can gate on it; run_tier1.sh only
surfaces the report (the tier-1 test verdict stays pytest's).

Usage: python benchmarks/check_regression.py [--dir benchmarks/runs]
"""

import argparse
import glob
import json
import os
import sys

RUNS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "runs")

# (dotted value path, direction, relative noise band)
# direction: "higher" = bigger is better, "lower" = smaller is better,
# "true" = must stay truthy (band unused)
FAMILIES = {
    "serving": {
        "glob": "*serving_paged*.json",
        "figures": [
            ("throughput.engine_paged.tokens_per_sec", "higher", 0.25),
            ("latency.engine_paged.ttft_p99_s", "lower", 0.35),
            # decode-MFU + int8-serving floors (PR-10 artifact fields;
            # SKIP against pre-PR-10 artifacts is by design): MFU is
            # wall-clock-derived like throughput, so it breathes with
            # host load; the int8/fp32 RATIO mostly cancels the machine
            # and gets the tight band
            ("throughput.engine_paged.decode_mfu", "higher", 0.35),
            ("throughput.engine_paged_int8.tokens_per_sec",
             "higher", 0.25),
            ("serving_int8_speedup", "higher", 0.15),
            # KV-quantization scoreboards (PR-12 fields; SKIP against
            # older artifacts by design): slots-at-equal-HBM is pure
            # dtype arithmetic (near-deterministic — tight band) and
            # the >= 2x-fp32 contract must hold outright; the kv8
            # throughput ratio cancels the machine like int8's; cold
            # TTFT is wall-clock (wide band); the rel-L2 quality
            # figures are seeded-deterministic up to backend rounding
            ("capacity.slots_at_equal_hbm_int8", "higher", 0.02),
            ("capacity.slots_int8_ge_2x_fp32", "true", 0.0),
            ("serving_kv8_speedup", "higher", 0.15),
            # cold TTFT is a single-digit-ms latency on a ONE-core
            # shared host: alternating same-code A/B runs measured
            # 5-45 ms swings purely from harness-process interleaving
            # (PR-13 calibration), and the 2x prior-run ceiling that
            # replaced the original 35% band STILL fired on machine
            # state (PR-18 recalibration: the same commit probed 29 ms
            # and 67 ms minutes apart; artifact history spans 6-20 ms)
            # — any prior-run ratio is narrower than the figure's own
            # variance. Absolute ceiling instead, sized above the
            # observed same-code range: a real chunk-path
            # pessimization shows up as an order of magnitude, not a
            # factor of two
            ("cold_prefill.ttft_p50_cold_ms", "ceiling", 100.0),
            ("quality.kv_int8_rel_l2", "lower", 0.10),
            ("quality.kv_int4_rel_l2", "lower", 0.10),
            # multi-tenant scheduling + speculative decoding (PR-13
            # fields; SKIP against older artifacts by design): the
            # spec speedup is a same-machine ratio (tight-ish band;
            # the bench itself asserts the absolute 1.5 floor on
            # every full run), and the two scheduler contracts —
            # latency-tier p99 separated below batch-tier, aggregate
            # goodput no worse than FIFO — are booleans that must
            # hold outright
            ("spec_decode_speedup", "higher", 0.15),
            ("spec_decode.acceptance_rate", "higher", 0.10),
            ("tier_p99_separation_ok", "true", 0.0),
            ("goodput_ge_fifo", "true", 0.0),
            # tiered prefix cache (PR-18 fields; SKIP against older
            # artifacts by design), both ABSOLUTE bounds on the
            # 10x-working-set chat trace: the avoided fraction is
            # counter arithmetic on a fixed trace (deterministic — the
            # >= 0.5 claim gates outright) and the TTFT ratio is a
            # same-machine A/B whose 1.0 ceiling is the feature's
            # existence condition (tiers slower than evict-and-
            # recompute = demotion/promotion overhead regression)
            ("cold_prefill_tokens_avoided_frac", "floor", 0.5),
            ("tiered_ttft_p99_ratio", "ceiling", 1.0),
        ],
    },
    "router": {
        # serving-fleet figures (serving_bench.py --fleet artifacts):
        # the goodput ratio and victim-TTFT ratio are same-machine
        # A/Bs (the machine mostly cancels — mid band); absolute fleet
        # throughput breathes with host load; placement hit rate is
        # near-deterministic on the fixed trace; the two booleans —
        # every submitted request completed, and disaggregated P/D
        # generation bitwise the colocated run — must hold outright
        "glob": "*serving_fleet*.json",
        "figures": [
            ("router_goodput_ratio", "higher", 0.15),
            ("fleet_tokens_per_sec", "higher", 0.25),
            ("victim_ttft_ratio", "lower", 0.35),
            ("placement_hit_rate", "higher", 0.10),
            ("all_requests_completed", "true", 0.0),
            ("pd_bitwise_ok", "true", 0.0),
            # observability plane (PR-16 fields; SKIP against older
            # artifacts by design): fleet goodput with tracing +
            # aggregation ON over OFF is a same-machine ratio near 1.0
            # — a hot-path pessimization in the trace/aggregate code
            # drags it down and the band catches it; the chaos boolean
            # (kill-injected run: joined multi-replica trace, labeled
            # fleet /metrics, dead-replica firing→resolved pair) must
            # hold outright
            ("observability_overhead", "higher", 0.15),
            ("chaos_joined_ok", "true", 0.0),
        ],
    },
    "fleet": {
        # fleet-control-plane chaos figures (serving_bench.py
        # --fleet-chaos artifacts): ALL absolute — the phase is a
        # same-run A/B plus structural booleans, so prior-run ratio
        # bands would double-count machine noise. Calibration (PR-19,
        # one-core shared host): latency-tier TTFT p99 under the
        # saturated diurnal peak lands ~2.2-2.6 s — 6.0 catches a
        # control plane that stopped holding the band; the controlled/
        # static ratio lands ~0.7-0.8 — 1.1 means "never WORSE than
        # doing nothing" with noise headroom; recovery (kill -> the
        # replacement reporting ok) lands ~0.15-0.19 s with a 0.02 s
        # heal backoff — 2.0 catches a heal loop gone slow; the
        # rewarm floor just needs the KV relay to have shipped
        # ANYTHING (a zero means the replacement came back cold)
        "glob": "*fleet_chaos*.json",
        "figures": [
            ("chaos_latency_ttft_p99_s", "ceiling", 6.0),
            ("chaos_ttft_ratio", "ceiling", 1.1),
            ("healed_capacity_frac", "floor", 1.0),
            ("recovery_s", "ceiling", 2.0),
            ("rewarm_blocks_avoided", "floor", 1.0),
            ("shed_before_saturate_ok", "true", 0.0),
            ("all_admitted_completed", "true", 0.0),
        ],
    },
    "elastic": {
        # elastic_bench.py recovery figures: wall-clock dominated by
        # worker restart + jax re-init + recompile, so both get the
        # widest band; the completed/single-restart boolean must hold
        "glob": "*elastic_bench*.json",
        "figures": [
            ("recovery_seconds", "lower", 0.5),
            # detection latency is QUANTIZED: the worker beats every
            # 0.5 s and the supervisor polls every 0.2 s, so a single
            # kill sample lands anywhere in 0-0.7 s depending on phase
            # alone — a prior-run ratio band narrower than one poll
            # interval (0.133 * 1.5 = 0.20) fires on phase, not code.
            # The absolute ceiling is the structural bound
            # (heartbeat cadence + poll interval + margin): a real
            # detection regression (a scan gone quadratic, a blocking
            # scrape) blows past 0.8 s outright
            ("detect_seconds", "ceiling", 0.8),
            ("completed", "true", 0.0),
            # gang observability plane (PR-17 fields; SKIP against
            # older artifacts by design): dark-over-traced min steady
            # step wall is an absolute floor. Calibration (PR-17): the
            # plane's true per-step cost is ~2 us (scope-pair delta,
            # buffer on vs off) on a >6 ms step, but alternating
            # same-code runs on this one-core shared host swing the
            # min-of-mins +-10% from machine state alone — a 0.97
            # floor would fire on load, not code, so 0.90 is the gate:
            # it still catches a structural regression (telemetry or
            # aggregation moving onto the per-step path costs >=0.5 ms
            # and shows as <0.9). The ledger boolean (valid checksum,
            # both coordination epochs, restart gap attributed
            # post-kill) and its >=90% wall coverage must hold outright
            ("training_observability_overhead", "floor", 0.90),
            ("goodput_ledger_ok", "true", 0.0),
            ("goodput_coverage", "floor", 0.9),
        ],
    },
    "zero": {
        # the staged artifacts are date-stamped (<date>_zero_bench_
        # data<N>_stages.json) and carry the legacy PR-5 keys too, so
        # one glob compares both schemas; the original fixed-name PR-5
        # artifact is parked under runs/legacy/ (it would sort AFTER
        # every date and masquerade as the latest run forever)
        "glob": "*zero_bench*stages.json",
        "figures": [
            ("opt_state_bytes_ratio", "lower", 0.02),
            ("zero1.opt_state_bytes_per_device", "lower", 0.02),
            ("zero1.step_ms_median", "lower", 0.35),
            ("traj_allclose", "true", 0.0),
            # staged artifact (zero_bench*_stages.json): bytes-ratio
            # ceilings per stage are near-deterministic (layout math);
            # step-time floors breathe with host load; the trajectory
            # and step-time-ordering booleans must stay true
            ("stages.2.grad_bytes_ratio", "lower", 0.02),
            ("stages.3.param_bytes_ratio", "lower", 0.02),
            ("stages.3.opt_state_bytes_ratio", "lower", 0.02),
            # min, not median: the one-core host shares with the
            # harness, so medians absorb background steals the program
            # did not cause
            ("stages.2.step_ms_min", "lower", 0.35),
            ("stages.3.step_ms_min", "lower", 0.35),
            ("stages.2.traj_allclose", "true", 0.0),
            ("stages.3.traj_allclose", "true", 0.0),
            ("stages.2.contract_ok", "true", 0.0),
            ("stages.3.contract_ok", "true", 0.0),
            ("step_time_no_worse_than_stage1", "true", 0.0),
        ],
    },
}


def lookup(doc, path):
    """Dotted-path lookup; None when any segment is missing."""
    cur = doc
    for seg in path.split("."):
        if not isinstance(cur, dict) or seg not in cur:
            return None
        cur = cur[seg]
    return cur


def compare_figure(latest, prev, direction, band):
    """(verdict, detail) for one figure of merit; SKIP when either
    artifact lacks it (schema drift is not a regression)."""
    if direction == "true":
        # a boolean contract holds (or not) on the latest artifact
        # alone — a figure new to the schema must not wait one run
        # before it can gate
        if latest is None:
            return "SKIP", "missing in latest"
        return ("PASS", "still true") if latest else \
            ("REGRESSED", f"was {prev!r}, now {latest!r}")
    if direction in ("floor", "ceiling"):
        # an ABSOLUTE bound (band is the bound itself, not a prior-run
        # ratio): gates on the latest artifact alone, like "true"
        if latest is None:
            return "SKIP", "missing in latest"
        latest = float(latest)
        ok = (latest >= band if direction == "floor"
              else latest <= band)
        return ("PASS" if ok else "REGRESSED"), \
            f"latest {latest:g} vs absolute {direction} {band:g}"
    if latest is None or prev is None:
        return "SKIP", "missing in latest" if latest is None \
            else "missing in previous"
    latest, prev = float(latest), float(prev)
    if direction == "higher":
        floor = prev * (1.0 - band)
        ok = latest >= floor
        detail = (f"latest {latest:g} vs prev {prev:g} "
                  f"(floor {floor:g}, band {band:.0%})")
    else:
        ceil = prev * (1.0 + band)
        ok = latest <= ceil
        detail = (f"latest {latest:g} vs prev {prev:g} "
                  f"(ceiling {ceil:g}, band {band:.0%})")
    return ("PASS" if ok else "REGRESSED"), detail


def check_family(name, spec, runs_dir):
    """Compare the two newest artifacts of one family; returns the
    list of (figure, verdict, detail) lines (empty = no artifacts)."""
    # order by date-stamped basename, not mtime: a fresh git checkout
    # gives every committed artifact the same mtime, which would make
    # latest-vs-previous arbitrary (and a gating CI compare inverted)
    paths = sorted(glob.glob(os.path.join(runs_dir, spec["glob"])),
                   key=os.path.basename)
    if not paths:
        return [("-", "SKIP", "no artifacts")]
    if len(paths) < 2:
        # a lone artifact still gates its ABSOLUTE figures — "true" /
        # "floor" / "ceiling" judge the latest alone; relative
        # directions wait for a second run
        try:
            with open(paths[-1]) as f:
                latest = json.load(f)
        except (OSError, ValueError) as e:
            return [("-", "SKIP", f"unreadable artifact: {e}")]
        lines = [("-", "BASELINE",
                  f"only {os.path.basename(paths[-1])} — absolute "
                  f"figures gate, relative ones wait for a second "
                  f"run")]
        for path, direction, band in spec["figures"]:
            if direction in ("true", "floor", "ceiling"):
                verdict, detail = compare_figure(
                    lookup(latest, path), None, direction, band)
                lines.append((path, verdict, detail))
        return lines
    prev_p, latest_p = paths[-2], paths[-1]
    try:
        with open(prev_p) as f:
            prev = json.load(f)
        with open(latest_p) as f:
            latest = json.load(f)
    except (OSError, ValueError) as e:
        return [("-", "SKIP", f"unreadable artifact: {e}")]
    lines = [("-", "COMPARING",
              f"{os.path.basename(latest_p)} vs "
              f"{os.path.basename(prev_p)}")]
    for path, direction, band in spec["figures"]:
        verdict, detail = compare_figure(
            lookup(latest, path), lookup(prev, path), direction, band)
        lines.append((path, verdict, detail))
    return lines


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--dir", default=RUNS,
                    help="artifact directory (default benchmarks/runs)")
    args = ap.parse_args(argv)
    regressed = False
    for name, spec in FAMILIES.items():
        for figure, verdict, detail in check_family(name, spec,
                                                    args.dir):
            print(f"sentinel {name} {figure}: {verdict} — {detail}")
            regressed |= verdict == "REGRESSED"
    print("SENTINEL: " + ("REGRESSED" if regressed else "PASS"))
    return 1 if regressed else 0


if __name__ == "__main__":
    sys.exit(main())
