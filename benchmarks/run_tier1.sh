#!/usr/bin/env bash
# Tier-1 verify — the ROADMAP.md command (plus --durations=20, which only
# adds a slowest-tests table to the output), so builders and reviewers
# stop hand-assembling the pipeline. Prints DOTS_PASSED=<n> (count of
# passing-test dots) and exits with pytest's status.
#
# The full suite takes ~16 min against the 870 s timeout, so the gate
# counts dots printed before the cutoff — the --durations table (also
# echoed below as SLOWEST TESTS when the run finishes in time) is the
# trim list for keeping tier-1 under the cutoff.
#
# Usage: benchmarks/run_tier1.sh   (from anywhere; cd's to the repo root)

cd "$(dirname "$0")/.." || exit 1

set -o pipefail
log=$(mktemp /tmp/_t1.XXXXXX.log)   # private log: concurrent runs must
trap 'rm -f "$log"' EXIT            # not corrupt each other's dot count
timeout -k 10 870 env JAX_PLATFORMS=cpu python -m pytest tests/ -q \
    -m 'not slow' --continue-on-collection-errors -p no:cacheprovider \
    -p no:xdist -p no:randomly --durations=20 2>&1 | tee "$log"
rc=${PIPESTATUS[0]}
echo DOTS_PASSED=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' "$log" | tr -cd . | wc -c)
if grep -aq 'slowest 20 durations' "$log"; then
    echo '== SLOWEST TESTS (trim candidates for the 870 s cutoff) =='
    sed -n '/slowest 20 durations/,/^[=[:space:]]*$/p' "$log" | head -25
fi
# perf-regression sentinel: latest vs previous serving/zero artifacts
# at their figures of merit, PASS/REGRESSED per figure with a noise
# band (benchmarks/check_regression.py) — replaces the old tail-echo
# of raw artifact numbers. Informational here: the tier-1 verdict
# stays pytest's (CI that wants to gate on perf runs the checker
# directly and takes its exit code).
echo '== PERF SENTINEL (benchmarks/check_regression.py) =='
python benchmarks/check_regression.py || true
latest_serving=$(ls benchmarks/runs/*serving_paged*.json 2>/dev/null | sort | tail -1)
# latest tiered-prefix-cache figures: cold-prefill blocks the
# DRAM/disk tiers absorbed + the tiered/baseline TTFT p99 ratio on
# the 10x-working-set chat trace, from the newest serving artifact
if [ -n "$latest_serving" ]; then
    echo "== TIERED PREFIX CACHE ($latest_serving) =="
    python - "$latest_serving" <<'PYEOF' || true
import json, sys
doc = json.load(open(sys.argv[1]))
tc = doc.get("tiered_cache")
if not tc:
    print("no tiered_cache section — rerun serving_bench.py")
else:
    print(json.dumps({
        "cold_prefill_tokens_avoided_frac":
            doc.get("cold_prefill_tokens_avoided_frac", "n/a"),
        "tiered_ttft_p99_ratio":
            doc.get("tiered_ttft_p99_ratio", "n/a"),
        "working_set_mult": tc.get("working_set_mult"),
        "tier_hit_blocks": tc.get("tiered", {}).get("tier_hit_blocks"),
        "demotions": tc.get("tiered", {}).get("demotions")}))
PYEOF
fi
# latest fleet observability-overhead figure: traced/untraced goodput
# ratio + the chaos-run verdict from the newest serving_fleet artifact
# (run serving_bench.py --fleet to refresh)
latest_fleet=$(ls benchmarks/runs/*serving_fleet*.json 2>/dev/null | sort | tail -1)
if [ -n "$latest_fleet" ]; then
    echo "== OBSERVABILITY OVERHEAD ($latest_fleet) =="
    python - "$latest_fleet" <<'PYEOF' || true
import json, sys
doc = json.load(open(sys.argv[1]))
print(json.dumps({
    "observability_overhead": doc.get("observability_overhead", "n/a"),
    "chaos_joined_ok": doc.get("chaos_joined_ok", "n/a"),
    "chaos": doc.get("fleet", {}).get("chaos", "n/a")}))
PYEOF
fi
# latest fleet-control-plane chaos figures: latency-tier TTFT p99
# under the diurnal peak, controlled/static ratio, healed capacity,
# recovery seconds, and the rewarm + shed verdicts from the newest
# fleet_chaos artifact (run serving_bench.py --fleet-chaos to refresh)
latest_chaos=$(ls benchmarks/runs/*fleet_chaos*.json 2>/dev/null | sort | tail -1)
if [ -n "$latest_chaos" ]; then
    echo "== FLEET CONTROL PLANE ($latest_chaos) =="
    python - "$latest_chaos" <<'PYEOF' || true
import json, sys
doc = json.load(open(sys.argv[1]))
print(json.dumps({
    "chaos_latency_ttft_p99_s":
        doc.get("chaos_latency_ttft_p99_s", "n/a"),
    "chaos_ttft_ratio": doc.get("chaos_ttft_ratio", "n/a"),
    "healed_capacity_frac": doc.get("healed_capacity_frac", "n/a"),
    "recovery_s": doc.get("recovery_s", "n/a"),
    "rewarm_blocks_avoided": doc.get("rewarm_blocks_avoided", "n/a"),
    "shed_before_saturate_ok":
        doc.get("shed_before_saturate_ok", "n/a")}))
PYEOF
fi
# latest training-gang observability figures: dark/traced steady-step
# ratio, the goodput-ledger verdict, and the run's goodput fraction
# from the newest elastic_bench artifact (run elastic_bench.py to
# refresh)
latest_elastic=$(ls benchmarks/runs/*elastic_bench*.json 2>/dev/null | sort | tail -1)
if [ -n "$latest_elastic" ]; then
    echo "== GANG OBSERVABILITY ($latest_elastic) =="
    python - "$latest_elastic" <<'PYEOF' || true
import json, sys
doc = json.load(open(sys.argv[1]))
print(json.dumps({
    "training_observability_overhead":
        doc.get("training_observability_overhead", "n/a"),
    "goodput_ledger_ok": doc.get("goodput_ledger_ok", "n/a"),
    "goodput_fraction": doc.get("goodput_fraction", "n/a"),
    "goodput_coverage": doc.get("goodput_coverage", "n/a")}))
PYEOF
fi
exit $rc
