#!/bin/sh
# builder's tool: the two full sets of one cell (same seeds in both), then
# the traced runs (seeds in $TSEEDS); every result line lands under
# chiprun_out/sets/<cell>/
#   sh chipbench/tools/sets.sh CELL SECONDS [SEED ...]
W=$1; SECS=${2:-30}; shift 2
SEEDS=${*:-"2147483659 3000000019 1234567891 4100000023 987654321 2500000003"}
OUT=chiprun_out/sets/$W
mkdir -p $OUT
for SET in A B; do
  for S in $SEEDS; do
    python3 chipbench/run.py --workload $W --seed $S --seconds $SECS --trace 0 > $OUT/$SET-$S.out 2> $OUT/$SET-$S.err
    echo "$SET $S rc=$? $(tail -n 1 $OUT/$SET-$S.out | cut -c1-400)"
  done
done
for S in ${TSEEDS:-3999999979 2222222223 2718281829}; do
  python3 chipbench/run.py --workload $W --seed $S --seconds $SECS --trace 1 > $OUT/T-$S.out 2> $OUT/T-$S.err
  echo "T $S rc=$? $(tail -n 1 $OUT/T-$S.out | cut -c1-1200)"
done
