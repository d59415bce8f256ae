#!/bin/sh
# builder's tool: the first cold run of a cell with its trace kept and dumped
set -x
mkdir -p chiprun_out/first_look
W=${1:-cgpt1.3b.batch-gen-standin}
python3 chipbench/run.py --workload $W --seed 101 --seconds ${2:-10} --trace 1 --keep-trace > chiprun_out/first_look/$W.trace1.out 2> chiprun_out/first_look/$W.trace1.err
echo rc=$?
tail -c 3000 chiprun_out/first_look/$W.trace1.err
tail -n 1 chiprun_out/first_look/$W.trace1.out
python3 chipbench/tools/trace_dump.py --record chiprun_out/first_look/$W.recorded.json > chiprun_out/first_look/$W.dump.txt 2>&1
du -sh chipbench/work/* .jax_cache
python3 chipbench/run.py --workload $W --seed 4000000102 --seconds ${2:-10} --trace 0 > chiprun_out/first_look/$W.trace0.out 2> chiprun_out/first_look/$W.trace0.err
echo rc=$?
tail -c 1500 chiprun_out/first_look/$W.trace0.err
tail -n 1 chiprun_out/first_look/$W.trace0.out
