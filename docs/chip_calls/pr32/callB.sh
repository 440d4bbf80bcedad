#!/bin/bash
# PR 32, call B: the working tree. One traced run of the new cell with the
# four controls standing in beside the program (the final draw of the
# weights: conv_std 0.05, router_bias_std 0.002); then the parent
# (.scratch/p32: git archive of 926b785 with this PR's BENCHMARK.json and
# benchmark/ laid over it) on the new cell, which has to fail at once, and
# on the Brumby cell with --trace 1, which has to run with this PR's
# benchmark files.
set -x
mkdir -p chiprun_out
CELL=ling3_flash_gen.serve_p1152_n128_c128
python docs/chip_calls/pr32/run_dump.py ling_b --workload $CELL --seed 3200000051 --seconds 45 --trace 1 --stand-in fp8,swapped,chunk_state_dropped,route_ungrouped > chiprun_out/ling_b.out 2> chiprun_out/ling_b.err; echo "rc ling_b=$?"
cd .scratch/p32
t0=$(date +%s); python benchmark/run.py --workload $CELL --seed 3200000077 --seconds 45 --trace 0 > ../../chiprun_out/parent_ling.out 2> ../../chiprun_out/parent_ling.err; echo "rc parent new cell=$? after $(( $(date +%s) - t0 )) s"; tail -3 ../../chiprun_out/parent_ling.err
python benchmark/run.py --workload brumby_14b_gen.serve_p576_n128_c16 --seed 3200000101 --seconds 45 --trace 1 > ../../chiprun_out/parent_brumby_t.out 2> ../../chiprun_out/parent_brumby_t.err; echo "rc parent brumby traced=$?"
