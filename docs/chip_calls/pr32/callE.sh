#!/bin/bash
# PR 32, call E: the final tree (.scratch/c32). The new cell once more with
# the four controls standing in beside the program, under the limits as
# committed; the Brumby cell traced, for `decode.step_ms_p50` and
# `lm.state_fill_pct` beside the parent's traced run of call B.
cd /root/repo/.scratch/c32
mkdir -p /root/repo/chiprun_out/pr32E
python3 benchmark/run.py --workload ling3_flash_gen.serve_p1152_n128_c128 --seed 3200000417 --seconds 45 --trace 0 --stand-in fp8,swapped,chunk_state_dropped,route_ungrouped > /root/repo/chiprun_out/pr32E/controls.out 2> /root/repo/chiprun_out/pr32E/controls.err; echo "rc controls=$?"
grep "^compared" /root/repo/chiprun_out/pr32E/controls.err
python3 benchmark/run.py --workload brumby_14b_gen.serve_p576_n128_c16 --seed 3200000431 --seconds 45 --trace 1 > /root/repo/chiprun_out/pr32E/brumby_t.out 2> /root/repo/chiprun_out/pr32E/brumby_t.err; echo "rc brumby traced=$?"
tail -n 1 /root/repo/chiprun_out/pr32E/brumby_t.out | cut -c1-3000
