#!/bin/bash
# Call A: the Trinity cell. First the parent under this change's benchmark
# files (.scratch/parent), which has to refuse the cell at once; then the
# final tree as git would commit it (.scratch/commit; both made by
# prepare.sh): a traced run on the checkout's first run (a cold compile, the
# checkpoint drawn and written), then an untraced run with every control of
# the check standing in for the program. Runs that would start after 1,500 s
# are skipped.
cd "$(dirname "$0")/../../.."
cell=trinity_large_gen.serve_p12288_n128_c16
out=chiprun_out/pr41A; mkdir -p $out
t0=$(date +%s)
( cd .scratch/parent && timeout 600 python3 benchmark/run.py --workload $cell \
    --seed 4100000101 --seconds 45 --trace 0 > /dev/null 2> ../../$out/parent.err )
echo "parent rc=$? took=$(( $(date +%s) - t0 ))s" | tee $out/parent.txt
tail -n 3 $out/parent.err | cut -c1-400 | tee -a $out/parent.txt
bash docs/chip_calls/pr41/call.sh pr41A 1500 \
  change:$cell:4100000101:1:45 \
  change:$cell:4100000203:0:45:fp8,swapped,chunk_cache_dropped,window_as_full,gate_dropped,rope_on_full,post_norms_dropped
