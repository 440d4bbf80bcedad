#!/bin/bash
# One chip call on the tree as git would commit it (.scratch/commit) and its
# parent (.scratch/parent; both made by docs/chip_calls/pr34/prepare.sh
# <parent>): the window layer's core alone at the Trinity and MiMo cells'
# sizes, traced (time_core.py); then the Trinity cell traced on the change
# (a checkout's first run), the parent and the change untraced on one seed,
# and the MiMo cell on the change and the parent on one seed. Runs that would
# start after the first argument's seconds (2,300 if none) are skipped.
cd "$(dirname "$0")/../../.."
out=chiprun_out/window_ring; mkdir -p $out
for w in trinity mimo; do
  TRACE=1 REPS=30 timeout 300 python docs/chip_calls/window_ring/time_core.py \
    .scratch/commit $w 2>> $out/core.err | tail -n 1 | tee -a $out/core.txt
done
T=trinity_large_gen.serve_p12288_n128_c16
M=mimo_v2_flash_gen.serve_p9216_n128_c32
bash docs/chip_calls/pr41/call.sh window_ring ${1:-2300} \
  change:$T:4200000101:1:45 \
  parent:$T:4200000203:0:45 \
  change:$T:4200000203:0:45 \
  change:$M:4200000307:0:45 \
  parent:$M:4200000307:0:45
