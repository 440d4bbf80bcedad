#!/bin/bash
# Call B: the final tree as git would commit it (.scratch/commit, made anew
# after the last code edit) against the parent under this change's benchmark
# files (.scratch/parent), one machine. The Ling cell, whose prompt chunks
# and decode steps take the rungs too (not claimed): the change traced on
# its checkout's first run (the new metric, the breakdown), then one
# untraced pair on a shared seed, the parent's on its checkout's first run.
# Last, the MiMo cell on the final tree on one more seed. Runs that would
# start after 1,050 s are skipped.
cd "$(dirname "$0")/../../.."
ling=ling3_flash_gen.serve_p1152_n128_c128
mimo=mimo_v2_flash_gen.serve_p9216_n128_c32
bash docs/chip_calls/pr38/call.sh pr39B 1050 \
  change:$ling:3900000053:1:45 \
  parent:$ling:3900000067:0:45 \
  change:$ling:3900000067:0:45 \
  change:$mimo:3900000041:0:45
