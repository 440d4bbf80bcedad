#!/bin/bash
# Call D: the Trinity cell after its check compares the widest gap, the ninth
# decile of the gaps and the ninth decile of the greedy choice's margin, and
# prints the reference's routing ties and the attention cores' trace counts,
# on the tree as git would commit it (.scratch/commit, made by prepare.sh): a
# traced run on the checkout's first run (the expert layer's roofline
# held_moe_roofline.serve), one run with every control of the check standing
# in for the program, then plain runs of one cycle, each with a seed of its
# own, for the program's readings. Runs that would start after 1,650 s are
# skipped.
cd "$(dirname "$0")/../../.."
cell=trinity_large_gen.serve_p12288_n128_c16
bash docs/chip_calls/pr41/call.sh pr41D 1650 \
  change:$cell:4100001009:1:45 \
  change:$cell:4100001103:0:10:fp8,swapped,chunk_cache_dropped,window_as_full,gate_dropped,rope_on_full,post_norms_dropped \
  change:$cell:4100001201:0:10 \
  change:$cell:4100001307:0:10 \
  change:$cell:4100001409:0:10 \
  change:$cell:4100001511:0:10
