#!/bin/bash
# Call B: the Trinity cell after its check compares the ninth decile of the
# served log-probabilities' gaps (serve_generate_gqa), on the tree as git
# would commit it (.scratch/commit, made by prepare.sh): one run with every
# control of the check standing in for the program, then plain runs, each
# with a seed of its own, for the spread of the rate and the program's own
# readings; the last at --seconds 10 (one cycle), for one more reading. Runs
# that would start after 1,450 s are skipped.
cd "$(dirname "$0")/../../.."
cell=trinity_large_gen.serve_p12288_n128_c16
bash docs/chip_calls/pr41/call.sh pr41B 1450 \
  change:$cell:4100000307:0:45:fp8,swapped,chunk_cache_dropped,window_as_full,gate_dropped,rope_on_full,post_norms_dropped \
  change:$cell:4100000401:0:45 \
  change:$cell:4100000503:0:45 \
  change:$cell:4100000607:0:45 \
  change:$cell:4100000709:0:10
