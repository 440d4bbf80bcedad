#!/bin/bash
# PR 37, call D: the final tree once more (after call B retention_chunk's XLA
# form took its part inside the chunk back into its loop over heads; the
# kernel path's compiled program is call B's): the Brumby cell traced from the
# tree as git would commit it, and one pair against the parent.
cd /root/repo
cell=brumby_14b_gen.serve_p576_n128_c16
bash docs/chip_calls/pr36/call.sh pr37D 1200 \
  change:$cell:3700000127:1 \
  change:$cell:3700000139:0 parent:$cell:3700000139:0
