#!/bin/bash
# PR 37, call B: the final tree. tune.py again (the kernel takes its operands
# heads first since call A), then the Brumby cell from the tree as git would
# commit it against the parent under this PR's benchmark files, one machine:
# the change traced, two pairs (one seed a pair, first side alternating), then
# four more runs of the change, a seed each.
cd /root/repo
python docs/chip_calls/pr37/tune.py 2>&1 | grep -v "persistent compilation cache\|warnings.warn"
cell=brumby_14b_gen.serve_p576_n128_c16
bash docs/chip_calls/pr36/call.sh pr37B 2900 \
  change:$cell:3700000037:1 \
  parent:$cell:3700000043:0 change:$cell:3700000043:0 \
  change:$cell:3700000061:0 parent:$cell:3700000061:0 \
  change:$cell:3700000073:0 change:$cell:3700000091:0 \
  change:$cell:3700000103:0 change:$cell:3700000111:0
