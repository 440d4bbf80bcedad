#!/bin/bash
# PR 34, call F: the Brumby generator cell (dl/lm.py's loader and loops are
# shared with the change), parent and change on one seed, the checkouts of
# call E. The BERT serve cell shares its configuration with the fine-tune cell
# call E measured and is left to the driver.
cd /root/repo
b=brumby_14b_gen.serve_p576_n128_c16
CHANGE_DIR=/root/repo/.scratch/commit bash docs/chip_calls/pr34/call.sh pr34F 600 \
  parent:$b:3400000239:0 change:$b:3400000239:0
