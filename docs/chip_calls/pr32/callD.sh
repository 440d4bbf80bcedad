#!/bin/bash
# PR 32, call D: the final tree from `git archive $(git write-tree)`
# (.scratch/c32), the new cell alone: five runs on five seeds, a sixth
# traced, and one run of the Brumby cell from the same files.
cd /root/repo
nc=ling3_flash_gen.serve_p1152_n128_c128
br=brumby_14b_gen.serve_p576_n128_c16
CHANGE_DIR=/root/repo/.scratch/c32 bash docs/chip_calls/pr32/call.sh pr32D 3300 \
  change:$nc:3200000311:0 change:$nc:2147483777:0 change:$nc:3200000347:0 \
  change:$nc:3200000359:1 change:$nc:3200000371:0 change:$nc:3200000383:0 \
  change:$br:3200000397:0
