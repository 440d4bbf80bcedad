#!/bin/bash
# PR 37, call A: the kernel alone and inside retention_chunk against the XLA
# form at the cell's shapes (tune.py), then the Brumby cell traced from the
# tree as git would commit it (checkouts: bash docs/chip_calls/pr34/prepare.sh
# 5fae84544ac657735274ad884013909fecc67eb7, after git add -A).
cd /root/repo
python docs/chip_calls/pr37/tune.py 2>&1 | grep -v "persistent compilation cache\|warnings.warn"
bash docs/chip_calls/pr36/call.sh pr37A 800 \
  change:brumby_14b_gen.serve_p576_n128_c16:3700000019:1
