#!/bin/bash
# PR 34, call G: the final tree as git would commit it (.scratch/commit), one
# traced run of the new cell on a seed of its own: the per-layer metrics under
# their final names, and `correct` under the limits as committed.
cd /root/repo
CHANGE_DIR=/root/repo/.scratch/commit bash docs/chip_calls/pr34/call.sh pr34G 60 \
  change:moonlight_16b_a3b_train.pretrain_pack8192:3400000191:1
