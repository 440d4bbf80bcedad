#!/bin/bash
# PR 34, call D1: the final tree as git would commit it (.scratch/commit, from
# prepare.sh): the new cell six times, each run on a seed of its own, then one
# run on a seventh with the three controls standing in (the float8 one last).
cd /root/repo
c=moonlight_16b_a3b_train.pretrain_pack8192
CHANGE_DIR=/root/repo/.scratch/commit bash docs/chip_calls/pr34/call.sh pr34D1 2900 \
  change:$c:3400000101:0 change:$c:3400000117:0 change:$c:3400000129:0 \
  change:$c:3400000137:0 change:$c:3400000149:0 change:$c:3400000153:0 \
  change:$c:3400000167:0:router_grad_dropped,bias_frozen,fp8
