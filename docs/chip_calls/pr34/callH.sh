#!/bin/bash
# PR 34, call H (the review round): the final tree as git would commit it
# (.scratch/commit) and the parent under this PR's benchmark files
# (.scratch/parent), one call: the new cell traced on a seed of its own (the
# first chip reading of `moe_roofline.train` under the matcher as committed,
# and `correct` under the limits the review asked for: `loss_gap`,
# `first_grad_diff_worst`, a `change_norm_gap` held against float8); the
# fine-tune cell, parent and change on one seed (dl/train.py's ladder rule
# changed by a line); the new cell on two more seeds, as far as the budget goes.
cd /root/repo
m=moonlight_16b_a3b_train.pretrain_pack8192
f=bert_base_cls.finetune_doc512
CHANGE_DIR=/root/repo/.scratch/commit bash docs/chip_calls/pr34/call.sh pr34H 1350 \
  change:$m:3400000251:1 \
  parent:$f:3400000281:0 change:$f:3400000281:0 \
  change:$m:3400000263:0 change:$m:3400000277:0
