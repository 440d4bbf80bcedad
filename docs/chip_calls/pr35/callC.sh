#!/bin/bash
# PR 35, call C: the kernels alone once more (tune.py as committed: call
# B's forward column read the loops), then the final tree as git would commit it (.scratch/commit)
# against the parent under this PR's benchmark files (.scratch/parent), one
# call: the Moonlight cell traced on a seed of its own (every number of
# ISSUE 35's table from the committed files), then parent and change in
# pairs, one seed a pair, and the change alone on seeds of its own until six
# untraced runs of it stand; last the fine-tune cell, parent and change on
# one seed (it shares dl/attn_pallas.py's module, none of the changed code).
cd /root/repo
python3 docs/chip_calls/pr35/tune.py 1024 512 > chiprun_out/pr35C_tune.txt 2>&1
grep "^{" chiprun_out/pr35C_tune.txt
m=moonlight_16b_a3b_train.pretrain_pack8192
f=bert_base_cls.finetune_doc512
CHANGE_DIR=/root/repo/.scratch/commit bash docs/chip_calls/pr34/call.sh pr35C 2950 \
  change:$m:3500000101:1 \
  parent:$m:3500000113:0 change:$m:3500000113:0 \
  change:$m:3500000127:0 parent:$m:3500000127:0 \
  change:$m:3500000131:0 change:$m:3500000149:0 \
  change:$m:3500000157:0 change:$m:3500000163:0 \
  parent:$f:3500000171:0 change:$f:3500000171:0
