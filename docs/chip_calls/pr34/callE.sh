#!/bin/bash
# PR 34, call E: the final tree as git would commit it (.scratch/commit) and
# the parent under this PR's benchmark files (.scratch/parent), one call:
# the host-memory probe (two minutes); the new cell with the float8 control
# standing in (call D1's seventh run was ended inside it, at the machine's host
# memory; the comparison holds 4 GB less since); then the cells the benchmark
# had that share the most code with the change (the fine-tune cell: dl/train.py;
# the Ling cell: dl/lm.py, dl/mla.py, dl/moe.py), parent and change on one seed
# a pair. The Brumby cell and the BERT serve cell are left to a later call or to
# the driver: the chip budget keeps a reserve for one more control run.
cd /root/repo
python3 docs/chip_calls/pr34/probe_host_memory.py > chiprun_out/pr34E_probe.txt 2>&1
m=moonlight_16b_a3b_train.pretrain_pack8192
f=bert_base_cls.finetune_doc512; l=ling3_flash_gen.serve_p1152_n128_c128
CHANGE_DIR=/root/repo/.scratch/commit bash docs/chip_calls/pr34/call.sh pr34E 2750 \
  change:$m:3400000179:0:fp8 \
  parent:$f:3400000211:0 change:$f:3400000211:0 \
  parent:$l:3400000223:0 change:$l:3400000223:0
