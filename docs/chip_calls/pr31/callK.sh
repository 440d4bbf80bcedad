#!/bin/bash
# PR 31, call K: the final tree (git archive $(git write-tree) in
# .scratch/commit; the fused core traced once a program) alone: each BERT cell
# cold, warm and traced, for `setup_s` beside the parent's of calls C, F, H.
cd /root/repo
ft=bert_base_cls.finetune_doc512; sv=bert_base_cls.serve_doc512_c256
CHANGE_DIR=/root/repo/.scratch/commit bash docs/chip_calls/pr31/call.sh pr31K 1500 \
  change:$ft:3100000411:0 change:$ft:3100000423:1 \
  change:$sv:3100000431:0 change:$sv:3100000443:0 change:$sv:2147483801:1
