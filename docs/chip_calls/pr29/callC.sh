#!/bin/bash
# the final tree, from the committed files (.scratch/commit = git archive
# $(git write-tree)): the claimed cell in four more pairs and two more runs of
# the change alone (six seeds of the change in all), a traced run, and the
# fine-tune cell once
S=bert_base_cls.serve_doc512_c256; F=bert_base_cls.finetune_doc512
CHANGE_DIR=/root/repo/.scratch/commit bash docs/chip_calls/pr29/call.sh C 3000 \
  change:$S:2900000211:0 parent:$S:2900000211:0 \
  parent:$S:2900000223:0 change:$S:2900000223:0 \
  change:$S:2900000239:0 parent:$S:2900000239:0 \
  parent:$S:2900000241:0 change:$S:2900000241:0 \
  change:$S:2900000257:0 change:$S:2900000263:0 \
  change:$S:2900000271:1 \
  change:$F:2900000283:0
