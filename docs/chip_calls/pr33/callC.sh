#!/bin/bash
# PR 33, call C: the final tree (git archive $(git write-tree) unpacked into
# .scratch/commit) against the parent (.scratch/parent: git archive of
# 5e274b2 with this PR's BENCHMARK.json and metric file laid over it). The
# served BERT cell: parent, change, change, parent on two seeds, a traced run
# of the change and four more seeds of the change alone; the fine-tune cell:
# parent, change on one seed.
cd /root/repo
ft=bert_base_cls.finetune_doc512; sv=bert_base_cls.serve_doc512_c256
CHANGE_DIR=/root/repo/.scratch/commit bash docs/chip_calls/pr31/call.sh pr33C 2900 \
  parent:$sv:3300000211:0 change:$sv:3300000211:0 \
  change:$sv:3300000223:0 parent:$sv:3300000223:0 \
  change:$sv:3300000239:1 \
  change:$sv:3300000241:0 change:$sv:3300000253:0 \
  change:$sv:2147483791:0 change:$sv:3300000271:0 \
  parent:$ft:3300000287:0 change:$ft:3300000287:0
