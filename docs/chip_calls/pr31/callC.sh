#!/bin/bash
# PR 31, call C: the final tree (git archive $(git write-tree) unpacked into
# .scratch/commit) against the parent (.scratch/parent: git archive of
# c20cc40 with this PR's BENCHMARK.json and benchmark/metrics laid over it),
# both BERT cells: parent, change, change, parent on two seeds a cell, a traced
# run of the change, and the served cell's change on three more seeds.
cd /root/repo
ft=bert_base_cls.finetune_doc512; sv=bert_base_cls.serve_doc512_c256
CHANGE_DIR=/root/repo/.scratch/commit bash docs/chip_calls/pr31/call.sh pr31C 3000 \
  parent:$ft:3100000211:0 change:$ft:3100000211:0 \
  change:$ft:3100000223:0 parent:$ft:3100000223:0 \
  change:$ft:2147483777:1 \
  parent:$sv:3100000237:0 change:$sv:3100000237:0 \
  change:$sv:3100000241:0 parent:$sv:3100000241:0 \
  change:$sv:3100000253:1 \
  change:$sv:3100000267:0 change:$sv:3100000271:0 change:$sv:2147483783:0
