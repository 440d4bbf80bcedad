#!/bin/bash
# PR 32, call C: the three cells the benchmark had, the working tree against
# the parent (.scratch/p32: git archive of 926b785 with this PR's
# BENCHMARK.json and benchmark/ laid over it) on one machine. Brumby, which
# runs the refactored dl/lm.py: parent, change (each a checkout's first
# run), change, parent (warm). The BERT cells, which run dl/sharding.py's
# new branches and nothing else of the change: parent, change.
cd /root/repo
br=brumby_14b_gen.serve_p576_n128_c16
ft=bert_base_cls.finetune_doc512
sv=bert_base_cls.serve_doc512_c256
bash docs/chip_calls/pr32/call.sh pr32C 2700 \
  parent:$br:3200000213:0 change:$br:3200000213:0 \
  change:$br:3200000227:0 parent:$br:3200000227:0 \
  parent:$ft:3200000239:0 change:$ft:3200000239:0 \
  parent:$sv:3200000251:0 change:$sv:3200000251:0
