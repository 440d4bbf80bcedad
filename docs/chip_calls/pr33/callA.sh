#!/bin/bash
# PR 33, call A: the working tree, the served BERT cell at slices of 64, 32
# and 128 rows: a traced run and a timed run each, the same two seeds for the
# three. PREDICT_SLICE is edited in the chip machine's own copy of the tree
# between runs (the copy is thrown away with the machine); one checkout, so
# every run after the first loads its programs from .jax_cache.
cd /root/repo
sv=bert_base_cls.serve_doc512_c256
for rows in 64 32 128; do
  sed -i "s/^PREDICT_SLICE = .*/PREDICT_SLICE = $rows/" alink_tpu/dl/train.py
  grep -n "^PREDICT_SLICE" alink_tpu/dl/train.py
  bash docs/chip_calls/pr31/call.sh pr33A_s$rows 1600 \
    change:$sv:3300000017:1 change:$sv:3300000029:0
done
