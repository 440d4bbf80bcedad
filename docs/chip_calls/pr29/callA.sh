#!/bin/bash
# the claimed cell, parent against change, one seed a pair and the first side
# alternating; a traced run of each side; then one pair in each other cell
S=bert_base_cls.serve_doc512_c256; F=bert_base_cls.finetune_doc512; G=brumby_14b_gen.serve_p576_n128_c16
bash docs/chip_calls/pr29/call.sh A 3150 \
  change:$S:2900000011:0 parent:$S:2900000011:0 \
  parent:$S:2900000023:0 change:$S:2900000023:0 \
  change:$S:2900000039:0 parent:$S:2900000039:0 \
  change:$S:2900000041:1 parent:$S:2900000041:1 \
  parent:$F:2900000053:0 change:$F:2900000053:0 \
  change:$G:2900000069:0 parent:$G:2900000069:0 \
  change:$F:2900000071:1
