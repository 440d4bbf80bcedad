#!/bin/bash
# PR 36, call A: the two BERT cells, the tree as git would commit it against
# the parent under this PR's benchmark files, one machine. First the change
# traced in both cells (the ten new metrics; the call stops if the first
# fails), then four pairs a cell, one seed a pair, first side alternating.
cd /root/repo
bash docs/chip_calls/pr36/call.sh pr36A 3250 \
  change:bert_base_cls.serve_doc512_c256:3600000011:1 change:bert_base_cls.finetune_doc512:3600000023:1 \
  parent:bert_base_cls.serve_doc512_c256:3600000037:0 change:bert_base_cls.serve_doc512_c256:3600000037:0 \
  change:bert_base_cls.serve_doc512_c256:3600000041:0 parent:bert_base_cls.serve_doc512_c256:3600000041:0 \
  parent:bert_base_cls.serve_doc512_c256:3600000053:0 change:bert_base_cls.serve_doc512_c256:3600000053:0 \
  change:bert_base_cls.serve_doc512_c256:3600000067:0 parent:bert_base_cls.serve_doc512_c256:3600000067:0 \
  parent:bert_base_cls.finetune_doc512:3600000079:0 change:bert_base_cls.finetune_doc512:3600000079:0 \
  change:bert_base_cls.finetune_doc512:3600000083:0 parent:bert_base_cls.finetune_doc512:3600000083:0 \
  parent:bert_base_cls.finetune_doc512:3600000097:0 change:bert_base_cls.finetune_doc512:3600000097:0 \
  change:bert_base_cls.finetune_doc512:3600000101:0 parent:bert_base_cls.finetune_doc512:3600000101:0
