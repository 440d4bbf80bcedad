#!/bin/bash
# PR 36, call C: the final tree. The BERT serve cell again, because call A's
# tree paid 2.3 us a collection in the collector's callback (it read the
# environment each time) and drained the collector at every span's end: the
# change traced, then three pairs, one seed a pair, first side alternating.
# Then the Ling cell: the change traced and two pairs (the generators run
# the least of the changed code: one comparison a decode step, some ten
# spans a cycle).
cd /root/repo
bash docs/chip_calls/pr36/call.sh pr36C 2500 \
  change:bert_base_cls.serve_doc512_c256:3600000229:1 \
  parent:bert_base_cls.serve_doc512_c256:3600000233:0 change:bert_base_cls.serve_doc512_c256:3600000233:0 \
  change:bert_base_cls.serve_doc512_c256:3600000241:0 parent:bert_base_cls.serve_doc512_c256:3600000241:0 \
  parent:bert_base_cls.serve_doc512_c256:3600000251:0 change:bert_base_cls.serve_doc512_c256:3600000251:0 \
  change:ling3_flash_gen.serve_p1152_n128_c128:3600000193:1 \
  parent:ling3_flash_gen.serve_p1152_n128_c128:3600000211:0 change:ling3_flash_gen.serve_p1152_n128_c128:3600000211:0 \
  change:ling3_flash_gen.serve_p1152_n128_c128:3600000223:0 parent:ling3_flash_gen.serve_p1152_n128_c128:3600000223:0
