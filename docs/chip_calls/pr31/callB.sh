#!/bin/bash
# PR 31, call B: the encoder's threshold readings again (parts 3, 4: the
# encoder kernel against XLA, and the kernels' names in a device trace), then
# one traced run of each BERT cell on the working tree.
cd /root/repo
python docs/chip_calls/pr31/threshold.py chiprun_out/pr31_thresholdB 34
bash docs/chip_calls/pr31/call.sh pr31B 1500 \
  change:bert_base_cls.finetune_doc512:3100000019:1 \
  change:bert_base_cls.serve_doc512_c256:3100000033:1
