#!/bin/bash
# PR 34, call C: the change after call B (the reference's scores stand in
# memory before its softmax, so that the float8 control compiles; the table
# sized from 2.0 rows/s/chip): one run with the three controls standing in,
# one with the float8 control alone, two plain runs. Every run on a seed of its
# own; the readings of all four are what the traffic file's limits are set from.
cd /root/repo
c=moonlight_16b_a3b_train.pretrain_pack8192
bash docs/chip_calls/pr34/call.sh pr34C 2300 \
  change:$c:3400000043:0:fp8,router_grad_dropped,bias_frozen \
  change:$c:3400000059:0:fp8 \
  change:$c:3400000067:0 \
  change:$c:3400000071:0
