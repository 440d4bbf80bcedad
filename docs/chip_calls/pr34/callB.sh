#!/bin/bash
# PR 34, call B: the change after call A (the experts' rows through plain
# products a piece at a time; the comparison's host memory cut): one traced
# run, one run with the three controls standing in.
cd /root/repo
c=moonlight_16b_a3b_train.pretrain_pack8192
bash docs/chip_calls/pr34/call.sh pr34B 2400 \
  change:$c:3400000019:1 \
  change:$c:3400000031:0:fp8,router_grad_dropped,bias_frozen
