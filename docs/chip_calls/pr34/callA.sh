#!/bin/bash
# PR 34, call A: the parent on the new cell (it has to fail at once), then the
# change: one traced run (memory, device time by operation, per-layer
# metrics), one run with the three controls standing in.
cd /root/repo
c=moonlight_16b_a3b_train.pretrain_pack8192
bash docs/chip_calls/pr34/call.sh pr34A 2400 \
  parent:$c:3400000007:0 change:$c:3400000019:1 \
  change:$c:3400000031:0:fp8,router_grad_dropped,bias_frozen
