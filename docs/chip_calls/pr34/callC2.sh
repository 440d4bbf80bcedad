#!/bin/bash
# PR 34, call C2: call C's first run was ended at the machine's 40 GiB of host
# memory inside the float8 control (after the program's own comparison). The
# same run again, with the check saying its host memory as it goes, the
# reference's gradient brought to the host a tensor at a time, and the float8
# control last.
cd /root/repo
c=moonlight_16b_a3b_train.pretrain_pack8192
bash docs/chip_calls/pr34/call.sh pr34C2 2300 \
  change:$c:3400000043:0:router_grad_dropped,bias_frozen,fp8
