#!/bin/bash
# PR 35, call B: the working tree (.scratch/commit: what git would commit of
# it) against the parent under this PR's benchmark files (.scratch/parent),
# one call. First the kernels alone by block and piece (tune.py in the form
# it had then, `block:piece` arguments; 4 minutes);
# then the Moonlight cell: parent traced (the new metric's reader on a
# program that lacks its counter; the parent's breakdown on this machine),
# change traced (the matched list of `mla_roofline.train`, the new share),
# then parent, change, change, parent untraced, one seed a pair.
cd /root/repo
python3 docs/chip_calls/pr35/tune.py 1024:1024 1024:512 1024:256 512:512 \
  > chiprun_out/pr35B_tune.txt 2>&1
grep "^{" chiprun_out/pr35B_tune.txt
m=moonlight_16b_a3b_train.pretrain_pack8192
CHANGE_DIR=/root/repo/.scratch/commit bash docs/chip_calls/pr34/call.sh pr35B 2900 \
  parent:$m:3500000011:1 change:$m:3500000011:1 \
  parent:$m:3500000023:0 change:$m:3500000023:0 \
  change:$m:3500000037:0 parent:$m:3500000037:0
