#!/bin/bash
# PR 36, call B: the Moonlight training cell (bound 1%: the cell a pause
# costs a verdict), the tree as git would commit it against the parent under
# this PR's benchmark files, one machine: the change traced, then four pairs,
# one seed a pair, first side alternating; then the Brumby cell: the change
# traced and two pairs.
cd /root/repo
bash docs/chip_calls/pr36/call.sh pr36B 3250 \
  change:moonlight_16b_a3b_train.pretrain_pack8192:3600000113:1 \
  parent:moonlight_16b_a3b_train.pretrain_pack8192:3600000127:0 change:moonlight_16b_a3b_train.pretrain_pack8192:3600000127:0 \
  change:moonlight_16b_a3b_train.pretrain_pack8192:3600000131:0 parent:moonlight_16b_a3b_train.pretrain_pack8192:3600000131:0 \
  parent:moonlight_16b_a3b_train.pretrain_pack8192:3600000149:0 change:moonlight_16b_a3b_train.pretrain_pack8192:3600000149:0 \
  change:moonlight_16b_a3b_train.pretrain_pack8192:3600000157:0 parent:moonlight_16b_a3b_train.pretrain_pack8192:3600000157:0 \
  change:brumby_14b_gen.serve_p576_n128_c16:3600000163:1 \
  parent:brumby_14b_gen.serve_p576_n128_c16:3600000179:0 change:brumby_14b_gen.serve_p576_n128_c16:3600000179:0 \
  change:brumby_14b_gen.serve_p576_n128_c16:3600000181:0 parent:brumby_14b_gen.serve_p576_n128_c16:3600000181:0
