#!/bin/bash
# PR 31, call L: the served cell, the final tree against the parent on one
# machine: parent, change (each a checkout's first run), change, parent
# (warm): `setup_s` side by side, and whether a window's later cycles
# lengthen on both sides (they did in call K's two runs of the change).
cd /root/repo
sv=bert_base_cls.serve_doc512_c256
CHANGE_DIR=/root/repo/.scratch/commit bash docs/chip_calls/pr31/call.sh pr31L 1200 \
  parent:$sv:3100000511:0 change:$sv:3100000511:0 \
  change:$sv:3100000523:0 parent:$sv:3100000523:0
