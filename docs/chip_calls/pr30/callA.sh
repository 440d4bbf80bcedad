#!/bin/bash
# no gain is claimed: every cell, parent against the final tree from the
# committed files (.scratch/commit = git archive $(git write-tree)), one seed a
# pair, run as parent, change, change, parent so that each side has a
# checkout's first run (which compiles) and a warm one
S=bert_base_cls.serve_doc512_c256; F=bert_base_cls.finetune_doc512; G=brumby_14b_gen.serve_p576_n128_c16
CHANGE_DIR=/root/repo/.scratch/commit bash docs/chip_calls/pr29/call.sh pr30A 3300 \
  parent:$F:3000000011:0 change:$F:3000000011:0 \
  change:$F:3000000023:0 parent:$F:3000000023:0 \
  parent:$S:3000000037:0 change:$S:3000000037:0 \
  change:$S:3000000041:0 parent:$S:3000000041:0 \
  parent:$G:3000000053:0 change:$G:3000000053:0 \
  change:$G:3000000067:0 parent:$G:3000000067:0
