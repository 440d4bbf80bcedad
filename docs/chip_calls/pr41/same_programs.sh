#!/bin/bash
# The serving programs (prefill chunk, decode step) of the named cells at the
# parent and at this tree, each unpacked at the same path and compiled for a
# described v5e (compile_programs.py); prints one line a checkout and cell.
# Equal "lowered" and "compiled" digests: the same programs.
#   bash docs/chip_calls/pr41/same_programs.sh <parent commit> <scratch dir> mimo ling
set -e
cd "$(dirname "$0")/../../.."
parent=$1; side=$2/side; shift 2
script=$(mktemp); cp docs/chip_calls/pr41/compile_programs.py $script
for rev in $parent $(git write-tree); do
  rm -rf $side; mkdir -p $side
  git archive $rev | tar -x -C $side
  for cell in "$@"; do
    JAX_PLATFORMS=cpu python $script $side $cell 2>/dev/null | tail -n 1
  done
done
rm -rf $side $script
