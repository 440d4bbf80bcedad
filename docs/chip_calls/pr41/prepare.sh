#!/bin/bash
# The two checkouts a comparing call runs from, both under .scratch/ (git-
# ignored, copied to the chip): parent = git archive of the parent commit with
# this PR's BENCHMARK.json and benchmark/ laid over it (as a comparing
# check lays them); commit = what git would commit of this tree (git add -A first).
# PR 34's prepare.sh with this PR's parent.
set -e
cd "$(dirname "$0")/../../.."
bash docs/chip_calls/pr34/prepare.sh ${1:-25ab7e1cbe446796b11c01816a8facfdd6b95e20}
