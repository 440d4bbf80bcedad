#!/bin/bash
# The two checkouts a comparing call runs from, both under .scratch/ (git-
# ignored, copied to the chip): parent = git archive of the parent commit with
# this PR's BENCHMARK.json and benchmark/ laid over it (as the driver lays
# them); commit = what git would commit of this tree (git add -A first).
set -e
cd /root/repo
parent=${1:-03436fc74f7fc8f6f4180fa5b87794d02c79ce5a}
rm -rf .scratch/parent .scratch/commit
mkdir -p .scratch/parent .scratch/commit
git archive $parent | tar -x -C .scratch/parent
git archive $(git write-tree) | tar -x -C .scratch/commit
cp .scratch/commit/BENCHMARK.json .scratch/parent/BENCHMARK.json
cp -r .scratch/commit/benchmark/. .scratch/parent/benchmark/
du -sh .scratch/parent .scratch/commit
