#!/bin/bash
# PR 36, call D: count_collections.py on the chip's host, from the tree as
# git would commit it: the callback's price a collection there, and the
# collections a served BERT cycle trips.
cd /root/repo/.scratch/commit
mkdir -p /root/repo/chiprun_out/pr36D
python3 /root/repo/docs/chip_calls/pr36/count_collections.py > /root/repo/chiprun_out/pr36D/count.out 2> /root/repo/chiprun_out/pr36D/count.err
echo rc=$?
grep -h "^callback\|^span:\|^window-or-setup\|serve_closed: window" /root/repo/chiprun_out/pr36D/count.out | cut -c1-700
tail -n 1 /root/repo/chiprun_out/pr36D/count.out | cut -c1-400
grep -c "for a usual" /root/repo/chiprun_out/pr36D/count.err
