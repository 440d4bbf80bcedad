#!/bin/bash
# PR 31, call F: where the served cell's warm set-up goes, change and parent:
# a cold run to fill the checkout's compile cache, then a warm one, each with
# the program's own record of first calls and span sums (warmup_spans.py).
cd /root/repo
out=/root/repo/chiprun_out/pr31F; mkdir -p $out
cell=bert_base_cls.serve_doc512_c256
for side in change parent; do
  dir=/root/repo; [ $side = parent ] && dir=/root/repo/.scratch/parent
  for pass in cold warm; do
    ( cd $dir && python /root/repo/docs/chip_calls/pr31/warmup_spans.py \
        --workload $cell --seed 3100000301 --seconds 3 --trace 0 ) \
      > $out/${side}_$pass.out 2> $out/${side}_$pass.err
    echo "== $side $pass rc=$?"
    grep -h "set-up parts" $out/${side}_$pass.out | cut -c1-300
    grep -h "first calls\|span\.\|timer" $out/${side}_$pass.err | cut -c1-1500
  done
done
