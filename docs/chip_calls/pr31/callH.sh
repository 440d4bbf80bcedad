#!/bin/bash
# PR 31, call H: the stages of every jit cache miss in the served cell's
# set-up (trace, lower, the cache's read, the executable's load: wall and the
# thread's CPU seconds), change and parent, a cold run then a warm one.
cd /root/repo
out=/root/repo/chiprun_out/pr31H; mkdir -p $out
cell=bert_base_cls.serve_doc512_c256
for side in change parent; do
  dir=/root/repo; [ $side = parent ] && dir=/root/repo/.scratch/parent
  for pass in cold warm; do
    ( cd $dir && PR31_STAGES=1 python /root/repo/docs/chip_calls/pr31/warmup_spans.py \
        --workload $cell --seed 3100000311 --seconds 3 --trace 0 ) \
      > $out/${side}_$pass.out 2> $out/${side}_$pass.err
    echo "== $side $pass rc=$?"
    grep -h "set-up parts" $out/${side}_$pass.out | cut -c1-300
    [ $pass = warm ] && grep -h "first calls\|^    (" $out/${side}_$pass.err | cut -c1-1500
  done
done
