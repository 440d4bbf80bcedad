#!/bin/bash
# PR 31, call J: the served cell's set-up with the fused core traced once a
# program (the working tree) and, for the cause, once a layer (as first
# written: PR31_UNSHARED): the stages of every jit cache miss, and a cProfile
# of a rung's trace on the executor's thread.
cd /root/repo
out=/root/repo/chiprun_out/pr31J; mkdir -p $out
cell=bert_base_cls.serve_doc512_c256
run() {  # name, env...
  name=$1; shift
  ( env "$@" PR31_STAGES=1 python docs/chip_calls/pr31/warmup_spans.py \
      --workload $cell --seed 3100000331 --seconds 3 --trace 0 ) \
    > $out/$name.out 2> $out/$name.err
  echo "== $name rc=$?"
  grep -h "set-up parts" $out/$name.out | cut -c1-300
  grep -h "first calls" $out/$name.err | cut -c1-700
  grep -h "_trace_for_jit" $out/$name.err | awk -F, '$3+0 > 0.15' | cut -c1-100 | sort | uniq -c | sort -rn | head -8
}
run shared_cold PR31_PROFILE_NESTED=1
run shared_warm X=1
rm -rf .jax_cache
run unshared_cold PR31_UNSHARED=1 PR31_PROFILE_NESTED=1
for n in shared_cold unshared_cold; do
  echo "== profile $n"; grep -A60 "outer trace 2" $out/$n.err | cut -c1-180 | head -75
done
