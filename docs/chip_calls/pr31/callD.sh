#!/bin/bash
# PR 31, call D: the fine-tune cell's compared numbers over seven seeds in one
# process a side (benchmark/tests/readings.py: the op's own step through its
# checked steps, no window), parent then change on the same seeds; then
# chip_smoke.py on the change (its `kernels` phase holds the fused core
# against XLA at highest precision; its `finetune` phase now runs the kernels).
cd /root/repo
SEEDS=3100000101,3100000113,3100000127,3100000139,3100000149,3100000157,2147483659
out=/root/repo/chiprun_out/pr31D; mkdir -p $out
for side in parent change; do
  dir=/root/repo; [ $side = parent ] && dir=/root/repo/.scratch/parent
  [ $side = change ] && [ -n "$CHANGE_DIR" ] && dir=$CHANGE_DIR
  ( cd $dir && python3 -m benchmark.tests.readings \
      --workload bert_base_cls.finetune_doc512 --seeds $SEEDS \
      --out $out/readings_$side.json ) > $out/readings_$side.out 2> $out/readings_$side.err
  echo "$side rc=$?"; grep "^readings:" $out/readings_$side.out | cut -c1-700
done
python chip_smoke.py > $out/chip_smoke.out 2> $out/chip_smoke.err; echo "chip_smoke rc=$?"
tail -n 1 $out/chip_smoke.out | cut -c1-3000
