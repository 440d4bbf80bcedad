#!/bin/bash
W=bert_base_cls.finetune_doc512_dp4
for spec in "2800000311:0" "2800000323:0" "2800000331:1"; do
  seed=${spec%%:*}; tr=${spec#*:}; s=$(date +%s)
  python3 benchmark/run.py --workload $W --seed $seed --seconds 45 --trace $tr > chiprun_out/D_$seed.out 2> chiprun_out/D_$seed.err
  echo "D rc=$? seed $seed trace $tr wall $(( $(date +%s) - s )) s"
  grep "finetune:\|set-up parts\|memory: peak\|trace:" chiprun_out/D_$seed.out | cut -c1-900
  tail -1 chiprun_out/D_$seed.out | cut -c1-3000
  tail -8 chiprun_out/D_$seed.err | cut -c1-200
done
