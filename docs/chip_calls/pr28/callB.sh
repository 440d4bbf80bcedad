#!/bin/bash
# the parent with this PR's benchmark files laid over it: has to fail at once
rm -rf .scratch/parent_new && cp -r .scratch/parent .scratch/parent_new
cp BENCHMARK.json .scratch/parent_new/ && cp -r benchmark/. .scratch/parent_new/benchmark/
( cd .scratch/parent_new && s=$(date +%s.%N); timeout 600 python3 benchmark/run.py --workload brumby_14b_gen.serve_p576_n128_c16 --seed 2800000101 --seconds 45 --trace 0 > ../../chiprun_out/parent_brumby.out 2> ../../chiprun_out/parent_brumby.err; echo "parent_brumby rc=$? seconds $(echo "$(date +%s.%N) - $s" | bc)" )
tail -3 chiprun_out/parent_brumby.err | cut -c1-300
W=brumby_14b_gen.serve_p576_n128_c16
i=0
for spec in "2800000111:fp8,swapped" "2800000127:fp8" "2800000131:" "2800000147:" "2800000153:" "2800000169:"; do
  i=$((i+1)); seed=${spec%%:*}; st=${spec#*:}
  extra=""; [ -n "$st" ] && extra="--stand-in $st"
  s=$(date +%s)
  python3 benchmark/run.py --workload $W --seed $seed --seconds 45 --trace 0 $extra > chiprun_out/B$i.out 2> chiprun_out/B$i.err
  echo "B$i rc=$? seed $seed wall $(( $(date +%s) - s )) s"
  grep "serve_generate: window\|set-up parts\|memory: peak\|check: a served\|check: stand-in\|check: 8 of" chiprun_out/B$i.out | cut -c1-700
  tail -1 chiprun_out/B$i.out | cut -c1-1500
done
s=$(date +%s)
python3 benchmark/run.py --workload $W --seed 2800000173 --seconds 45 --trace 1 > chiprun_out/B7t.out 2> chiprun_out/B7t.err
echo "B7t rc=$? wall $(( $(date +%s) - s )) s"
grep "serve_generate: window\|set-up parts\|trace:" chiprun_out/B7t.out | cut -c1-1500
tail -1 chiprun_out/B7t.out | cut -c1-6000
tail -5 chiprun_out/B7t.err | cut -c1-300
