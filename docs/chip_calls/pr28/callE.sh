#!/bin/bash
# the committed files alone: .scratch/commit is git archive $(git write-tree)
cd .scratch/commit || exit 9
W=brumby_14b_gen.serve_p576_n128_c16
O=../../chiprun_out
for spec in "2800000411:0:fp8,swapped" "2800000423:0:" "2800000431:0:" "2800000447:1:"; do
  IFS=: read seed tr st <<< "$spec"
  extra=""; [ -n "$st" ] && extra="--stand-in $st"
  s=$(date +%s)
  python3 benchmark/run.py --workload $W --seed $seed --seconds 45 --trace $tr $extra > $O/E_$seed.out 2> $O/E_$seed.err
  echo "E rc=$? seed $seed trace $tr wall $(( $(date +%s) - s )) s"
  grep "serve_generate: window\|set-up parts\|memory: peak\|check: a served\|check: stand-in\|check: 8 of\|^trace:" $O/E_$seed.out | cut -c1-900
  tail -1 $O/E_$seed.out | cut -c1-3500
  grep "^compared" $O/E_$seed.err | tr '\n' ';' | cut -c1-900; echo
done
