#!/bin/bash
# call F (review round): the committed files alone (.scratch/commit is
# git archive $(git write-tree)), the Brumby cell with the gate's bias:
# two control runs, three sound ones (the last with a window of 90 s), one traced
cd .scratch/commit || exit 9
W=brumby_14b_gen.serve_p576_n128_c16
O=../../chiprun_out
t0=$(date +%s)
for spec in "2800000511:0:45:fp8,swapped,chunk_state_dropped" "2800000523:0:45:fp8,chunk_state_dropped" \
            "2800000531:0:45:" "2800000569:1:45:" "2800000547:0:45:" "2800000557:0:90:"; do
  IFS=: read seed tr secs st <<< "$spec"
  if [ $(( $(date +%s) - t0 )) -gt ${BUDGET_S:-1500} ]; then echo "F SKIPPED seed $seed"; continue; fi
  extra=""; [ -n "$st" ] && extra="--stand-in $st"
  s=$(date +%s)
  python3 benchmark/run.py --workload $W --seed $seed --seconds $secs --trace $tr $extra > $O/F_$seed.out 2> $O/F_$seed.err
  echo "F rc=$? seed $seed trace $tr seconds $secs wall $(( $(date +%s) - s )) s"
  grep "serve_generate: window\|set-up parts\|memory: peak\|check: a served\|check: stand-in\|check: 8 of\|^trace:" $O/F_$seed.out | cut -c1-700
  tail -1 $O/F_$seed.out | cut -c1-3000
  grep "^compared" $O/F_$seed.err | tr '\n' ';' | cut -c1-900; echo
done
