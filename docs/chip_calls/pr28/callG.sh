#!/bin/bash
# call G (review round, last): the final committed files, one seed, the three
# controls against the re-read limits (both 0.3)
cd .scratch/commit || exit 9
W=brumby_14b_gen.serve_p576_n128_c16
O=../../chiprun_out
seed=2800000613
s=$(date +%s)
python3 benchmark/run.py --workload $W --seed $seed --seconds 45 --trace 0 --stand-in fp8,swapped,chunk_state_dropped > $O/G_$seed.out 2> $O/G_$seed.err
echo "G rc=$? seed $seed wall $(( $(date +%s) - s )) s"
grep "serve_generate: window\|set-up parts\|memory: peak\|check: a served\|check: stand-in\|check: 8 of" $O/G_$seed.out | cut -c1-500
tail -1 $O/G_$seed.out | cut -c1-2500
