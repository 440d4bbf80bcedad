#!/bin/bash
# usage: call.sh <tag> <budget_s> <run>...
#   run = side:cell:seed:trace:seconds[:stand-ins, comma-separated]
# side = here (the copy's own tree) | parent (.scratch/parent: git archive of
# the parent commit with this PR's BENCHMARK.json and benchmark/ laid over
# it) | change (.scratch/commit: what git would commit of this tree). PR
# 38's call.sh with the Trinity generator's line kept too: PR 36's loop
# (docs/chip_calls/pr36/call.sh) with a run's seconds and its controls; it
# never stops early, since a run with controls is not `correct` by design.
tag=$1; budget=$2; shift 2
root=$(cd "$(dirname "$0")/../../.." && pwd)
out=$root/chiprun_out/$tag; mkdir -p $out
t0=$(date +%s)
i=0
for run in "$@"; do
  i=$((i+1))
  IFS=: read side cell seed trace seconds stand <<< "$run"
  now=$(( $(date +%s) - t0 ))
  if [ $now -gt $budget ]; then echo "SKIPPED $run at ${now}s" | tee -a $out/summary.txt; continue; fi
  dir=$root; [ "$side" = parent ] && dir=$root/.scratch/parent
  [ "$side" = change ] && dir=$root/.scratch/commit
  extra=(); [ -n "$stand" ] && extra=(--stand-in "$stand")
  name=$(printf "%02d" $i)_${side}_${cell##*.}_${seed}_t${trace}
  ( cd $dir && python3 benchmark/run.py --workload $cell --seed $seed --seconds $seconds --trace $trace "${extra[@]}" > $out/$name.out 2> $out/$name.err ); rc=$?
  took=$(( $(date +%s) - t0 - now ))
  echo "$name rc=$rc took=${took}s stand-ins=${stand:-none}" | tee -a $out/summary.txt
  tail -n 1 $out/$name.out > $out/$name.json
  python3 - "$out/$name.json" <<'PY' | tee -a $out/summary.txt
import json, sys
try:
    r = json.load(open(sys.argv[1]))
    m = {k: v["value"] for k, v in r["metrics"].items()}
    print("  correct", r["correct"], "failed", r["failed"], "/", r["attempted"],
          {k: round(v, 4) for k, v in m.items()},
          "mem", r["device"].get("memory_peak_bytes"),
          "busy", r["device"].get("busy_s"), "of", r["device"].get("window_s"),
          {k: c["value"] for k, c in r.get("compared", {}).items()})
    for k in ("device_ops", "idle_gaps"):
        if k in r.get("breakdown", {}):
            print("  ", k, r["breakdown"][k][:10])
except Exception as e:
    print("  no result:", e)
PY
  grep -h "serve_generate\|gen_mimo\|gen_trinity\|^check: \|^memory: \|reference and comparison\|set-up parts\|^window:\|^trace:" $out/$name.out | cut -c1-2500 >> $out/summary.txt
  grep -h "for a usual" $out/$name.err | cut -c1-700 >> $out/slow_units.txt
  grep -v "for a usual" $out/$name.err | tail -n 25 | cut -c1-600 >> $out/summary.txt
done
echo "total $(( $(date +%s) - t0 ))s" | tee -a $out/summary.txt
