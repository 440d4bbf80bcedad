#!/bin/bash
# usage: call.sh <tag> <budget_s> <run>...   run = side:cell:seed:trace
# side = parent (.scratch/parent: git archive of the parent commit with this
# PR's BENCHMARK.json and benchmark/ laid over it) | change (.scratch/commit:
# what git would commit of this tree); docs/chip_calls/pr34/prepare.sh
# <parent commit> makes both. The loop is docs/chip_calls/pr34/call.sh's with
# two things more: the call ends at once if the change's first run fails (a
# broken instrument must not cost the parent's runs), and every slow-unit
# line the program logged ("... for a usual ...", on standard error at
# WARNING) is gathered by run into slow_units.txt. Runs after <budget_s>
# seconds are skipped, and said so.
tag=$1; budget=$2; shift 2
root=/root/repo
out=$root/chiprun_out/$tag; mkdir -p $out
t0=$(date +%s)
i=0; change_seen=0
for run in "$@"; do
  i=$((i+1))
  IFS=: read side cell seed trace <<< "$run"
  now=$(( $(date +%s) - t0 ))
  if [ $now -gt $budget ]; then echo "SKIPPED $run at ${now}s" | tee -a $out/summary.txt; continue; fi
  dir=$root/.scratch/commit; [ "$side" = parent ] && dir=$root/.scratch/parent
  name=$(printf "%02d" $i)_${side}_${cell##*.}_${seed}_t${trace}
  ( cd $dir && python3 benchmark/run.py --workload $cell --seed $seed --seconds 45 --trace $trace > $out/$name.out 2> $out/$name.err ); rc=$?
  took=$(( $(date +%s) - t0 - now ))
  echo "$name rc=$rc took=${took}s" | tee -a $out/summary.txt
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
            print("  ", k, r["breakdown"][k][:8])
except Exception as e:
    print("  no result:", e)
PY
  grep -h "serve_closed: \|serve_generate\|finetune: epochs\|pretrain_lm: \|^check: \|^memory: \|reference and comparison\|set-up parts\|^window:\|^trace:" $out/$name.out | cut -c1-1800 >> $out/summary.txt
  n=$(grep -c "for a usual" $out/$name.err)
  echo "$name: $n slow-unit line(s)" | tee -a $out/slow_units.txt
  grep -h "for a usual" $out/$name.err | cut -c1-700 | tee -a $out/slow_units.txt
  grep -v "for a usual" $out/$name.err | tail -n 12 | cut -c1-600 >> $out/summary.txt
  if [ "$side" = change ] && [ $change_seen = 0 ]; then
    change_seen=1
    if ! grep -q '"correct": true' $out/$name.json; then
      echo "the change's first run gave no correct result: stopping" | tee -a $out/summary.txt
      tail -n 40 $out/$name.err
      exit 1
    fi
  fi
done
echo "total $(( $(date +%s) - t0 ))s" | tee -a $out/summary.txt
