#!/bin/bash
# PR 36, call E (the review round): (1) REVIEW 36 (2), the fine-tune cell's
# `setup_s`: epoch 0 takes 20 s or 38 s, and call A's change drew the slow
# one four times of four. Parent, change and the change with the step
# watcher taken out (`nowatch`: the commit's checkout with `watch = None` in
# `dl/train.py` for that run, the line count and so the compile cache's keys
# the same),
# through retrievals.py, which prints every cache retrieval's seconds as it
# falls; windows of 10 s, since only set-up is read. (2) The served BERT cell
# on the tree of the review round (an ordinary unit walks no ring, CPU seconds
# are summed by name and observed when read): the change traced, then pairs.
# usage: callE.sh <budget_s>; runs after <budget_s> seconds are skipped.
# The checkouts: `git add -A; bash docs/chip_calls/pr34/prepare.sh <parent>`.
budget=${1:-1650}
root=/root/repo
out=$root/chiprun_out/pr36E; mkdir -p $out
t0=$(date +%s)
i=0
run() {   # side cell seed trace seconds
  i=$((i+1))
  now=$(( $(date +%s) - t0 ))
  if [ $now -gt $budget ]; then echo "SKIPPED $* at ${now}s" | tee -a $out/summary.txt; return; fi
  name=$(printf "%02d" $i)_$1_${2##*.}_$3_t$4
  dir=$root/.scratch/$1; train=$root/.scratch/commit/alink_tpu/dl/train.py
  if [ $1 = nowatch ]; then
    dir=$root/.scratch/commit; cp $train $train.kept
    sed -i 's/watch = _StepWatch() if tracing_enabled() else None/watch = None/' $train
    grep -c "watch = None$" $train | sed 's/^/  nowatch lines: /' | tee -a $out/summary.txt
  fi
  ( cd $dir && python3 $root/docs/chip_calls/pr36/retrievals.py --workload $2 --seed $3 --seconds $5 --trace $4 > $out/$name.out 2> $out/$name.err ); rc=$?
  [ $1 = nowatch ] && mv $train.kept $train
  echo "$name rc=$rc took=$(( $(date +%s) - t0 - now ))s" | tee -a $out/summary.txt
  tail -n 1 $out/$name.out > $out/$name.json
  python3 - "$out/$name.json" <<'PY' | tee -a $out/summary.txt
import json, sys
try:
    r = json.load(open(sys.argv[1]))
    print("  correct", r["correct"], "failed", r["failed"], "/", r["attempted"],
          {k: round(v["value"], 4) for k, v in r["metrics"].items()})
except Exception as e:
    print("  no result:", e)
PY
  grep -h "finetune: epochs\|serve_closed: window\|set-up parts\|^set-up:\|^window:" $out/$name.out | cut -c1-1500 | tee -a $out/summary.txt
  grep -h "^jaxtime" $out/$name.err | grep "compilation_cache\|load" | awk '$NF+0 >= 0.5 || /load/' | cut -c1-200 | tee -a $out/summary.txt
  echo "$name: $(grep -c 'for a usual' $out/$name.err) slow-unit line(s)" | tee -a $out/slow_units.txt
  grep -h "for a usual" $out/$name.err | cut -c1-700 >> $out/slow_units.txt
}
ft=bert_base_cls.finetune_doc512
sv=bert_base_cls.serve_doc512_c256
run parent  $ft 3600000307 0 10
run commit  $ft 3600000307 0 10
run nowatch $ft 3600000311 0 10
run commit  $ft 3600000311 0 10
run parent  $ft 3600000311 0 10
run nowatch $ft 3600000313 0 10
run parent  $ft 3600000313 0 10
run commit  $ft 3600000313 0 10
run commit  $ft 3600000331 0 10
run parent  $ft 3600000331 0 10
run commit  $sv 3600000337 1 45
run commit  $sv 3600000347 0 45
run parent  $sv 3600000349 0 45
run parent  $sv 3600000347 0 45
run commit  $sv 3600000349 0 45
echo "total $(( $(date +%s) - t0 ))s" | tee -a $out/summary.txt
