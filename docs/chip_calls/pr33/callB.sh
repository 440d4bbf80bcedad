#!/bin/bash
# PR 33, call B: the working tree. (1) One traced run at 64 rows a slice with
# the program's span log on (ALINK_TRACE_LOG), and gaps.py over it: where the
# batch's unnamed 10 ms lie. (2) 64 against 32 rows a slice on two more seeds,
# timed runs, 64, 32, 32, 64, and the parent (.scratch/parent: git archive of
# 5e274b2 with this PR's BENCHMARK.json and metric file laid over it) on the
# same two seeds.
cd /root/repo
sv=bert_base_cls.serve_doc512_c256
mkdir -p chiprun_out/pr33B
set_slice() { sed -i "s/^PREDICT_SLICE = .*/PREDICT_SLICE = $1/" alink_tpu/dl/train.py; grep -n "^PREDICT_SLICE" alink_tpu/dl/train.py; }
set_slice 64
ALINK_TRACE_LOG=/root/repo/chiprun_out/pr33B/spans.jsonl bash docs/chip_calls/pr31/call.sh pr33B_log 1600 change:$sv:3300000101:1
python3 docs/chip_calls/pr33/gaps.py chiprun_out/pr33B/spans.jsonl 40 | tee chiprun_out/pr33B/gaps.txt
tail -n 1200 chiprun_out/pr33B/spans.jsonl > chiprun_out/pr33B/spans_tail.jsonl; rm chiprun_out/pr33B/spans.jsonl
bash docs/chip_calls/pr31/call.sh pr33B_s64a 1600 change:$sv:3300000113:0
set_slice 32
bash docs/chip_calls/pr31/call.sh pr33B_s32 1600 change:$sv:3300000113:0 change:$sv:3300000127:0
set_slice 64
bash docs/chip_calls/pr31/call.sh pr33B_s64b 1600 change:$sv:3300000127:0
bash docs/chip_calls/pr31/call.sh pr33B_parent 1600 parent:$sv:3300000127:0 parent:$sv:3300000113:0
