"""Where a served batch's unnamed time lies: reads a span log (the program's
ALINK_TRACE_LOG, one JSON object a finished span) and prints, over the last
batches in it, the mean milliseconds a batch between each two neighbouring
leaf spans, by the pair's names. Usage: gaps.py <spans.jsonl> [batches]"""
import collections
import json
import sys

LEAVES = ("serving.build_table", "mapper.load_model", "bert.tokenize",
          "dl.predict.place_params", "dl.predict.apply", "bert.postprocess")
spans = [json.loads(line) for line in open(sys.argv[1])]
keep = int(sys.argv[2]) if len(sys.argv) > 2 else 100
batches = [s for s in spans if s["name"] == "serving.batch"][-keep:]
leaves = sorted((s for s in spans if s["name"] in LEAVES),
                key=lambda s: s["t_start"])
gaps, walls = collections.Counter(), collections.Counter()
for b in batches:
    lo, hi = b["t_start"], b["t_start"] + b["wall_s"]
    inside = [s for s in leaves if lo <= s["t_start"] < hi]
    at, prev = lo, "batch.start"
    for s in inside:
        gaps[(prev, s["name"])] += s["t_start"] - at
        walls[s["name"]] += s["wall_s"]
        at, prev = s["t_start"] + s["wall_s"], s["name"]
    gaps[(prev, "batch.end")] += hi - at
n = len(batches)
print(f"{n} batches, mean wall {1e3 * sum(b['wall_s'] for b in batches) / n:.3f} ms")
for name, w in walls.most_common():
    print(f"  leaf {name:28s} {1e3 * w / n:9.3f} ms a batch")
for pair, g in gaps.most_common():
    print(f"  gap  {pair[0]:24s} -> {pair[1]:24s} {1e3 * g / n:9.3f} ms a batch")
print(f"  gaps in all {1e3 * sum(gaps.values()) / n:.3f} ms a batch")
