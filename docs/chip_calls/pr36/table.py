"""Parent beside change from a call's result lines: for each cell and side
the untraced runs' end-to-end metrics, their median and spread (distance of
the first and third quartile, statistics.quantiles(n=4), over the median).

    python docs/chip_calls/pr36/table.py chiprun_out/pr36A [chiprun_out/pr36B ...]
"""
import glob
import json
import os
import statistics
import sys

runs = {}
for d in sys.argv[1:]:
    for path in sorted(glob.glob(os.path.join(d, "*_t0.json"))):
        side, rest = os.path.basename(path).split("_", 2)[1:]
        cell = rest.rsplit("_", 2)[0]
        try:
            r = json.load(open(path))
        except ValueError:
            continue
        for name, m in r["metrics"].items():
            runs.setdefault((cell, name, side), []).append(m["value"])
for (cell, name, side), vals in sorted(runs.items()):
    med = statistics.median(vals)
    q = statistics.quantiles(vals, n=4) if len(vals) > 1 else [med, med, med]
    print(f"{cell:28s} {name:26s} {side:6s} n={len(vals)} median {med:.4f} "
          f"spread {100 * (q[2] - q[0]) / med:.2f}%  {[round(v, 4) for v in vals]}")
