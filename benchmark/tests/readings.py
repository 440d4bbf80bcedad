"""The readings a fine-tune cell's limits are set from, many seeds in one
process: for each seed the op's own compiled step is driven from the seed
through its checked steps at the cell's own size (its batch, its sequence
length, its table and schedule), the job is stopped there, and every number of
the comparison is printed for the program and, on the first ``--control-seeds``
seeds, for each ``--stand-in`` (the lower-precision control, the planted
faults). No window is measured and no rate is printed.

    python3 -m benchmark.tests.readings --workload <cell> --seeds 1,2,3 \\
        --stand-in fp8,half_batch --control-seeds 3 [--out chiprun_out/x.json]

The last lines give, for every number, the largest reading of the program (the
lower reading) and the smallest of each stand-in (the upper reading).
"""

from __future__ import annotations

import argparse
import gc
import importlib
import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


class _NoLimit(dict):
    """Limits under which every number is compared and none fails."""

    def __contains__(self, name) -> bool:
        return True

    def __missing__(self, name) -> float:
        return float("inf")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="whole numbers, with commas")
    ap.add_argument("--seconds", type=float, default=None,
                    help="sizes the table; default: run_seconds")
    ap.add_argument("--stand-in", default="")
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--benchmark-file", default=os.path.join(ROOT, "BENCHMARK.json"))
    ap.add_argument("--rehearsal", type=int, default=0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    sys.path.insert(0, ROOT)
    from benchmark import run as bench_run

    bench = bench_run._load(args.benchmark_file)
    cell = next(w for w in bench["workloads"] if w["name"] == args.workload)
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = bench_run._load(os.path.join(ROOT, entry["file"]))
    traffic = bench_run._load(os.path.join(
        os.path.dirname(os.path.dirname(os.path.join(ROOT, entry["file"]))),
        "traffic", cell["traffic"] + ".json"))
    if not args.rehearsal:
        os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
        os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    import alink_tpu  # noqa: F401

    dev = jax.devices()[0]
    if not args.rehearsal and dev.platform != "tpu":
        print(f"readings: needs a TPU; jax found {dev.platform}", file=sys.stderr)
        return 3
    print(f"readings: {dev.platform} {dev.device_kind}; cell {cell['name']}", flush=True)
    kind = importlib.import_module(f"benchmark.kinds.{traffic['kind']}")
    seconds = args.seconds if args.seconds is not None else bench["run_seconds"]
    table = []
    for i, seed in enumerate(int(s) for s in args.seeds.split(",") if s):
        t0 = time.perf_counter()
        run_args = argparse.Namespace(
            seed=seed, seconds=float(seconds), trace=0,
            stand_in=args.stand_in if i < args.control_seeds else "")
        ctx = bench_run.Context(run_args, cell, config,
                                dict(traffic, limits=_NoLimit()))
        ctx.readings_only = True
        try:
            out = kind.run(ctx)
            gc.collect()
            check = out.pop("check")
            del out
            row = {"seed": seed, **{n: v for n, v, _ in check()}}
        finally:
            shutil.rmtree(ctx.workdir, ignore_errors=True)
        del check, ctx
        gc.collect()
        table.append(row)
        print(f"readings: seed {seed} took {time.perf_counter() - t0:.1f} s: "
              f"{json.dumps(row)}", flush=True)
        if args.out:
            os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
            with open(args.out, "w") as f:
                json.dump(table, f, indent=1)
    names = sorted({n for row in table for n in row if n != "seed"})
    for n in names:
        vals = [row[n] for row in table if n in row]
        print(f"readings: {n}: seeds {len(vals)} min {min(vals):.6g} max "
              f"{max(vals):.6g}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
