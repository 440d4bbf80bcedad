"""The benchmark's one command.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process, which holds the chip. It finds the cell in ``BENCHMARK.json`` and
everything that belongs to it by name: ``configs/<config>.json``,
``traffic/<traffic>.json``, the traffic's ``kinds/<kind>.py`` and, in a traced
run, ``metrics/<metric>.json`` with the reducer each names. Nothing in this file
holds the name of a cell, a configuration or a metric.

The last line of standard output is the result; the lines before it say what the
run did (README.md lists them); the last lines of standard error are the numbers
``correct`` was decided from, each beside its limit.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse
import gc
import importlib
import json
import os
import re
import shutil
import sys
from typing import Any, Dict, List, Optional

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.join(ROOT, "benchmark")


def _load(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


class Context:
    """What a kind gets: the cell's files, the seed, and the run's clocks."""

    def __init__(self, args, cell, config, traffic):
        self.seed, self.seconds, self.trace = args.seed, args.seconds, bool(args.trace)
        self.cell, self.config, self.traffic = cell, config, traffic
        self.stand_ins = [s for s in (args.stand_in or "").split(",") if s]
        self.trace_seconds = min(args.seconds, traffic.get("trace_seconds", 10.0))
        self.workdir = os.path.join(ROOT, ".bench_work", cell["name"])
        shutil.rmtree(self.workdir, ignore_errors=True)
        os.makedirs(self.workdir)
        self.parts: List[tuple] = [("start", T_PROCESS)]
        self.trace_reduced: Optional[Dict[str, Any]] = None
        self.host_spans: Dict[str, List[float]] = {}
        self._trace_dir: Optional[str] = None

    def mark(self, name: str) -> None:
        self.parts.append((name, time.perf_counter()))

    def say(self, msg: str) -> None:
        print(msg, flush=True)

    def snapshot(self) -> Dict[str, Any]:
        from alink_tpu.common.metrics import metrics

        return {"counters": dict(metrics.counters()),
                "hists": metrics.histogram_states(), "t": time.perf_counter()}

    @staticmethod
    def delta(a: Dict[str, Any], b: Dict[str, Any]) -> Dict[str, Any]:
        from benchmark.reducers.counters import hist_delta

        names = set(a["counters"]) | set(b["counters"])
        return {"counters": {n: b["counters"].get(n, 0) - a["counters"].get(n, 0)
                             for n in names},
                "hists": {n: hist_delta(a["hists"].get(n), s)
                          for n, s in b["hists"].items()},
                "seconds": b["t"] - a["t"]}

    def trace_start(self, mark: bool = True) -> None:
        """Start the profiler and, unless the kind marks it later with
        ``trace_mark_open``, mark the traced window's opening edge."""
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        self._trace_dir = os.path.join(self.workdir, "trace")
        jax.profiler.start_trace(self._trace_dir, profiler_options=opts)
        if mark:
            self.trace_mark_open()

    @staticmethod
    def trace_mark_open() -> None:
        import jax

        with jax.profiler.TraceAnnotation("bench.window_open"):
            pass

    def trace_stop(self) -> None:
        """Mark the closing edge and stop the profiler; ``trace_reduce``
        reads what it wrote, once the window's work is over."""
        import jax

        with jax.profiler.TraceAnnotation("bench.window_close"):
            pass
        jax.profiler.stop_trace()

    def trace_reduce(self) -> None:
        from benchmark.reducers import trace as trace_reducer

        if self._trace_dir is None:
            return
        self.trace_reduced = trace_reducer.reduce_dir(self._trace_dir)
        shutil.rmtree(self._trace_dir, ignore_errors=True)

    def annotate(self, targets: Dict[str, str]) -> List[tuple]:
        """Wrap each ``module:Class.method`` of ``targets`` so that a call
        shows as a ``TraceAnnotation`` of the given name on the profiler's
        host timeline and adds its host seconds to ``host_spans[name]``.
        Returns what ``restore`` needs."""
        import jax

        undo = []
        for name, target in targets.items():
            mod, _, attr = target.partition(":")
            owner = importlib.import_module(mod)
            *path, leaf = attr.split(".")
            for part in path:
                owner = getattr(owner, part)
            real = getattr(owner, leaf)
            spans = self.host_spans.setdefault(name, [])

            def wrapped(*a, _real=real, _name=name, _spans=spans, **kw):
                t0 = time.perf_counter()
                with jax.profiler.TraceAnnotation(_name):
                    try:
                        return _real(*a, **kw)
                    finally:
                        _spans.append(time.perf_counter() - t0)

            setattr(owner, leaf, wrapped)
            undo.append((owner, leaf, real))
        return undo

    @staticmethod
    def restore(undo: List[tuple]) -> None:
        for owner, leaf, real in undo:
            setattr(owner, leaf, real)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, default=0)
    # for benchmark/tests and the control runs of PERF.md; the driver passes none
    ap.add_argument("--benchmark-file", default=os.path.join(ROOT, "BENCHMARK.json"))
    ap.add_argument("--rehearsal", type=int, default=0,
                    help="1: run without a TPU (tests); no device metric is printed")
    ap.add_argument("--stand-in", default=None,
                    help="the reference with this precision (fp8) or fault "
                         "(half_batch, frozen, swapped) stands in for the "
                         "program's outputs in the comparison: the control; "
                         "several with commas, each compared under its name")
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(ROOT, "alink_tpu")):
        print("benchmark: the program (alink_tpu/) is not in this checkout",
              file=sys.stderr)
        return 2
    bench = _load(args.benchmark_file)
    cells = {w["name"]: w for w in bench["workloads"]}
    if args.workload not in cells:
        print(f"benchmark: no workload {args.workload!r}", file=sys.stderr)
        return 2
    cell = cells[args.workload]
    cfg_entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = _load(os.path.join(ROOT, cfg_entry["file"]))
    traffic_dir = os.path.join(os.path.dirname(os.path.dirname(
        os.path.join(ROOT, cfg_entry["file"]))), "traffic")
    traffic = _load(os.path.join(traffic_dir, cell["traffic"] + ".json"))

    if not args.rehearsal:    # XLA:CPU entries are pinned to one machine's features
        # the cache lives inside the checkout, whatever the machine exports, and
        # is not evicted: only a checkout's first run of a cell compiles
        os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
        os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, ROOT)
    import jax

    import alink_tpu  # noqa: F401  (places the compile cache)

    devs = jax.devices()
    platform, kind = devs[0].platform, devs[0].device_kind
    if not args.rehearsal and (platform != "tpu" or len(devs) < cell["chips"]):
        print(f"benchmark: needs {cell['chips']} TPU chip(s); jax found "
              f"{len(devs)} x {platform} ({kind})", file=sys.stderr)
        return 3
    if len(devs) > cell["chips"] and not args.rehearsal:
        print(f"benchmark: the cell asks for {cell['chips']} chip(s) and the "
              f"machine shows {len(devs)}", file=sys.stderr)
        return 3
    ctx = Context(args, cell, config, traffic)
    ctx.mark("jax_start")
    ctx.say(f"device: platform {platform}, kind {kind}, count {len(devs)}; jax "
            f"{jax.__version__}; compile cache "
            f"{os.environ.get('JAX_COMPILATION_CACHE_DIR')}; workload "
            f"{cell['name']} seed {args.seed} seconds {args.seconds} trace "
            f"{args.trace}")
    peaks = None
    try:
        from benchmark.reducers.model import device_peaks

        peaks = device_peaks(kind)
    except KeyError:
        if not args.rehearsal:
            raise
    try:
        kind_mod = importlib.import_module(f"benchmark.kinds.{traffic['kind']}")
        out = kind_mod.run(ctx)
        parts = ctx.parts + [(out["last_setup_part"], out["t_open"])]
        setup_s = out["t_open"] - T_PROCESS
        ctx.say("set-up parts (s): " + ", ".join(
            f"{n} {t1 - t0:.2f}" for (_, t0), (n, t1) in zip(parts, parts[1:]))
            + f"; setup_s {setup_s:.2f}")
        stats = [d.memory_stats() or {} for d in devs]
        # live buffers and the running program's scratch are counted apart on
        # the TPU ("reserved"); the peak of a chip is the two together
        peak = max((int(s.get("peak_bytes_in_use", 0))
                    + int(s.get("peak_bytes_reserved", 0)) for s in stats), default=0)
        ctx.say(f"memory: peak {peak} bytes on the fullest chip (live buffers "
                f"and program scratch); its counters {stats[0]}")
        gc.collect()
        t_check = time.perf_counter()
        ctx.trace_reduce()
        compared = out["check"]()
        ctx.say(f"reference and comparison took {time.perf_counter() - t_check:.2f} s")
    finally:
        shutil.rmtree(ctx.workdir, ignore_errors=True)

    correct = all(v == v and v <= lim for _, v, lim in compared)
    facts = out["facts"]
    warm = facts["counters_setup"]["counters"]
    ctx.say(f"set-up: persistent compile cache hits {warm.get('jit.persist_hit', 0)}, "
            f"misses {warm.get('jit.persist_miss', 0)}; programs compiled "
            f"{warm.get('jit.compile', 0)}")
    seen = facts["counters_window"]["counters"]
    ctx.say(f"window: {out['window_s']:.3f} s; programs traced in it "
            f"{seen.get('jit.trace', 0)}, compiled in it {seen.get('jit.compile', 0)}"
            + (" -- A COMPILE INSIDE THE WINDOW" if seen.get("jit.compile") else ""))
    facts.update({"config": config, "traffic": traffic, "chips": len(devs),
                  "peaks": peaks, "window_s": out["window_s"], "setup_s": setup_s,
                  "trace": ctx.trace_reduced, "host_spans": ctx.host_spans})
    metrics_out: Dict[str, Any] = {}
    device = {"platform": platform, "kind": kind, "count": len(devs),
              "memory_peak_bytes": peak}
    result: Dict[str, Any] = {"correct": bool(correct),
                              "attempted": int(out["attempted"]),
                              "failed": int(out["failed"])}
    if args.trace:
        for m in bench["per_layer"]:
            if "workloads" in m and cell["name"] not in m["workloads"]:
                continue
            spec = _load(os.path.join(HERE, "metrics", m["name"] + ".json"))
            mod, fn = spec["reducer"].rsplit(".", 1)
            facts["metric"] = m["name"]
            value = getattr(importlib.import_module(f"benchmark.reducers.{mod}"),
                            fn)(facts, **spec.get("args", {}))
            if value is not None:
                metrics_out[m["name"]] = {"value": float(value), "unit": m["unit"]}
        tr = ctx.trace_reduced
        if tr is not None:
            plain = lambda rows: [[re.sub(r"[\s/]+", "_", n), s] for n, s in rows[:10]]
            device["busy_s"], device["window_s"] = tr["busy_s"], tr["window_s"]
            result["breakdown"] = {"device_ops": plain(tr["device_ops"]),
                                   "idle_gaps": plain(tr["idle_gaps"])}
            ctx.say(f"trace: {tr['summary']}")
            for name, ops in tr.get("matched", {}).items():
                ctx.say(f"trace: {name} matched " + "; ".join(
                    f"{n} {s:.4f} s" for n, s in ops))
    else:
        values = dict(out["end_to_end"], setup_s=setup_s)
        for m in bench["end_to_end"]:
            if m["name"] in values:
                metrics_out[m["name"]] = {"value": float(values[m["name"]]),
                                          "unit": m["unit"]}
    result["metrics"], result["device"] = metrics_out, device
    result["compared"] = {n: {"value": v if v == v else None, "limit": lim}
                          for n, v, lim in compared}
    sys.stdout.flush()
    for n, v, lim in compared:
        print(f"compared {n}: {v!r} limit {lim!r} "
              f"{'ok' if v == v and v <= lim else 'FAILS'}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
