"""Call D of PR 36: what the collector's callback costs the served BERT cell,
from two numbers read on the chip's host. (1) The price of the callback a
collection: young collections of an empty young generation timed with the
hook, without it, and with an empty callback in its place. (2) The
collections a served cycle trips: one run of the cell (`benchmark/run.py`'s
`main`, unchanged) with `Context.delta` wrapped to print the growth of the
`host.gc` counters and of `span.host.gc_s` over the window.

    cd .scratch/commit && python3 ../../docs/chip_calls/pr36/count_collections.py
"""
import gc
import os
import sys
import time

sys.path.insert(0, os.getcwd())
import alink_tpu.common.tracing as tracing  # noqa: E402


def young(n=200_000):
    t0 = time.perf_counter()
    for _ in range(n):
        gc.collect(0)
    return (time.perf_counter() - t0) / n * 1e6


def spans(n=50_000):
    t0 = time.perf_counter()
    for _ in range(n):
        with tracing.trace_span("count.probe"):
            pass
    return (time.perf_counter() - t0) / n * 1e6


def thread_time(n=500_000):
    t0 = time.perf_counter()
    for _ in range(n):
        time.thread_time()
    return (time.perf_counter() - t0) / n * 1e6


print(f"callback: a young collection {young():.3f} us with the hook", flush=True)
gc.callbacks.remove(tracing._on_gc)
print(f"callback: {young():.3f} us without it", flush=True)
noop = lambda phase, info: None
gc.callbacks.append(noop)
print(f"callback: {young():.3f} us with an empty callback", flush=True)
gc.callbacks.remove(noop)
gc.callbacks.append(tracing._on_gc)
print(f"span: {spans():.3f} us a span opened and finished; "
      f"time.thread_time() {thread_time():.3f} us", flush=True)

import benchmark.run as run  # noqa: E402

plain = run.Context.delta


def delta(a, b):
    d = plain(a, b)
    h = d["hists"].get("span.host.gc_s") or {}
    print("window-or-setup: seconds", round(d["seconds"], 3),
          {k: v for k, v in d["counters"].items() if k.startswith(("host.gc", "slow."))},
          "span.host.gc_s count", h.get("count"), "sum", h.get("sum"),
          "serving.batch count", (d["hists"].get("span.serving.batch_s") or {}).get("count"),
          "spans finished", sum(v["count"] for k, v in d["hists"].items()
                                if k.startswith("span.") and k != "span.host.gc_s"),
          flush=True)
    return d


run.Context.delta = staticmethod(delta)
sys.exit(run.main(["--workload", "bert_base_cls.serve_doc512_c256", "--seed",
                   "3600000257", "--seconds", "45", "--trace", "0"]))
