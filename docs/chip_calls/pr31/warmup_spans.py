"""PR 31, diagnostic: one run of a cell through benchmark/run.py in this
process, then the program's own record of where set-up went: every
first call of a cached program (`jit.compile_event`: trace + cache load or
compile + first execution, ms) and the per-name span sums.

    python docs/chip_calls/pr31/warmup_spans.py --workload <cell> --seed n --seconds 3 --trace 0
"""
import atexit
import os
import runpy
import sys

sys.path.insert(0, os.getcwd())


def report():
    from alink_tpu.common.metrics import metrics

    events = metrics.series("jit.compile_event")
    print("first calls:", [(e.get("kernel"), e.get("ms")) for e in events],
          file=sys.stderr)
    for name in sorted(metrics.histogram_names()):
        if name.startswith("span."):
            h = metrics.histogram(name)
            print(f"  {name}: count {h.get('count')} sum {h.get('sum'):.3f}",
                  file=sys.stderr)
    for name in ("jitcache.compile_s", "jitcache.dl.apply_logits.compile_s",
                 "jitcache.dl.train_step.compile_s"):
        print("  timer", name, metrics.timer_stats(name), file=sys.stderr)


TIMED = []        # (function, thread, wall s, this thread's CPU s)


def _timed(owner, name):
    import threading
    import time

    fn = getattr(owner, name)

    def wrapper(*a, **k):
        t0, c0 = time.perf_counter(), time.thread_time()
        try:
            return fn(*a, **k)
        finally:
            TIMED.append((name, threading.current_thread().name,
                          round(time.perf_counter() - t0, 3),
                          round(time.thread_time() - c0, 3)))

    setattr(owner, name, wrapper)


def instrument():
    """Wall and CPU seconds of the stages of a jit cache miss, whichever
    thread runs it: trace, lower, the cache's read, the executable's load."""
    from jax._src import compilation_cache, compiler, pjit
    from jax._src.interpreters import pxla

    _timed(pjit, "_trace_for_jit")
    _timed(pjit, "_resolve_and_lower")
    _timed(compiler, "compile_or_get_cached")
    _timed(compilation_cache, "get_executable_and_time")
    _timed(pxla.UnloadedMeshExecutable, "from_hlo")
    _timed(pxla.UnloadedMeshExecutable, "load")


def report_timed():
    big = [t for t in TIMED if t[2] >= 0.05]
    print("stages over 50 ms (function, thread, wall, cpu):", file=sys.stderr)
    for t in big:
        print("   ", t, file=sys.stderr)


def profile_nested():
    """cProfile of the outermost jit traces that run on the executor's
    threads; printed for the second and third that take over a second (a
    served rung's forward program)."""
    import cProfile
    import pstats
    import threading
    import time

    from jax._src import pjit

    fn = pjit._trace_for_jit
    local = threading.local()
    seen = [0]

    def wrapper(*a, **k):
        depth = getattr(local, "depth", 0)
        local.depth = depth + 1
        try:
            if depth != 0 or not threading.current_thread().name.startswith(
                    os.environ.get("PR31_PROFILE_THREADS", "alink-dag")):
                return fn(*a, **k)
            prof = cProfile.Profile()
            t0 = time.perf_counter()
            out = prof.runcall(fn, *a, **k)
            took = time.perf_counter() - t0
            if took >= 1.0:
                seen[0] += 1
                if seen[0] in (2, 3):
                    print(f"outer trace {seen[0]} on "
                          f"{threading.current_thread().name}: {took:.3f} s",
                          file=sys.stderr)
                    pstats.Stats(prof, stream=sys.stderr).sort_stats(
                        "cumulative").print_stats(70)
            return out
        finally:
            local.depth = depth

    pjit._trace_for_jit = wrapper


def unshared():
    """The fused core as it was first written: the custom_vjp called bare,
    so every layer traces and lowers its own copy of the kernels."""
    import jax.numpy as jnp

    from alink_tpu.dl import attention, attn_pallas

    def fused_attention(qkv, mask=None, *, num_heads, interpret=False):
        b, s_len = qkv.shape[:2]
        mask = jnp.ones((b, s_len), jnp.int32) if mask is None else mask
        return attn_pallas._build_fused().__wrapped__(
            qkv.transpose(0, 2, 1, 3), mask.astype(jnp.int32)[:, None, :],
            int(num_heads), bool(interpret))

    attention.fused_attention = fused_attention


atexit.register(report)
if os.environ.get("PR31_UNSHARED"):
    unshared()
if os.environ.get("PR31_PROFILE_NESTED"):
    profile_nested()
if os.environ.get("PR31_STAGES"):
    instrument()
    atexit.register(report_timed)
sys.argv = ["benchmark/run.py"] + sys.argv[1:]
runpy.run_path("benchmark/run.py", run_name="__main__")
