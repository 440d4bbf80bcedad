"""benchmark/run.py of the checkout this is started from, with every duration
jax's monitoring reports for the compile cache and the compiler printed as it
falls (standard error, "jaxtime" lines: seconds since start, event, seconds;
the compile cache's events all, the compiler's from half a second). jax is
imported before run.py starts its clock, so `setup_s` reads some 3 s less
than without the wrapper, on both sides alike.

For REVIEW 36 (2): epoch 0 of the fine-tune cell takes 20 s or 38 s, and the
step's cache retrievals are the guess. This shows, on either side and with no
edit to it, how long each retrieval took and when. Usage, from a checkout:

    python3 /root/repo/docs/chip_calls/pr36/retrievals.py --workload ... (run.py's arguments)
"""
import os
import runpy
import sys
import time

from jax._src import monitoring

T0 = time.perf_counter()


def _said(event, duration, **kw):
    if "compilation_cache" in event or ("compil" in event and duration >= 0.5):
        print(f"jaxtime {time.perf_counter() - T0:8.2f} {event} {duration:.3f}",
              file=sys.stderr, flush=True)


monitoring.register_event_duration_secs_listener(_said)
print(f"jaxtime load {open('/proc/loadavg').read().strip()} cores "
      f"{len(os.sched_getaffinity(0))}", file=sys.stderr, flush=True)
sys.argv = ["benchmark/run.py"] + sys.argv[1:]
sys.path.insert(0, os.getcwd())
try:
    runpy.run_path("benchmark/run.py", run_name="__main__")
finally:
    print(f"jaxtime load {open('/proc/loadavg').read().strip()} at "
          f"{time.perf_counter() - T0:.2f}", file=sys.stderr, flush=True)
