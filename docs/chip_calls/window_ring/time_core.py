"""On the chip: one window layer's core (``dl/gqa.window_attend``, the kernel
and what it does to the ring) timed alone at a generator cell's size, for a
prompt chunk and a decode step, the ring donated and taken back as the
serving programs do. Prints one JSON line of milliseconds a call (median of
``REPS`` timed calls after a warm one). Run it from a checkout, or give the
checkout to time as the first argument; with ``TRACE=1`` also the device
operations of ten calls under the profiler, the largest first (microseconds a
call):

    python docs/chip_calls/window_ring/time_core.py [<checkout>] [trinity|mimo]
"""

import json
import os
import sys
import time

ROOT = sys.argv[1] if len(sys.argv) > 1 else os.getcwd()
WHICH = sys.argv[2] if len(sys.argv) > 2 else "trinity"
sys.path.insert(0, ROOT)

import jax                         # noqa: E402
import jax.numpy as jnp            # noqa: E402
import numpy as np                 # noqa: E402

from alink_tpu.dl import gqa as Q  # noqa: E402

# rows, query heads, key/value heads, q/k width, v width, window, chunk
CELLS = {"trinity": (16, 48, 8, 128, 128, 4096, 64),
         "mimo": (32, 64, 8, 192, 128, 128, 64)}
REPS = int(os.environ.get("REPS", 50))


def device_ops(step, calls: int = 10):
    """The device operations of ``calls`` calls of ``step`` (which takes and
    gives back the donated ring), summed by the trace reader's label."""
    import tempfile
    from collections import Counter

    from benchmark.reducers.trace import op_label, read_events

    with tempfile.TemporaryDirectory() as d:
        with jax.profiler.trace(d):
            for _ in range(calls):
                jax.block_until_ready(step())
        paths = [os.path.join(r, f) for r, _, fs in os.walk(d) for f in fs
                 if f.endswith(".xplane.pb")]
        events = read_events(paths[0])
    by = Counter()
    for plane, _, name, _, dur, detail in events:
        if plane.startswith("/device:"):
            by[op_label(name, detail)] += dur / 1e3 / calls
    return [(k, round(v, 1)) for k, v in by.most_common(14)] + [
        ("all", round(sum(by.values()), 1))]


def main():
    B, Hq, hkv, D, Dv, W, chunk = CELLS[WHICH]
    out = {"checkout": ROOT, "cell": WHICH, "device": jax.devices()[0].device_kind}
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    bf = jnp.bfloat16
    sink = jnp.linspace(-1.0, 2.0, Hq)
    for name, T, start in (("prompt_chunk", chunk, 8192), ("decode_step", 1, 12345)):
        q = jax.random.normal(ks[0], (B, T, Hq, D), bf)
        k = jax.random.normal(ks[1], (B, T, hkv, D), bf)
        v = jax.random.normal(ks[2], (B, T, hkv, Dv), bf)
        ring_k = jax.random.normal(ks[3], (B, hkv, D, W), bf)
        ring_v = jax.random.normal(ks[4], (B, hkv, W, Dv), bf)
        length = jnp.full((B,), start, jnp.int32)
        valid = jnp.ones((B, T), bool)

        def call(q, k, v, ring_k, ring_v, length, valid):
            return Q.window_attend(q, k, v, ring_k, ring_v, length, valid, sink,
                                   window=W, scale=D ** -0.5, dtype=bf)

        f = jax.jit(call, donate_argnums=(3, 4))
        o, ring_k, ring_v, length = f(q, k, v, ring_k, ring_v, length, valid)
        jax.block_until_ready(ring_v)
        took = []
        for _ in range(REPS):
            t0 = time.perf_counter()
            o, ring_k, ring_v, length = f(q, k, v, ring_k, ring_v, length, valid)
            jax.block_until_ready((o, ring_k, ring_v))
            took.append(time.perf_counter() - t0)
        out[name + "_ms"] = float(np.median(took) * 1e3)
        if os.environ.get("TRACE") == "1":
            held = [ring_k, ring_v, length]

            def step():
                o, *held[:] = f(q, k, v, *held, valid)
                return o, held
            out[name + "_ops_us"] = device_ops(step)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
