"""On the chip: ``dl/retention.retention_chunk`` at the Brumby cell's shapes
(16 rows, a chunk of 256 positions, 40 + 8 heads of 128, bfloat16 products,
the state 16 x 8 x 8,256 x 128 float32 donated), down the XLA form (the loop
over key/value heads, phi from ``power_embed``) and down the kernel of
``dl/retention_pallas.chunk_through_state``: milliseconds a layer and chunk,
the kernel alone, and the largest differences from the XLA form.

    chiprun --chips 1 --timeout 900 -- python docs/chip_calls/pr37/tune.py

What a layer and chunk needs (ISSUE 37): 0.42 TFLOP, 2.1 ms at the peak, and
the state read and written once, 2 x 541 MB, 1.3 ms at 819 GB/s.
"""

import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.getcwd())
from alink_tpu.dl import retention as R          # noqa: E402
from alink_tpu.dl import retention_pallas as RP  # noqa: E402

B, T, HQ, HKV, D = 16, 256, 40, 8, 128
P = R.phi_dim(D)
REPS = 10
DTYPE = jnp.bfloat16


def timed(f, small, S, z):
    """``f(*small, S, z) -> (..., S, z)`` with the state donated, as the
    generator's prefill program holds it."""
    out = f(*small, S, z)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(REPS):
        out = f(*small, *out[-2:])
    jax.block_until_ready(out)
    return 1e3 * (time.perf_counter() - t0) / REPS


def main():
    assert jax.default_backend() == "tpu", jax.default_backend()
    ks = jax.random.split(jax.random.PRNGKey(37), 6)
    q = jax.random.normal(ks[0], (B, T, HQ, D))
    k = jax.random.normal(ks[1], (B, T, HKV, D))
    v = jax.random.normal(ks[2], (B, T, HKV, D))
    # gates near 1, as the cell's seeded bias puts them
    lg = jax.nn.log_sigmoid(jax.random.normal(ks[3], (B, T, HKV)) + 7.0)
    valid = jnp.arange(T)[None, :] < jnp.where(jnp.arange(B) % 4 == 3, T // 2,
                                               T)[:, None]
    chunk = lambda: (lambda q, k, v, lg, valid, S, z: R.retention_chunk(
        q, k, v, lg, valid, S, z, eps=1e-6, dtype=DTYPE))
    # the state an earlier chunk leaves, so that the normaliser is a sum of
    # squares as a prompt's is
    os.environ["ALINK_RETENTION_PALLAS"] = "0"
    earlier = jax.jit(chunk())(
        jax.random.normal(ks[4], q.shape), jax.random.normal(ks[5], k.shape),
        jax.random.normal(ks[4], v.shape), lg, None,
        jnp.zeros((B, HKV, P, D)), jnp.zeros((B, HKV, P)))[1:]
    fresh = lambda: tuple(x + 0.0 for x in earlier)
    small = (q, k, v, lg, valid)
    rows, outs = [], {}
    for name, knob in (("xla_form", "0"), ("kernel", "1")):
        os.environ["ALINK_RETENTION_PALLAS"] = knob
        # a function of its own a reading: the knob is read while tracing,
        # and one function's second jit would answer from the first's cache
        f = jax.jit(chunk(), donate_argnums=(5, 6))
        outs[name] = [np.asarray(x) for x in f(*small, *fresh())]
        rows.append({"name": name, "layer_chunk_ms": timed(f, small, *fresh())})
        print(json.dumps(rows[-1]), flush=True)
    k0, lg0 = R._mask_padding(k, lg, valid)
    heads = lambda x: x.transpose(0, 2, 1, 3)
    alone = jax.jit(lambda q, k, v, cum, S, z: RP.chunk_through_state(
        q, k, v, cum, S, z, dtype=DTYPE), donate_argnums=(4, 5))
    rows.append({"name": "kernel_alone", "layer_chunk_ms": timed(alone, (
        q.reshape(B, T, HKV, HQ // HKV, D).transpose(0, 2, 3, 1, 4),
        heads(k0), heads(v), jnp.cumsum(lg0, axis=1).transpose(0, 2, 1)),
        *fresh())})
    print(json.dumps(rows[-1]), flush=True)
    diffs = {n: {"max_abs_diff": float(np.abs(a - b).max()),
                 "max_abs": float(np.abs(b).max())}
             for n, a, b in zip(("o", "S", "z"), outs["kernel"],
                                outs["xla_form"])}
    print(json.dumps(diffs), flush=True)
    os.makedirs("chiprun_out/pr37", exist_ok=True)
    with open("chiprun_out/pr37/tune.json", "w") as f:
        json.dump({"rows": rows, "diffs": diffs}, f, indent=1)


if __name__ == "__main__":
    main()
