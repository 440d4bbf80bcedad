"""On the chip: ``dl/mla.causal_core`` at the Moonlight cell's shapes (2 rows
of 8,192 positions, 16 heads, q/k 192 wide and v 128, bfloat16, the casts and
the transposes to heads-first and back included), down XLA's block loops and
down the kernels of ``dl/attn_pallas.causal_attention``: milliseconds a call
forward and forward + backward by the kernels' block, and the largest
differences from the loops.

    chiprun --chips 1 --timeout 1500 -- python docs/chip_calls/pr35/tune.py 1024 512

(Call B ran an earlier form, `block:piece` with the forward block's keys a
piece at a time, and handed every block's forward to one `jax.jit(core)`,
whose cache answered with the first program, the loops': its forward column
is the loops' every time. Here each reading jits a function of its own.)
"""

import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.getcwd())
from alink_tpu.dl import attn_pallas as P   # noqa: E402
from alink_tpu.dl import mla as A           # noqa: E402

B, H, T, D, DV = 2, 16, 8192, 192, 128
REPS = 10


def timed(f, *args):
    jax.block_until_ready(f(*args))
    t0 = time.perf_counter()
    for _ in range(REPS):
        out = f(*args)
    jax.block_until_ready(out)
    return 1e3 * (time.perf_counter() - t0) / REPS, out


def main():
    assert jax.default_backend() == "tpu", jax.default_backend()
    rng = np.random.default_rng(35)
    draw = lambda w: jnp.asarray(rng.normal(size=(B, T, H, w)), jnp.float32)
    q, k, v, g = draw(D), draw(D), draw(DV), draw(DV)
    core = lambda q, k, v: A.causal_core(q, k, v, scale=D ** -0.5,
                                         dtype=jnp.bfloat16)
    rows = []

    def both(name):
        fwd = jax.jit(lambda q, k, v: core(q, k, v))
        grad = jax.jit(jax.grad(lambda q, k, v, g: (core(q, k, v) * g).sum(),
                                argnums=(0, 1, 2)))
        t_f, o = timed(fwd, q, k, v)
        t_b, gr = timed(grad, q, k, v, g)
        rows.append({"name": name, "fwd_ms": t_f, "fwd_bwd_ms": t_b})
        print(json.dumps(rows[-1]), flush=True)
        return [np.asarray(x) for x in (o, *gr)]

    rule, A.use_causal_attention = A.use_causal_attention, lambda *a: False
    ref = both("xla_loops_1024")
    A.use_causal_attention = rule
    for blk in [int(x) for x in sys.argv[1:]] or [1024]:
        P._CAUSAL_BLOCK = blk
        P._build_causal.cache_clear()
        got = both(f"kernels_{blk}")
        rows[-1]["max_abs_diff_vs_loops"] = {
            n: float(np.abs(a - b).max())
            for n, a, b in zip(("o", "dq", "dk", "dv"), got, ref)}
        print(json.dumps(rows[-1]["max_abs_diff_vs_loops"]), flush=True)
    os.makedirs("chiprun_out/pr35", exist_ok=True)
    with open("chiprun_out/pr35/tune.json", "w") as f:
        json.dump(rows, f, indent=1)


if __name__ == "__main__":
    main()
