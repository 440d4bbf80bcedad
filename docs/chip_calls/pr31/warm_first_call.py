"""PR 31, diagnostic: where a warm process's first call of an encoder program
spends its time with the fused attention core on and off: trace, lower,
compile (a persistent-cache retrieval when warm), first and second execution.
Run twice in one call (cold, then warm):

    python docs/chip_calls/pr31/warm_first_call.py [wrap]

``wrap`` runs the fused core under a jit of its own, so that the twelve
layers of a program share one lowering."""
import os
import sys
import time

sys.path.insert(0, os.getcwd())
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(os.getcwd(), ".scratch", "diag_cache")
import jax
import jax.numpy as jnp

jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
from alink_tpu.dl import attention
from alink_tpu.dl.modules import BertConfig, TransformerEncoder

if "wrap" in sys.argv[1:]:
    inner = attention.fused_attention
    jitted = jax.jit(inner, static_argnames=("num_heads", "interpret"))
    attention.fused_attention = jitted

cfg = BertConfig.base(dropout=0.0)
model = TransformerEncoder(cfg)
params = jax.jit(lambda k: model.init(
    k, jnp.zeros((1, 512), jnp.int32), jnp.ones((1, 512), jnp.int32))["params"])(
        jax.random.PRNGKey(0))
jax.block_until_ready(params)
for knob in ("0", "1"):
    os.environ["ALINK_ATTN_PALLAS"] = knob
    for rows in (8, 64, 256):
        ids = jnp.zeros((rows, 512), jnp.int32)
        mask = jnp.ones((rows, 512), jnp.int32)
        f = jax.jit(lambda p, i, m: model.apply({"params": p}, i, m))
        t0 = time.perf_counter()
        traced = f.trace(params, ids, mask)
        t1 = time.perf_counter()
        lowered = traced.lower()
        t2 = time.perf_counter()
        compiled = lowered.compile()
        t3 = time.perf_counter()
        jax.block_until_ready(compiled(params, ids, mask))
        t4 = time.perf_counter()
        jax.block_until_ready(compiled(params, ids, mask))
        t5 = time.perf_counter()
        print(f"knob {knob} rows {rows}: trace {t1 - t0:.2f} lower {t2 - t1:.2f} "
              f"compile-or-load {t3 - t2:.2f} first run {t4 - t3:.3f} "
              f"second run {t5 - t4:.3f}", flush=True)
