"""PR 31, diagnostic: cProfile of a warm process's first call of the served
forward program through the program's own path (dl.train.predict_model: a
mesh, placed parameters, the ProgramCache), fused core on or off. Run twice
in one call for each knob (the first fills the persistent cache):

    python docs/chip_calls/pr31/first_call_profile.py <0|1>
"""
import cProfile
import os
import pstats
import sys
import time

sys.path.insert(0, os.getcwd())
os.environ["ALINK_ATTN_PALLAS"] = sys.argv[1]
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(
    os.getcwd(), ".scratch", "diag_cache_" + sys.argv[1])
import jax
import jax.numpy as jnp
import numpy as np

import alink_tpu  # noqa: F401
from alink_tpu.dl.modules import BertConfig, TransformerEncoder
from alink_tpu.dl.train import predict_model, prepare_params

model = TransformerEncoder(BertConfig.base(dropout=0.0))
params = jax.device_get(jax.jit(lambda k: model.init(
    k, jnp.zeros((1, 512), jnp.int32), jnp.ones((1, 512), jnp.int32)))(
        jax.random.PRNGKey(0)))
prepared = prepare_params(model, params)
for rows in (8, 64):
    inputs = {"input_ids": np.zeros((rows, 512), np.int32),
              "attention_mask": np.ones((rows, 512), np.int32)}
    prof = cProfile.Profile()
    t0 = time.perf_counter()
    prof.enable()
    predict_model(model, prepared, inputs, batch_size=rows)
    prof.disable()
    print(f"knob {sys.argv[1]} rows {rows}: first call {time.perf_counter() - t0:.2f} s",
          flush=True)
    t0 = time.perf_counter()
    predict_model(model, prepared, inputs, batch_size=rows)
    print(f"   second call {time.perf_counter() - t0:.3f} s", flush=True)
    stats = pstats.Stats(prof, stream=sys.stdout)
    stats.sort_stats("cumulative").print_stats(45)
