"""Without the chip: the generator's prefill-chunk program at the Brumby
cell's size (16 rows, a chunk of 256, 8 layers, the whole vocabulary)
compiled for a described v5e, with the chunk kernel and with the XLA form
(``ALINK_RETENTION_PALLAS=0``): whether Mosaic and XLA take it, and the
compiler's memory account.

    JAX_PLATFORMS=cpu python docs/chip_calls/pr37/compile_prefill.py [0|1]
"""

import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.getcwd())

import jax                                    # noqa: E402
import jax.numpy as jnp                       # noqa: E402
from jax.experimental import topologies       # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from alink_tpu.dl import lm as L              # noqa: E402

ROWS = 16


def main():
    os.environ["ALINK_RETENTION_PALLAS"] = sys.argv[1] if len(sys.argv) > 1 else "1"
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    jax.default_backend = lambda: "tpu"       # einsum_f32's branch
    with open("benchmark/configs/brumby_14b_gen.json") as f:
        cfg = L.CausalLMConfig.from_hf(json.load(f), dtype="bfloat16")
    shape = lambda s, d: jax.ShapeDtypeStruct(tuple(s), jnp.dtype(d), sharding=one)
    params = L.params_from_tensors(cfg, [
        (n, shape(s, "bfloat16")) for n, s in L.tensor_shapes(cfg).items()])
    state = [tuple(shape(s, d) for s, d in cfg.layer_state(i, ROWS, 0))
             for i in range(cfg.num_hidden_layers)]
    T = cfg.prefill_chunk
    args = (params, state, shape((ROWS, T), "int32"), shape((ROWS, T), "int32"),
            shape((), "bool"), shape((ROWS,), "int32"),
            shape((ROWS, cfg.hidden_size), "float32"))
    t0 = time.time()
    compiled = L._build_prefill_chunk(cfg, ROWS).lower(*args).compile()
    m = compiled.memory_analysis()
    text = compiled.as_text()
    print(json.dumps({
        "knob": os.environ["ALINK_RETENTION_PALLAS"],
        "compile_s": round(time.time() - t0, 1),
        "argument_bytes": m.argument_size_in_bytes,
        "temp_bytes": m.temp_size_in_bytes,
        "alias_bytes": m.alias_size_in_bytes,
        "kernels": text.count("retention_chunk_state"),
        "phi_shaped": sum(text.count(s) for s in (
            "f32[16,256,5,8385]", "f32[16,256,5,8256]", "f32[16,256,5,65,128]",
            "f32[16,5,256,8256]", "f32[16,5,256,65,128]")),
        "state_copies": text.count("f32[16,8,8256,128]{") }))
    os.makedirs("chiprun_out/pr37", exist_ok=True)
    with open(f"chiprun_out/pr37/prefill_{os.environ['ALINK_RETENTION_PALLAS']}.hlo.txt", "w") as f:
        f.write(text)


if __name__ == "__main__":
    main()
