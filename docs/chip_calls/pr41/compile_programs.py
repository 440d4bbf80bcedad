"""Without the chip: a grouped-query generator cell's prefill-chunk and
decode-step programs compiled for a described v5e at the cell's size, from
one checkout, with the Pallas kernels on: whether XLA and Mosaic take them,
the compiler's memory account, the kernels in the compiled text, and digests
of the lowered and the compiled text (metadata left out: it carries source
lines). Run it at the parent and at the change, each unpacked at one and
the same path (a kernel's body names its source files): equal digests are
the same programs.

    JAX_PLATFORMS=cpu python docs/chip_calls/pr41/compile_programs.py \
        <checkout> mimo|trinity|ling
"""

import hashlib
import json
import os
import re
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ["ALINK_ATTN_PALLAS"] = "1"
# no call stacks in the programs' locations: a kernel's serialized body
# would otherwise carry its callers' source lines
os.environ["JAX_INCLUDE_FULL_TRACEBACKS_IN_LOCATIONS"] = "0"
os.environ["JAX_TRACEBACK_IN_LOCATIONS_LIMIT"] = "0"
ROOT, WHICH = sys.argv[1], sys.argv[2]
sys.path.insert(0, ROOT)

import jax                                    # noqa: E402
import jax.numpy as jnp                       # noqa: E402
from jax.experimental import topologies       # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from alink_tpu.dl import lm as L              # noqa: E402

# (configuration file, key of the router's outputs, rows, positions a slot)
CELLS = {"mimo": ("mimo_v2_flash_gen", "n_routed_experts", 32, 16512),
         "trinity": ("trinity_large_gen", "num_experts", 16, 16512),
         "ling": ("ling3_flash_gen", "num_experts", 128, 2176)}


def digest(text: str) -> str:
    """Of the text without what names source files and lines: each
    operation's metadata and the compiled module's tables of them (from
    ``FileNames`` to the first computation). A kernel's serialized body keeps
    its own source locations, so run both checkouts from one path."""
    kept, skip = [], False
    for line in text.splitlines():
        if line == "FileNames":
            skip = True
        elif skip and line[:1] in ("%", "E"):
            skip = False
        if not skip:
            kept.append(re.sub(r",? metadata=\{[^}]*\}", "", line))
    return hashlib.sha256("\n".join(kept).encode()).hexdigest()[:16]


def main():
    name, key, rows, positions = CELLS[WHICH]
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    jax.default_backend = lambda: "tpu"       # einsum_f32's branch
    with open(f"{ROOT}/benchmark/configs/{name}.json") as f:
        hf = json.load(f)
    hf.update({key: hf["published"][key],
               "experts_held": hf["deployment"]["experts_held"]})
    cfg = L.CausalLMConfig.from_hf(hf)
    shape = lambda s, d: jax.ShapeDtypeStruct(tuple(s), jnp.dtype(d), sharding=one)
    E = cfg.experts_held[1] - cfg.experts_held[0]
    H, F = cfg.hidden_size, cfg.moe_intermediate_size
    tree = {"layers": [dict() for _ in range(cfg.num_hidden_layers)]}
    for tensor, s in L.tensor_shapes(cfg).items():
        path = L.tree_path(tensor)
        if len(path) == 5:                    # one expert's matrix: stacked below
            continue
        node = tree
        for k in path[:-1]:
            node = node[k] if isinstance(node, list) else node.setdefault(k, {})
        node[path[-1]] = shape(s, "bfloat16")
    for i, layer in enumerate(tree["layers"]):
        if cfg.ffn_type(i) == "experts":
            layer["experts_gate_up"] = shape((E, H, 2 * F), "bfloat16")
            layer["experts_down"] = shape((E, F, H), "bfloat16")
    state = [tuple(shape(s, d) for s, d in cfg.layer_state(i, rows, positions))
             for i in range(cfg.num_hidden_layers)]
    through = (shape((2,), "int32"),)
    T = cfg.prefill_chunk
    out = {"checkout": ROOT, "cell": name, "chunk": T}
    for prog, build, args in (
            ("prefill", L._build_prefill_chunk,
             (tree, state, shape((rows, T), "int32"), shape((rows, T), "int32"),
              shape((), "bool"), shape((rows,), "int32"),
              shape((rows, H), "float32")) + through),
            ("decode", L._build_decode_step,
             (tree, state, shape((rows,), "int32"), shape((rows,), "int32"))
             + through)):
        t0 = time.time()
        lowered = build(cfg, rows).lower(*args)
        compiled = lowered.compile()
        m = compiled.memory_analysis()
        text = compiled.as_text()
        out[prog] = {"compile_s": round(time.time() - t0, 1),
                     "argument_bytes": m.argument_size_in_bytes,
                     "temp_bytes": m.temp_size_in_bytes,
                     "alias_bytes": m.alias_size_in_bytes,
                     "kernels": sorted(set(re.findall(
                         r'op_name="[^"]*/([a-z_]+)/pallas_call"', text))),
                     "custom_calls": text.count("custom_call_target=\"tpu_custom_call\""),
                     "lowered": digest(lowered.as_text()),
                     "compiled": digest(text)}
    print(json.dumps(out))


if __name__ == "__main__":
    main()
