"""What a ``bailing_hybrid`` generator cell makes from ``--seed``: the prompts
(``gen_lm.make_prompts``) and the checkpoint in the public HF layout, as
``gen_lm`` does for its family. It imports nothing of the program (the
layer pattern is the reference's ``layer_kinds``).

The checkpoint is one chip's share of the model (the configuration's
``deployment``): ``config.json`` carries the published router width under
``num_experts`` and this repo's key ``experts_held`` (``[lo, hi)``), and the
shards hold those experts alone, each under its published name
(``mlp.experts.<e>.gate_proj.weight``).

Weights, all bfloat16: matrices, embedding and head N(0, ``weight_std``); norm
scales 1 + N(0, 0.02); the router N(0, ``router_std``) and its bias N(0,
``router_bias_std``); the convolutions' taps N(0, ``conv_std``); ``A_log`` and
``dt_bias`` evenly spaced over the heads between the two ends the traffic file
gives (a head's channels share its ``dt_bias``), the same in every layer: no
draw, a spread of memory lengths as trained heads have. A layer is drawn on
the device in one jitted call, brought to the host and written as one shard.
"""

from __future__ import annotations

import json
import os
import time
from typing import Dict, List, Sequence, Tuple

import numpy as np

from benchmark import gen
from benchmark.gen_lm import _write_shard, make_prompts
from benchmark.reference.ling import layer_kinds

OWN_KEYS = ("source", "paper", "reduced", "published", "deployment", "assumed",
            "memory_reckoned", "fit", "parameters")


def layer_specs(cfg: Dict, mixer: str, ffn: str
                ) -> List[Tuple[str, Tuple[int, ...], str]]:
    """(tensor name inside a layer, shape, kind) of one block's tensors; a
    linear's weight is ``(out, in)``, and ``mlp.experts`` stands for every
    held expert's matrix of that name, stacked. ``kind``: w, g as in
    ``gen_lm``; r the router, rb its bias, c a convolution, A ``A_log``, dt
    ``dt_bias``."""
    h, d, hq = cfg["hidden_size"], cfg["head_dim"], cfg["num_attention_heads"]
    out = [("input_layernorm.weight", (h,), "g"),
           ("post_attention_layernorm.weight", (h,), "g")]
    if mixer == "kda":
        k = cfg["short_conv_kernel_size"]
        out += [(f"self_attn.{p}_proj.weight", (hq * d, h), "w") for p in "qkvfg"]
        out += [(f"self_attn.{p}_conv1d.weight", (hq * d, 1, k), "c") for p in "qkv"]
        out += [("self_attn.b_proj.weight", (hq, h), "w"),
                ("self_attn.A_log", (hq,), "A"), ("self_attn.dt_bias", (hq * d,), "dt"),
                ("self_attn.o_norm.weight", (d,), "g"),
                ("self_attn.o_proj.weight", (h, hq * d), "w")]
    else:
        r, dn, dr, dv = (cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
                         cfg["qk_rope_head_dim"], cfg["v_head_dim"])
        out += [("self_attn.q_proj.weight", (hq * (dn + dr), h), "w"),
                ("self_attn.kv_a_proj_with_mqa.weight", (r + dr, h), "w"),
                ("self_attn.kv_a_layernorm.weight", (r,), "g"),
                ("self_attn.kv_b_proj.weight", (hq * (dn + dv), r), "w"),
                ("self_attn.g_proj.weight", (hq, h), "w"),
                ("self_attn.o_proj.weight", (h, hq * dv), "w")]
    if ffn == "dense":
        f = cfg["intermediate_size"]
        return out + [("mlp.gate_proj.weight", (f, h), "w"),
                      ("mlp.up_proj.weight", (f, h), "w"),
                      ("mlp.down_proj.weight", (h, f), "w")]
    e, f = cfg["num_experts"], cfg["moe_intermediate_size"]
    fs, routed = cfg["moe_shared_expert_intermediate_size"], \
        cfg["published"]["num_experts"]
    return out + [("mlp.gate.weight", (routed, h), "r"),
                  ("mlp.gate.expert_bias", (routed,), "rb"),
                  ("mlp.experts.gate_proj.weight", (e, f, h), "w"),
                  ("mlp.experts.up_proj.weight", (e, f, h), "w"),
                  ("mlp.experts.down_proj.weight", (e, h, f), "w"),
                  ("mlp.shared_experts.gate_proj.weight", (fs, h), "w"),
                  ("mlp.shared_experts.up_proj.weight", (fs, h), "w"),
                  ("mlp.shared_experts.down_proj.weight", (h, fs), "w")]


def parameters(cfg: Dict) -> int:
    return (sum(int(np.prod(s)) for kinds in layer_kinds(cfg)
                for _, s, _ in layer_specs(cfg, *kinds))
            + 2 * cfg["vocab_size"] * cfg["hidden_size"] + cfg["hidden_size"])


def write_checkpoint(path: str, cfg: Dict, seed: int, draw_with: Dict,
                     vocab: Sequence[str]) -> int:
    """The whole checkpoint directory from the seed; ``draw_with`` is the
    traffic file's ``weights``. Returns the bytes of the shards."""
    import jax
    import jax.numpy as jnp

    os.makedirs(path, exist_ok=True)
    lo, hi = cfg["deployment"]["experts_held"]
    hf = {k: v for k, v in cfg.items() if k not in OWN_KEYS}
    hf.update(num_experts=cfg["published"]["num_experts"], experts_held=[lo, hi])
    with open(os.path.join(path, "config.json"), "w") as f:
        json.dump(hf, f, indent=1)
    with open(os.path.join(path, "vocab.txt"), "w", encoding="utf-8") as f:
        f.write("\n".join(vocab) + "\n")
    std = {"w": draw_with["weight_std"], "g": 0.02, "r": draw_with["router_std"],
           "rb": draw_with["router_bias_std"], "c": draw_with["conv_std"]}
    heads, d = cfg["num_attention_heads"], cfg["head_dim"]

    def draw(key, specs):
        outs = []
        for k, (_, shape, kind) in zip(jax.random.split(key, len(specs)), specs):
            if kind == "A":
                x = jnp.linspace(*draw_with["A_log"], heads, dtype=jnp.float32)
            elif kind == "dt":
                x = jnp.repeat(jnp.linspace(*draw_with["dt_bias"], heads,
                                            dtype=jnp.float32), d)
            else:
                x = std[kind] * jax.random.normal(k, shape, jnp.float32) \
                    + (1.0 if kind == "g" else 0.0)
            outs.append(x.astype(jnp.bfloat16))
        return outs

    kinds = layer_kinds(cfg)
    table = [("table", (cfg["vocab_size"], cfg["hidden_size"]), "w"),
             ("norm", (cfg["hidden_size"],), "g")]
    draw_table = jax.jit(lambda key: draw(key, table))
    draw_layer = {k: jax.jit(lambda key, s=layer_specs(cfg, *k): draw(key, s))
                  for k in sorted(set(kinds))}
    n = len(kinds)
    keys = jax.random.split(jax.random.PRNGKey(int(seed) % (2 ** 31 - 1)), n + 2)
    shards, weight_map, total = n + 2, {}, 0

    def shard(i: int, tensors) -> None:
        nonlocal total
        fname = f"model-{i + 1:05d}-of-{shards:05d}.safetensors"
        total += _write_shard(os.path.join(path, fname), tensors)
        weight_map.update({name: fname for name, _ in tensors})

    embed, _ = jax.device_get(draw_table(keys[0]))
    shard(0, [("model.embed_tokens.weight", embed)])
    del embed
    for i, kind in enumerate(kinds):
        arrays = jax.device_get(draw_layer[kind](keys[1 + i]))
        tensors = []
        for (name, _, _), a in zip(layer_specs(cfg, *kind), arrays):
            if name.startswith("mlp.experts."):
                leaf = name[len("mlp.experts."):]
                tensors += [(f"model.layers.{i}.mlp.experts.{lo + e}.{leaf}", a[e])
                            for e in range(hi - lo)]
            else:
                tensors.append((f"model.layers.{i}.{name}", a))
        shard(1 + i, tensors)
        del arrays, tensors
    head, norm = jax.device_get(draw_table(keys[n + 1]))
    shard(n + 1, [("model.norm.weight", norm), ("lm_head.weight", head)])
    with open(os.path.join(path, "model.safetensors.index.json"), "w") as f:
        json.dump({"metadata": {"total_size": total}, "weight_map": weight_map}, f)
    return total


def seeded_inputs(ctx, n_prompts: int):
    """Vocabulary, prompts with their lengths, and the checkpoint under the
    run's work directory, all from ``ctx.seed``; marks the set-up parts
    ``prompts`` and ``checkpoint_write``."""
    cfg, traffic = ctx.config, ctx.traffic
    vocab = gen.make_vocab(cfg["vocab_size"])
    prompts, lengths = make_prompts(ctx.seed, n_prompts, traffic["prompt_lengths"],
                                    traffic["prompt_weights"], vocab)
    ctx.mark("prompts")
    path = os.path.join(ctx.workdir, "checkpoint")
    t0 = time.perf_counter()
    written = write_checkpoint(path, cfg, ctx.seed, traffic["weights"], vocab)
    ctx.say(f"gen_ling: checkpoint of {parameters(cfg)} parameters, {written} "
            f"bytes in {cfg['num_hidden_layers'] + 2} bfloat16 shards, drawn "
            f"and written in {time.perf_counter() - t0:.2f} s")
    ctx.mark("checkpoint_write")
    return vocab, prompts, lengths, path, written
