"""What an ``afmoe`` generator cell makes from ``--seed``: the prompts
(``gen_lm.make_prompts``) and the checkpoint in the HF layout, as
``gen_mimo`` does for its family. It imports nothing of the program (the
layer pattern is the reference's ``layer_kinds``).

The checkpoint is one chip's share of the model (the configuration's
``deployment``): ``config.json`` carries the published router width under
``num_experts`` and this repo's key ``experts_held`` (``[lo, hi)``), and the
shards hold those experts alone, each under its published name
(``mlp.experts.<e>.gate_proj.weight``).

Weights, all bfloat16: matrices, embedding and head N(0, ``weight_std``);
the attention's output gate N(0, ``gate_std``); every norm's scale 1 + N(0,
0.02); the router N(0, ``router_std``) and its bias N(0,
``router_bias_std``). A layer is drawn on the device in one jitted call,
brought to the host and written as one shard.
"""

from __future__ import annotations

import json
import os
import time
from typing import Dict, List, Sequence, Tuple

import numpy as np

from benchmark import gen
from benchmark.gen_lm import _write_shard, make_prompts
from benchmark.reference.trinity import layer_kinds

OWN_KEYS = ("source", "reduced", "published", "deployment", "assumed", "fit")


def layer_specs(cfg: Dict, mixer: str, ffn: str
                ) -> List[Tuple[str, Tuple[int, ...], str]]:
    """(tensor name inside a layer, shape, kind) of one block's tensors; a
    linear's weight is ``(out, in)``, and ``mlp.experts`` stands for every
    held expert's matrix of that name, stacked. ``kind``: w a matrix, a the
    output gate, g a norm's scale, r the router, rb its bias. Both mixers
    have the same tensors."""
    h, hq, hkv = (cfg["hidden_size"], cfg["num_attention_heads"],
                  cfg["num_key_value_heads"])
    d = cfg["head_dim"]
    out = [(f"{norm}.weight", (h,), "g") for norm in (
        "input_layernorm", "post_attention_layernorm", "pre_mlp_layernorm",
        "post_mlp_layernorm")]
    out += [("self_attn.q_proj.weight", (hq * d, h), "w"),
            ("self_attn.k_proj.weight", (hkv * d, h), "w"),
            ("self_attn.v_proj.weight", (hkv * d, h), "w"),
            ("self_attn.q_norm.weight", (d,), "g"),
            ("self_attn.k_norm.weight", (d,), "g"),
            ("self_attn.g_proj.weight", (hq * d, h), "a"),
            ("self_attn.o_proj.weight", (h, hq * d), "w")]
    swiglu = lambda p, f, *lead: [
        (f"mlp.{p}gate_proj.weight", lead + (f, h), "w"),
        (f"mlp.{p}up_proj.weight", lead + (f, h), "w"),
        (f"mlp.{p}down_proj.weight", lead + (h, f), "w")]
    if ffn == "dense":
        return out + swiglu("", cfg["intermediate_size"])
    f, routed = cfg["moe_intermediate_size"], cfg["published"]["num_experts"]
    return (out + [("mlp.gate.weight", (routed, h), "r"),
                   ("mlp.gate.expert_bias", (routed,), "rb")]
            + swiglu("experts.", f, cfg["num_experts"])
            + swiglu("shared_experts.", f * cfg["num_shared_experts"]))


def parameters(cfg: Dict) -> int:
    return (sum(int(np.prod(s)) for kinds in layer_kinds(cfg)
                for _, s, _ in layer_specs(cfg, *kinds))
            + 2 * cfg["vocab_size"] * cfg["hidden_size"] + cfg["hidden_size"])


def hf_config(cfg: Dict) -> Dict:
    """The checkpoint's ``config.json``: the configuration without this
    benchmark's own keys, the router's published width, the experts held."""
    hf = {k: v for k, v in cfg.items() if k not in OWN_KEYS}
    hf.update(num_experts=cfg["published"]["num_experts"],
              experts_held=list(cfg["deployment"]["experts_held"]))
    return hf


def write_checkpoint(path: str, cfg: Dict, seed: int, draw_with: Dict,
                     vocab: Sequence[str]) -> int:
    """The whole checkpoint directory from the seed; ``draw_with`` is the
    traffic file's ``weights``. Returns the bytes of the shards."""
    import jax
    import jax.numpy as jnp

    os.makedirs(path, exist_ok=True)
    lo, hi = cfg["deployment"]["experts_held"]
    with open(os.path.join(path, "config.json"), "w") as f:
        json.dump(hf_config(cfg), f, indent=1)
    with open(os.path.join(path, "vocab.txt"), "w", encoding="utf-8") as f:
        f.write("\n".join(vocab) + "\n")
    std = {"w": draw_with["weight_std"], "a": draw_with["gate_std"], "g": 0.02,
           "r": draw_with["router_std"], "rb": draw_with["router_bias_std"]}

    def draw(key, specs):
        return [(std[kind] * jax.random.normal(k, shape, jnp.float32)
                 + (1.0 if kind == "g" else 0.0)).astype(jnp.bfloat16)
                for k, (_, shape, kind) in zip(jax.random.split(key, len(specs)),
                                               specs)]

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
    ctx.say(f"gen_trinity: checkpoint of {parameters(cfg)} parameters, {written} "
            f"bytes in {cfg['num_hidden_layers'] + 2} bfloat16 shards, drawn "
            f"and written in {time.perf_counter() - t0:.2f} s")
    ctx.mark("checkpoint_write")
    return vocab, prompts, lengths, path, written
