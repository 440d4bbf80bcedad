"""What a generator cell makes from ``--seed``: the prompts and the causal
language model's checkpoint, in the public HF layout (``config.json``, sharded
bfloat16 safetensors with their index, ``vocab.txt``). Like ``gen.py`` it
imports nothing of the program; the vocabulary is ``gen.make_vocab``'s at the
configuration's size.

Weights: every matrix and the two vocabulary tables N(0, ``std``); every norm
scale 1 + N(0, 0.02); the gate's bias, one value a key/value head, evenly
spaced over ``gate_bias`` (low, high), the same in every layer: no draw, a
spread of memory lengths as trained heads have. They are drawn on the device, a layer at a time in one
jitted call (every layer has the same shapes, so one program draws them all),
brought to the host as bfloat16 and written as one shard a layer, one for the
embedding and one for the final norm and the head: billions of parameters
never exist as float32 on the host.
"""

from __future__ import annotations

import json
import os
import struct
import time
from typing import Dict, List, Sequence, Tuple

import numpy as np

from benchmark import gen

HF_KEYS = ("model_type", "vocab_size", "hidden_size", "intermediate_size",
           "num_hidden_layers", "num_attention_heads", "num_key_value_heads",
           "head_dim", "hidden_act", "rms_norm_eps", "rope_theta", "rope_scaling",
           "max_position_embeddings", "tie_word_embeddings", "attention_bias")


def layer_specs(cfg: Dict) -> List[Tuple[str, Tuple[int, ...], str]]:
    """(tensor name inside a layer, shape, kind) of one block's tensors; a
    linear's weight is ``(out, in)``. ``kind``: w = N(0, std); g = 1 +
    N(0, 0.02); b = the gate's bias."""
    h, f, d = cfg["hidden_size"], cfg["intermediate_size"], cfg["head_dim"]
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return [("input_layernorm.weight", (h,), "g"),
            ("self_attn.q_proj.weight", (hq * d, h), "w"),
            ("self_attn.k_proj.weight", (hkv * d, h), "w"),
            ("self_attn.v_proj.weight", (hkv * d, h), "w"),
            ("self_attn.g_proj.weight", (hkv, h), "w"),
            ("self_attn.g_proj.bias", (hkv,), "b"),
            ("self_attn.q_norm.weight", (d,), "g"),
            ("self_attn.k_norm.weight", (d,), "g"),
            ("self_attn.o_proj.weight", (h, hq * d), "w"),
            ("post_attention_layernorm.weight", (h,), "g"),
            ("mlp.gate_proj.weight", (f, h), "w"),
            ("mlp.up_proj.weight", (f, h), "w"),
            ("mlp.down_proj.weight", (h, f), "w")]


def parameters(cfg: Dict) -> int:
    per_layer = sum(int(np.prod(s)) for _, s, _ in layer_specs(cfg))
    return (cfg["num_hidden_layers"] * per_layer
            + 2 * cfg["vocab_size"] * cfg["hidden_size"] + cfg["hidden_size"])


def _write_shard(path: str, tensors: Sequence[Tuple[str, np.ndarray]]) -> int:
    header, off = {}, 0
    for name, a in tensors:
        header[name] = {"dtype": "BF16", "shape": list(a.shape),
                        "data_offsets": [off, off + a.nbytes]}
        off += a.nbytes
    hb = json.dumps(header).encode()
    with open(path, "wb") as f:
        f.write(struct.pack("<Q", len(hb)))
        f.write(hb)
        for _, a in tensors:
            f.write(np.ascontiguousarray(a).view(np.uint8).reshape(-1).data)
    return 8 + len(hb) + off


def write_checkpoint(path: str, cfg: Dict, seed: int, std: float,
                     gate_bias: Sequence[float], vocab: Sequence[str]) -> int:
    """The whole checkpoint directory from the seed. Returns the bytes of
    the safetensors shards."""
    import jax
    import jax.numpy as jnp

    os.makedirs(path, exist_ok=True)
    with open(os.path.join(path, "config.json"), "w") as f:
        json.dump({k: cfg[k] for k in HF_KEYS if k in cfg}, f, indent=1)
    with open(os.path.join(path, "vocab.txt"), "w", encoding="utf-8") as f:
        f.write("\n".join(vocab) + "\n")

    def draw(key, specs):
        outs = []
        for k, (_, shape, kind) in zip(jax.random.split(key, len(specs)), specs):
            if kind == "b":
                x = jnp.linspace(*gate_bias, shape[0], dtype=jnp.float32)
            else:
                x = (std if kind == "w" else 0.02) * jax.random.normal(
                    k, shape, jnp.float32) + (1.0 if kind == "g" else 0.0)
            outs.append(x.astype(jnp.bfloat16))
        return outs

    specs = layer_specs(cfg)
    table = [("table", (cfg["vocab_size"], cfg["hidden_size"]), "w"),
             ("norm", (cfg["hidden_size"],), "g")]
    draw_layer = jax.jit(lambda key: draw(key, specs))
    draw_table = jax.jit(lambda key: draw(key, table))
    n = cfg["num_hidden_layers"]
    keys = jax.random.split(jax.random.PRNGKey(int(seed) % (2 ** 31 - 1)), n + 2)
    shards = n + 2
    weight_map, total = {}, 0

    def shard(i: int, tensors) -> None:
        nonlocal total
        fname = f"model-{i + 1:05d}-of-{shards:05d}.safetensors"
        total += _write_shard(os.path.join(path, fname), tensors)
        weight_map.update({name: fname for name, _ in tensors})

    embed, _ = jax.device_get(draw_table(keys[0]))
    shard(0, [("model.embed_tokens.weight", embed)])
    del embed
    for i in range(n):
        arrays = jax.device_get(draw_layer(keys[1 + i]))
        shard(1 + i, [(f"model.layers.{i}.{name}", a)
                      for (name, _, _), a in zip(specs, arrays)])
        del arrays
    head, norm = jax.device_get(draw_table(keys[n + 1]))
    shard(n + 1, [("model.norm.weight", norm), ("lm_head.weight", head)])
    with open(os.path.join(path, "model.safetensors.index.json"), "w") as f:
        json.dump({"metadata": {"total_size": total}, "weight_map": weight_map}, f)
    return total


def make_prompts(seed: int, n: int, lengths: Sequence[int],
                 weights: Sequence[float], vocab: Sequence[str]
                 ) -> Tuple[List[str], np.ndarray]:
    """``n`` distinct prompts of whole vocabulary words, each of a length (in
    tokens: a whole word is one piece) drawn from ``lengths`` with
    ``weights``, and those lengths."""
    rng = np.random.default_rng([int(seed), 13])
    words = [t for t in vocab if len(t) == 6 and t.isalpha()]
    drawn = rng.choice(np.asarray(lengths), size=n, p=np.asarray(weights))
    prompts = []
    for i, length in enumerate(drawn):
        idx = rng.integers(0, len(words), size=int(length))
        idx[0] = i % len(words)
        prompts.append(" ".join(words[j] for j in idx))
    return prompts, drawn.astype(np.int64)


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
    written = write_checkpoint(path, cfg, ctx.seed, traffic["weight_std"],
                               traffic["gate_bias"], vocab)
    ctx.say(f"gen_lm: checkpoint of {parameters(cfg)} parameters, {written} "
            f"bytes in {cfg['num_hidden_layers'] + 2} bfloat16 shards, drawn "
            f"and written in {time.perf_counter() - t0:.2f} s")
    ctx.mark("checkpoint_write")
    return vocab, prompts, lengths, path, written
