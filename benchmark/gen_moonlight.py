"""What a ``deepseek_v3`` training cell makes from ``--seed``: the documents
and the checkpoint in the public HF layout, as ``gen_ling`` does for its
family. It imports nothing of the program.

The checkpoint is one chip's share of the model (the configuration's
``deployment``): ``config.json`` carries the published router width under
``n_routed_experts`` and this repo's key ``experts_held`` (``[lo, hi)``), and
the shards hold those experts alone, each under its published name
(``mlp.experts.<e>.gate_proj.weight``).

Weights, all bfloat16 (the program widens them to its float32 masters, the
reference to float32: both start from the same values): matrices, embedding
and head N(0, ``weight_std``); norm scales 1 + N(0, 0.02); the router N(0,
``router_std``); its bias ``e_score_correction_bias`` zero. A layer is drawn
on the device in one jitted call, brought to the host and written as one shard.

Documents: token counts log-normal (``median``, ``sigma``, cut at ``cut``),
each a run of whole vocabulary words (one piece a word), drawn until the
packed rows asked for are filled.
"""

from __future__ import annotations

import json
import os
import time
from typing import Dict, List, Sequence, Tuple

import numpy as np

from benchmark import gen
from benchmark.gen_lm import _write_shard

OWN_KEYS = ("source", "paper", "reduced", "published", "deployment", "assumed",
            "memory_reckoned", "fit", "parameters")


def layer_specs(cfg: Dict, ffn: str) -> List[Tuple[str, Tuple[int, ...], str]]:
    """(tensor name inside a layer, shape, kind) of one block's tensors; a
    linear's weight is ``(out, in)``, and ``mlp.experts`` stands for every
    held expert's matrix of that name, stacked. ``kind``: w a matrix, g a
    norm's scale, r the router, z its bias (zero)."""
    h, hq = cfg["hidden_size"], cfg["num_attention_heads"]
    r, dn, dr, dv = (cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
                     cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    out = [("input_layernorm.weight", (h,), "g"),
           ("post_attention_layernorm.weight", (h,), "g"),
           ("self_attn.q_proj.weight", (hq * (dn + dr), h), "w"),
           ("self_attn.kv_a_proj_with_mqa.weight", (r + dr, h), "w"),
           ("self_attn.kv_a_layernorm.weight", (r,), "g"),
           ("self_attn.kv_b_proj.weight", (hq * (dn + dv), r), "w"),
           ("self_attn.o_proj.weight", (h, hq * dv), "w")]
    if ffn == "dense":
        f = cfg["intermediate_size"]
        return out + [("mlp.gate_proj.weight", (f, h), "w"),
                      ("mlp.up_proj.weight", (f, h), "w"),
                      ("mlp.down_proj.weight", (h, f), "w")]
    lo, hi = cfg["deployment"]["experts_held"]
    e, f = hi - lo, cfg["moe_intermediate_size"]
    fs, routed = cfg["n_shared_experts"] * f, cfg["published"]["n_routed_experts"]
    return out + [("mlp.gate.weight", (routed, h), "r"),
                  ("mlp.gate.e_score_correction_bias", (routed,), "z"),
                  ("mlp.experts.gate_proj.weight", (e, f, h), "w"),
                  ("mlp.experts.up_proj.weight", (e, f, h), "w"),
                  ("mlp.experts.down_proj.weight", (e, h, f), "w"),
                  ("mlp.shared_experts.gate_proj.weight", (fs, h), "w"),
                  ("mlp.shared_experts.up_proj.weight", (fs, h), "w"),
                  ("mlp.shared_experts.down_proj.weight", (h, fs), "w")]


def layer_ffns(cfg: Dict) -> List[str]:
    return ["dense" if i < cfg["first_k_dense_replace"] else "experts"
            for i in range(cfg["num_hidden_layers"])]


def parameters(cfg: Dict) -> int:
    return (sum(int(np.prod(s)) for ffn in layer_ffns(cfg)
                for _, s, _ in layer_specs(cfg, ffn))
            + 2 * cfg["vocab_size"] * cfg["hidden_size"] + cfg["hidden_size"])


def hf_config(cfg: Dict) -> Dict:
    """The checkpoint's ``config.json``: the configuration's published keys,
    the router's published width, the experts this share holds, and the two
    training rates the source leaves open (``assumed``), which the program
    reads from here and the reference from the configuration."""
    hf = {k: v for k, v in cfg.items() if k not in OWN_KEYS}
    hf.update(n_routed_experts=cfg["published"]["n_routed_experts"],
              experts_held=list(cfg["deployment"]["experts_held"]),
              aux_loss_alpha=cfg["assumed"]["balance_alpha"],
              bias_update_rate=cfg["assumed"]["bias_update_rate"])
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
    std = {"w": draw_with["weight_std"], "g": 0.02, "r": draw_with["router_std"],
           "z": 0.0}

    def draw(key, specs):
        return [(std[kind] * jax.random.normal(k, shape, jnp.float32)
                 + (1.0 if kind == "g" else 0.0)).astype(jnp.bfloat16)
                for k, (_, shape, kind) in zip(
                    jax.random.split(key, len(specs)), specs)]

    ffns = layer_ffns(cfg)
    table = [("table", (cfg["vocab_size"], cfg["hidden_size"]), "w"),
             ("norm", (cfg["hidden_size"],), "g")]
    draw_table = jax.jit(lambda key: draw(key, table))
    draw_layer = {k: jax.jit(lambda key, s=layer_specs(cfg, k): draw(key, s))
                  for k in sorted(set(ffns))}
    n = len(ffns)
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
    for i, ffn in enumerate(ffns):
        arrays = jax.device_get(draw_layer[ffn](keys[1 + i]))
        tensors = []
        for (name, _, _), a in zip(layer_specs(cfg, ffn), arrays):
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


def make_documents(seed: int, rows: int, seq_len: int, lengths: Dict,
                   vocab: Sequence[str]) -> Tuple[List[str], np.ndarray]:
    """Documents whose tokens, each followed by the end-of-document id, fill
    ``rows`` packed rows of ``seq_len`` and a little more; and the packed
    rows' ids as the program should see them, ``(rows, seq_len)``."""
    rng = np.random.default_rng([int(seed), 17])
    word_ids = np.asarray([i for i, t in enumerate(vocab)
                           if len(t) == 6 and t.isalpha()])
    eod = vocab.index("[SEP]")
    need, docs, flat = rows * seq_len + 1, [], []
    have = 0
    while have < need:
        n = int(np.clip(np.round(rng.lognormal(np.log(lengths["median"]),
                                               lengths["sigma"])),
                        1, lengths["cut"]))
        n = max(1, min(n, need - have - 1))     # the last one ends the rows
        ids = word_ids[rng.integers(0, len(word_ids), size=n)]
        docs.append(" ".join(vocab[i] for i in ids))
        flat += [ids, [eod]]
        have += n + 1
    packed = np.concatenate(flat).astype(np.int32)[:rows * seq_len]
    return docs, packed.reshape(rows, seq_len)


def seeded_inputs(ctx, rows: int):
    """Vocabulary, documents, the packed rows they should make, and the
    checkpoint under the run's work directory, all from ``ctx.seed``; marks
    the set-up parts ``documents`` and ``checkpoint_write``."""
    cfg, traffic = ctx.config, ctx.traffic
    vocab = gen.make_vocab(cfg["vocab_size"])
    docs, packed = make_documents(ctx.seed, rows, traffic["seq_len"],
                                  traffic["document_tokens"], vocab)
    ctx.mark("documents")
    path = os.path.join(ctx.workdir, "checkpoint")
    t0 = time.perf_counter()
    written = write_checkpoint(path, cfg, ctx.seed, traffic["weights"], vocab)
    ctx.say(f"gen_moonlight: checkpoint of {parameters(cfg)} parameters, "
            f"{written} bytes in {cfg['num_hidden_layers'] + 2} bfloat16 "
            f"shards, drawn and written in {time.perf_counter() - t0:.2f} s; "
            f"{len(docs)} documents")
    ctx.mark("checkpoint_write")
    return vocab, docs, packed, path, written
