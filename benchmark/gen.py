"""Everything a run makes from ``--seed``: the vocabulary, the documents, the
labels, the weights and the HF-layout checkpoint the program ingests.

Copied in spirit from ``chip_smoke.py`` (``_rows``, ``_write_checkpoint``) and
kept here because later PRs may edit that file and may not edit the yardstick.
Nothing here imports the program: the checkpoint is written in the public HF
layout (``config.json`` + ``model.safetensors`` + ``vocab.txt``) by a writer of
this file's own, and the reference reads the same tensors by the same names.
"""

from __future__ import annotations

import json
import os
import struct
from typing import Dict, List, Tuple

import numpy as np

_CONS = "bdfghjklmnprstvz"
_VOWS = "aeiou"
SPECIALS = {0: "[PAD]", 100: "[UNK]", 101: "[CLS]", 102: "[SEP]", 103: "[MASK]"}
PUNCT = [".", ",", ";", "!", "?"]


def make_vocab(size: int) -> List[str]:
    """A fixed WordPiece vocabulary of ``size`` rows in BERT's file layout:
    the specials where bert-base-uncased has them (or packed at the front of
    a toy vocabulary), punctuation, six-letter whole words and three-letter
    ``##`` continuations. The same for every seed, as a published vocab is."""
    if size < 64:
        raise ValueError("a vocabulary needs at least 64 rows")
    rng = np.random.default_rng(20181011)
    vocab = [f"[unused{i}]" for i in range(size)]
    specials = SPECIALS if size > 200 else {
        i: t for i, t in enumerate(SPECIALS.values())}
    for i, t in specials.items():
        vocab[i] = t
    free = [i for i in range(size) if i not in specials]
    free = free[len(free) // 300:]      # keep some [unusedN] rows, as BERT does
    n_cont = (len(free) - len(PUNCT)) // 3
    n_word = len(free) - len(PUNCT) - n_cont
    syl = [c + v for c in _CONS for v in _VOWS]                 # 80 syllables
    abc = "abcdefghijklmnopqrstuvwxyz"
    words = ["".join(syl[j] for j in np.unravel_index(i, (80, 80, 80)))
             for i in rng.permutation(80 ** 3)[:n_word]]
    conts = ["##" + "".join(abc[j] for j in np.unravel_index(i, (26, 26, 26)))
             for i in rng.permutation(26 ** 3)[:n_cont]]
    for i, t in zip(free, PUNCT + words + conts):
        vocab[i] = t
    return vocab


def make_documents(seed: int, n: int, min_pieces: int, vocab: List[str]
                   ) -> Tuple[List[str], np.ndarray]:
    """``n`` distinct documents of at least ``min_pieces`` word pieces each
    (whole words, words with one or two continuations, punctuation), and a
    label bit for each, all drawn from ``seed``."""
    rng = np.random.default_rng([int(seed), 7])
    words = [t for t in vocab if len(t) == 6 and t.isalpha()]
    conts = [t[2:] for t in vocab if t.startswith("##")]
    docs = []
    for i in range(n):
        parts, pieces = [f"{words[i % len(words)]}"], 1
        shape = rng.integers(0, 10, size=min_pieces + 8)
        wi = rng.integers(0, len(words), size=min_pieces + 8)
        ci = rng.integers(0, len(conts), size=(min_pieces + 8, 2))
        k = 0
        while pieces < min_pieces + 4:
            s = shape[k]
            if s < 6:
                parts.append(words[wi[k]]); pieces += 1
            elif s < 8:
                parts.append(words[wi[k]] + conts[ci[k, 0]]); pieces += 2
            elif s < 9:
                parts.append(words[wi[k]] + conts[ci[k, 0]] + conts[ci[k, 1]])
                pieces += 3
            else:
                parts[-1] += PUNCT[wi[k] % len(PUNCT)]; pieces += 1
            k += 1
        docs.append(" ".join(parts))
    labels = rng.integers(0, 2, size=n).astype(np.int64)
    if n > 1:
        labels[0], labels[1] = 0, 1      # both classes present, whatever n
    return docs, labels


def tensor_specs(cfg: Dict) -> List[Tuple[str, Tuple[int, ...], str]]:
    """(HF name, shape, kind) of every tensor of a BERT encoder with pooler.
    ``kind``: w = N(0, 0.02); b = N(0, 0.02); g = 1 + N(0, 0.02)."""
    h, f = cfg["hidden_size"], cfg["intermediate_size"]
    out = [("bert.embeddings.word_embeddings.weight", (cfg["vocab_size"], h), "w"),
           ("bert.embeddings.position_embeddings.weight",
            (cfg["max_position_embeddings"], h), "w"),
           ("bert.embeddings.token_type_embeddings.weight",
            (cfg["type_vocab_size"], h), "w"),
           ("bert.embeddings.LayerNorm.weight", (h,), "g"),
           ("bert.embeddings.LayerNorm.bias", (h,), "b")]
    for i in range(cfg["num_hidden_layers"]):
        p = f"bert.encoder.layer.{i}."
        for nm in ("attention.self.query", "attention.self.key",
                   "attention.self.value", "attention.output.dense"):
            out += [(p + nm + ".weight", (h, h), "w"), (p + nm + ".bias", (h,), "b")]
        out += [(p + "attention.output.LayerNorm.weight", (h,), "g"),
                (p + "attention.output.LayerNorm.bias", (h,), "b"),
                (p + "intermediate.dense.weight", (f, h), "w"),
                (p + "intermediate.dense.bias", (f,), "b"),
                (p + "output.dense.weight", (h, f), "w"),
                (p + "output.dense.bias", (h,), "b"),
                (p + "output.LayerNorm.weight", (h,), "g"),
                (p + "output.LayerNorm.bias", (h,), "b")]
    out += [("bert.pooler.dense.weight", (h, h), "w"),
            ("bert.pooler.dense.bias", (h,), "b")]
    return out


def make_weights(cfg: Dict, seed: int, std: float = 0.02) -> Dict[str, np.ndarray]:
    """All encoder tensors, float32, drawn on the device in one jitted call
    from the seed and brought to the host once (the checkpoint is a file).
    ``std`` is that of the matrices and embeddings; biases and layer-norm
    offsets keep 0.02."""
    import jax
    import jax.numpy as jnp

    specs = tensor_specs(cfg)

    def draw(key):
        keys = jax.random.split(key, len(specs))
        outs = []
        for k, (_, shape, kind) in zip(keys, specs):
            x = (std if kind == "w" else 0.02) * jax.random.normal(
                k, shape, jnp.float32)
            outs.append(x + 1.0 if kind == "g" else x)
        return outs

    key = jax.random.PRNGKey(int(seed) % (2 ** 31 - 1))
    arrays = jax.device_get(jax.jit(draw)(key))
    return {name: np.asarray(a) for (name, _, _), a in zip(specs, arrays)}


def write_checkpoint(path: str, cfg: Dict, tensors: Dict[str, np.ndarray],
                     vocab: List[str]) -> int:
    """HF layout: config.json, model.safetensors (float32), vocab.txt.
    Returns the bytes written."""
    os.makedirs(path, exist_ok=True)
    hf_keys = ("model_type", "vocab_size", "hidden_size", "num_hidden_layers",
               "num_attention_heads", "intermediate_size",
               "max_position_embeddings", "type_vocab_size", "hidden_act",
               "layer_norm_eps", "hidden_dropout_prob",
               "attention_probs_dropout_prob", "initializer_range",
               "pad_token_id", "position_embedding_type")
    with open(os.path.join(path, "config.json"), "w") as f:
        json.dump({k: cfg[k] for k in hf_keys if k in cfg}, f, indent=1)
    with open(os.path.join(path, "vocab.txt"), "w", encoding="utf-8") as f:
        f.write("\n".join(vocab) + "\n")
    header, off = {}, 0
    names = sorted(tensors)
    for name in names:
        a = tensors[name]
        header[name] = {"dtype": "F32", "shape": list(a.shape),
                        "data_offsets": [off, off + a.nbytes]}
        off += a.nbytes
    hb = json.dumps(header).encode()
    with open(os.path.join(path, "model.safetensors"), "wb") as f:
        f.write(struct.pack("<Q", len(hb)))
        f.write(hb)
        for name in names:
            f.write(np.ascontiguousarray(tensors[name], np.float32).tobytes())
    return off + len(hb) + 8


def seeded_inputs(ctx, n_docs: int):
    """What every BERT kind makes first, all from ``ctx.seed``: the
    vocabulary, ``n_docs`` documents with labels, the weights, and the
    checkpoint written under the run's work directory. Marks the set-up
    parts ``documents`` and ``checkpoint_write``. Returns (vocab, docs,
    labels, weights, checkpoint path, bytes written)."""
    cfg, traffic = ctx.config, ctx.traffic
    vocab = make_vocab(cfg["vocab_size"])
    docs, labels = make_documents(ctx.seed, n_docs, traffic["min_pieces"], vocab)
    ctx.mark("documents")
    weights = make_weights(cfg, ctx.seed, traffic.get("weight_std", 0.02))
    path = os.path.join(ctx.workdir, "checkpoint")
    written = write_checkpoint(path, cfg, weights, vocab)
    ctx.mark("checkpoint_write")
    return vocab, docs, labels, weights, path, written
