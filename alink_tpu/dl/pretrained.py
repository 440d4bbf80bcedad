"""Pretrained BERT checkpoint ingest — the BertResources analog.

The reference ships pretrained BERT vocab + checkpoints through its
resource-plugin system and fine-tunes from them (reference:
core/src/main/java/com/alibaba/alink/common/dl/BertResources.java:28,76-85;
consumed by common/dl/BaseEasyTransferTrainBatchOp.java). This build runs
zero-egress, so resources are resolved from the local plugin directory
(``MLEnvironment.get_plugin_dir()``), same contract as the reference's
pre-downloaded plugin layout — the user drops a checkpoint directory there
(or passes an explicit path) and the BERT ops fine-tune from it.

Supported on-disk formats (auto-detected):
- HuggingFace layout: ``config.json`` + ``model.safetensors`` /
  ``pytorch_model.bin`` / ``flax_model.msgpack`` + ``vocab.txt``
- HuggingFace sharded layout: ``model-0000k-of-0000n.safetensors`` with
  ``model.safetensors.index.json``, read tensor by tensor in the file's own
  dtype (:func:`iter_safetensors`; the causal LM's ingest, dl/lm.py)
- google-research TF v1 checkpoint: ``bert_config.json`` +
  ``bert_model.ckpt.{index,data-*}`` + ``vocab.txt`` (the exact artifact the
  reference's CKPT resources unpack, e.g. uncased_L-12_H-768_A-12.zip)

Weights map into :class:`alink_tpu.dl.modules.TransformerEncoder`'s tree
(qkv fused, ``pool="cls"`` for pretrained fidelity); the classifier head is
freshly initialised, which is what fine-tuning means.
"""

from __future__ import annotations

import json
import os
import struct
from typing import Any, Dict, Iterator, List, Optional, Tuple

import numpy as np

from ..common.exceptions import (AkIllegalArgumentException,
                                 AkPluginNotExistException)

# normalized model names accepted by ``bertModelName`` (reference enum
# BertResources.ModelName) -> plugin subdirectory
MODEL_NAME_DIRS = {
    "base-uncased": "bert-base-uncased",
    "base-cased": "bert-base-cased",
    "base-chinese": "bert-base-chinese",
    "base-multilingual-cased": "bert-base-multilingual-cased",
}


def _normalize_model_name(name: str) -> str:
    n = name.strip().lower().replace("_", "-")
    if n.startswith("bert-"):
        n = n[len("bert-"):]
    return n


def resolve_bert_resource(model_name: str) -> str:
    """Resolve ``bertModelName`` to a local checkpoint directory under the
    plugin dir, or raise naming exactly what to place where (the zero-egress
    stand-in for the reference's resource downloader)."""
    from ..common.env import AlinkGlobalConfiguration

    n = _normalize_model_name(model_name)
    sub = MODEL_NAME_DIRS.get(n, f"bert-{n}")
    root = AlinkGlobalConfiguration.get_plugin_dir()
    cand = os.path.join(root, "bert", sub)
    if os.path.isdir(cand) and _detect_format(cand) is not None:
        return cand
    raise AkPluginNotExistException(
        f"pretrained BERT resource {model_name!r} not found: place a "
        f"checkpoint directory at {cand} (HuggingFace layout with "
        f"config.json + model.safetensors + vocab.txt, or a google-research "
        f"TF checkpoint with bert_config.json + bert_model.ckpt.* + "
        f"vocab.txt). The reference downloads these through its resource "
        f"plugin (BertResources.java); this build is zero-egress, so the "
        f"files must be staged locally."
    )


def _detect_format(path: str) -> Optional[str]:
    if os.path.isfile(os.path.join(path, "model.safetensors")):
        return "safetensors"
    if os.path.isfile(os.path.join(path, "pytorch_model.bin")):
        return "torch"
    if os.path.isfile(os.path.join(path, "flax_model.msgpack")):
        return "flax"
    for f in os.listdir(path) if os.path.isdir(path) else []:
        if f.endswith(".ckpt.index") or f.endswith(".ckpt.meta"):
            return "tf_ckpt"
    return None


# ---------------------------------------------------------------------------
# raw tensor readers -> flat {hf_style_name: np.ndarray}
# ---------------------------------------------------------------------------


def _read_safetensors(path: str) -> Dict[str, np.ndarray]:
    """Minimal standalone safetensors reader (header is JSON; tensors are
    raw little-endian buffers). Avoids framework tensor detours."""
    _DT = {
        "F64": np.float64, "F32": np.float32, "F16": np.float16,
        "BF16": None, "I64": np.int64, "I32": np.int32, "I16": np.int16,
        "I8": np.int8, "U8": np.uint8, "BOOL": np.bool_,
    }
    out: Dict[str, np.ndarray] = {}
    with open(path, "rb") as f:
        (hlen,) = struct.unpack("<Q", f.read(8))
        header = json.loads(f.read(hlen))
        blob = f.read()
    for name, info in header.items():
        if name == "__metadata__":
            continue
        a, b = info["data_offsets"]
        raw = blob[a:b]
        if info["dtype"] == "BF16":
            u16 = np.frombuffer(raw, np.uint16).astype(np.uint32) << 16
            arr = u16.view(np.float32)
        else:
            arr = np.frombuffer(raw, _DT[info["dtype"]])
        out[name] = arr.reshape(info["shape"]).copy()
    return out


SAFETENSORS_INDEX = "model.safetensors.index.json"
_ST_NAMES = {"F64": "float64", "F32": "float32", "F16": "float16",
             "BF16": "bfloat16", "I64": "int64", "I32": "int32",
             "I16": "int16", "I8": "int8", "U8": "uint8", "BOOL": "bool"}


def _st_dtype(tag: str):
    import ml_dtypes

    return np.dtype(ml_dtypes.bfloat16 if tag == "BF16" else _ST_NAMES[tag])


def safetensors_files(path: str) -> List[str]:
    """The safetensors files of an HF-layout directory, in the index's
    order (``model.safetensors.index.json``) or the single
    ``model.safetensors``."""
    index = os.path.join(path, SAFETENSORS_INDEX)
    if os.path.isfile(index):
        with open(index) as f:
            names = json.load(f)["weight_map"].values()
        return [os.path.join(path, n) for n in dict.fromkeys(names)]
    return [os.path.join(path, "model.safetensors")]


def iter_safetensors(path: str) -> Iterator[Tuple[str, np.ndarray]]:
    """``(name, array)`` for every tensor of a sharded or single-file
    safetensors directory, each a read-only view of the memory-mapped file
    in the file's own dtype (bfloat16 as ``ml_dtypes.bfloat16``): nothing
    is widened and no file is held in memory whole, so a tree larger than
    the host would like as float32 goes to the device tensor by tensor."""
    for file in safetensors_files(path):
        with open(file, "rb") as f:
            (hlen,) = struct.unpack("<Q", f.read(8))
            header = json.loads(f.read(hlen))
        blob = np.memmap(file, np.uint8, mode="r", offset=8 + hlen)
        for name, info in header.items():
            if name == "__metadata__":
                continue
            a, b = info["data_offsets"]
            yield name, blob[a:b].view(_st_dtype(info["dtype"])).reshape(
                info["shape"])


def _read_torch_bin(path: str) -> Dict[str, np.ndarray]:
    import torch

    state = torch.load(path, map_location="cpu", weights_only=True)
    return {k: v.float().numpy() for k, v in state.items()}


def _read_flax_msgpack(path: str) -> Dict[str, np.ndarray]:
    from flax import serialization, traverse_util

    with open(path, "rb") as f:
        tree = serialization.msgpack_restore(f.read())
    flat = traverse_util.flatten_dict(tree, sep=".")
    # HF flax names: embeddings.word_embeddings.embedding etc. Convert to the
    # torch-style names the mapper below understands. Renames are anchored to
    # the last path segment ("...embeddings" must not become "...weights").
    out = {}
    for k, v in flat.items():
        if k.endswith(".embedding"):
            k = k[: -len(".embedding")] + ".weight"
        elif k.endswith(".kernel"):  # flax kernels are already (in, out)
            k = k[: -len(".kernel")] + ".weight_t"
        elif k.endswith(".scale"):
            k = k[: -len(".scale")] + ".weight"
        out[k] = np.asarray(v)
    return out


def _read_tf_ckpt(path: str) -> Dict[str, np.ndarray]:
    """google-research BERT v1 checkpoint -> HF-style names.

    TF variable names (bert/encoder/layer_0/attention/self/query/kernel, ...)
    are renamed; TF kernels are already (in, out) so they're tagged
    ``weight_t`` to skip the torch transpose."""
    import tensorflow as tf

    reader = tf.train.load_checkpoint(path)
    shapes = reader.get_variable_to_shape_map()
    out: Dict[str, np.ndarray] = {}
    for var in shapes:
        if not var.startswith("bert/") or "adam" in var.lower():
            continue
        name = var[len("bert/"):]
        name = (name.replace("/", ".")
                    .replace("encoder.layer_", "encoder.layer.")
                    .replace("LayerNorm.gamma", "LayerNorm.weight")
                    .replace("LayerNorm.beta", "LayerNorm.bias")
                    .replace(".kernel", ".weight_t"))
        if name.startswith("embeddings.") and name.endswith("_embeddings"):
            name += ".weight"
        out[name] = np.asarray(reader.get_tensor(var))
    return out


def _infer_do_lower_case(path: str, hf_cfg: Dict[str, Any]) -> bool:
    """HF keeps the casing flag in tokenizer_config.json, not config.json;
    google bert_config.json has neither. Fall back to the directory name
    ('-cased' checkpoints must not be lowercased/accent-stripped)."""
    tc = os.path.join(path, "tokenizer_config.json")
    if os.path.isfile(tc):
        with open(tc) as f:
            v = json.load(f).get("do_lower_case")
        if v is not None:
            return bool(v)
    if "do_lower_case" in hf_cfg:
        return bool(hf_cfg["do_lower_case"])
    base = os.path.basename(os.path.normpath(path)).lower()
    if "uncased" in base:
        return True
    if "cased" in base or "chinese" in base or "multilingual" in base:
        return False
    return True


def _load_config(path: str) -> Dict[str, Any]:
    for fname in ("config.json", "bert_config.json"):
        p = os.path.join(path, fname)
        if os.path.isfile(p):
            with open(p) as f:
                return json.load(f)
    raise AkIllegalArgumentException(
        f"no config.json / bert_config.json under {path}")


def load_vocab_file(path: str) -> "list[str]":
    p = os.path.join(path, "vocab.txt") if os.path.isdir(path) else path
    if not os.path.isfile(p):
        raise AkPluginNotExistException(
            f"vocab.txt not found under {os.path.dirname(p) or p} — the "
            f"pretrained tokenizer requires the published WordPiece vocab "
            f"(reference ships it as the VOCAB resource, BertResources.java)")
    with open(p, encoding="utf-8") as f:
        return [line.rstrip("\n") for line in f]


# ---------------------------------------------------------------------------
# HF-name tensors -> TransformerEncoder param tree
# ---------------------------------------------------------------------------


def _strip_prefix(raw: Dict[str, np.ndarray]) -> Dict[str, np.ndarray]:
    out = {}
    for k, v in raw.items():
        if k.startswith("bert."):
            k = k[len("bert."):]
        out[k] = v
    return out


class _W:
    """Name-indexed tensor store with (in,out)-orientation handling."""

    def __init__(self, raw: Dict[str, np.ndarray]):
        self.raw = _strip_prefix(raw)

    def dense(self, prefix: str) -> Tuple[np.ndarray, np.ndarray]:
        """Returns (kernel (in,out), bias)."""
        if prefix + ".weight_t" in self.raw:  # already (in, out)
            k = self.raw[prefix + ".weight_t"]
        else:
            k = self.raw[prefix + ".weight"].T  # torch (out, in)
        b = self.raw[prefix + ".bias"]
        return np.ascontiguousarray(k, np.float32), b.astype(np.float32)

    def ln(self, prefix: str) -> Dict[str, np.ndarray]:
        return {"scale": self.raw[prefix + ".weight"].astype(np.float32),
                "bias": self.raw[prefix + ".bias"].astype(np.float32)}

    def emb(self, name: str) -> np.ndarray:
        return self.raw[name + ".weight"].astype(np.float32)

    def has(self, name: str) -> bool:
        return any(k.startswith(name) for k in self.raw)


def bert_tree_from_hf(raw: Dict[str, np.ndarray],
                      num_layers: int) -> Dict[str, Any]:
    """Build the ``TransformerEncoder`` encoder subtree (no head) from
    HF-style named tensors. qkv is fused into the DenseGeneral layout
    (kernel (hidden, 3, heads*dim), bias (3, heads*dim))."""
    w = _W(raw)
    tree: Dict[str, Any] = {
        "tok_emb": {"embedding": w.emb("embeddings.word_embeddings")},
        "pos_emb": {"embedding": w.emb("embeddings.position_embeddings")},
        "ln_emb": w.ln("embeddings.LayerNorm"),
    }
    if w.has("embeddings.token_type_embeddings"):
        tree["type_emb"] = {
            "embedding": w.emb("embeddings.token_type_embeddings")}
    hidden = tree["tok_emb"]["embedding"].shape[1]
    for i in range(num_layers):
        p = f"encoder.layer.{i}."
        qk, qb = w.dense(p + "attention.self.query")
        kk, kb = w.dense(p + "attention.self.key")
        vk, vb = w.dense(p + "attention.self.value")
        ok, ob = w.dense(p + "attention.output.dense")
        ik, ib = w.dense(p + "intermediate.dense")
        mk, mb = w.dense(p + "output.dense")
        tree[f"layer_{i}"] = {
            "attention": {
                "qkv": {
                    "kernel": np.stack([qk, kk, vk], axis=1),  # (h, 3, h)
                    "bias": np.stack([qb, kb, vb], axis=0),    # (3, h)
                },
                "out": {"kernel": ok, "bias": ob},
            },
            "ln_att": w.ln(p + "attention.output.LayerNorm"),
            "mlp_in": {"kernel": ik, "bias": ib},
            "mlp_out": {"kernel": mk, "bias": mb},
            "ln_mlp": w.ln(p + "output.LayerNorm"),
        }
        assert tree[f"layer_{i}"]["attention"]["qkv"]["kernel"].shape[0] == hidden
    if w.has("pooler.dense"):
        pk, pb = w.dense("pooler.dense")
        tree["pooler"] = {"kernel": pk, "bias": pb}
    return tree


def load_bert_checkpoint(path: str):
    """Read a checkpoint directory -> (config_dict, encoder_subtree).

    ``config_dict`` carries the architecture (hidden_size, num_layers, ...)
    with HF/google key names normalised to :class:`BertConfig` fields."""
    fmt = _detect_format(path)
    if fmt is None:
        raise AkPluginNotExistException(
            f"no BERT checkpoint found under {path} (looked for "
            f"model.safetensors / pytorch_model.bin / flax_model.msgpack / "
            f"*.ckpt.index)")
    hf_cfg = _load_config(path)
    cfg = {
        "vocab_size": hf_cfg["vocab_size"],
        "hidden_size": hf_cfg["hidden_size"],
        "num_layers": hf_cfg.get("num_hidden_layers", hf_cfg.get("num_layers")),
        "num_heads": hf_cfg.get("num_attention_heads", hf_cfg.get("num_heads")),
        "intermediate_size": hf_cfg["intermediate_size"],
        "max_position": hf_cfg.get("max_position_embeddings", 512),
        "type_vocab_size": hf_cfg.get("type_vocab_size", 2),
        "do_lower_case": _infer_do_lower_case(path, hf_cfg),
    }
    reader = {
        "safetensors": lambda p: _read_safetensors(
            os.path.join(p, "model.safetensors")),
        "torch": lambda p: _read_torch_bin(os.path.join(p, "pytorch_model.bin")),
        "flax": lambda p: _read_flax_msgpack(
            os.path.join(p, "flax_model.msgpack")),
        "tf_ckpt": _read_tf_ckpt_dir,
    }[fmt]
    tree = bert_tree_from_hf(reader(path), cfg["num_layers"])
    return cfg, tree


def _read_tf_ckpt_dir(path: str) -> Dict[str, np.ndarray]:
    for f in sorted(os.listdir(path)):
        if f.endswith(".ckpt.index"):
            return _read_tf_ckpt(os.path.join(path, f[: -len(".index")]))
    raise AkPluginNotExistException(f"no *.ckpt.index under {path}")


def init_from_pretrained(model, cfg, subtree: Dict[str, Any], sample: dict,
                         seed: int = 0):
    """model.init with the encoder subtree grafted in; head (and any part the
    checkpoint lacks, e.g. pooler in some exports) keeps its fresh init."""
    import warnings

    import jax

    template = model.init(jax.random.PRNGKey(seed), **sample)
    params = dict(template["params"])
    skipped: list = []
    merged = _merge(params, subtree, skipped=skipped)
    if skipped:
        # silently dropping checkpoint tensors would leave layers at random
        # init and "fine-tuning" would quietly train from scratch
        warnings.warn(
            f"pretrained checkpoint tensors not consumed by the model "
            f"(left at fresh init): {skipped[:8]}"
            f"{' ...' if len(skipped) > 8 else ''}")
    return {**template, "params": merged}


def _merge(template: Dict[str, Any], new: Dict[str, Any], *, skipped: list,
           prefix: str = "") -> Dict[str, Any]:
    out = dict(template)
    for k, v in new.items():
        if k not in out:
            skipped.append(prefix + k)
            continue
        if isinstance(v, dict) and isinstance(out[k], dict):
            out[k] = _merge(out[k], v, skipped=skipped, prefix=prefix + k + ".")
        else:
            tv = out[k]
            if tuple(np.shape(tv)) != tuple(np.shape(v)):
                raise AkIllegalArgumentException(
                    f"pretrained tensor {k} has shape {np.shape(v)}, model "
                    f"expects {tuple(np.shape(tv))} — config mismatch")
            out[k] = np.asarray(v, np.float32)
    return out


# ---------------------------------------------------------------------------
# export (round-trip): params -> HF-layout directory
# ---------------------------------------------------------------------------


def save_bert_checkpoint(params, cfg, path: str, vocab: "list[str]") -> None:
    """Write an HF-layout checkpoint (config.json + model.safetensors +
    vocab.txt) from a TransformerEncoder param tree, so models trained here
    can be re-ingested (and shipped to other BERT stacks)."""
    os.makedirs(path, exist_ok=True)
    p = params.get("params", params)
    tensors: Dict[str, np.ndarray] = {}

    def dense_out(prefix: str, sub):  # to torch (out, in)
        tensors[prefix + ".weight"] = np.ascontiguousarray(
            np.asarray(sub["kernel"], np.float32).T)
        tensors[prefix + ".bias"] = np.asarray(sub["bias"], np.float32)

    def ln_out(prefix: str, sub):
        tensors[prefix + ".weight"] = np.asarray(sub["scale"], np.float32)
        tensors[prefix + ".bias"] = np.asarray(sub["bias"], np.float32)

    tensors["bert.embeddings.word_embeddings.weight"] = np.asarray(
        p["tok_emb"]["embedding"], np.float32)
    tensors["bert.embeddings.position_embeddings.weight"] = np.asarray(
        p["pos_emb"]["embedding"], np.float32)
    if "type_emb" in p:
        tensors["bert.embeddings.token_type_embeddings.weight"] = np.asarray(
            p["type_emb"]["embedding"], np.float32)
    ln_out("bert.embeddings.LayerNorm", p["ln_emb"])
    n_layers = cfg.num_layers if hasattr(cfg, "num_layers") else cfg["num_layers"]
    for i in range(n_layers):
        lp = p[f"layer_{i}"]
        hfp = f"bert.encoder.layer.{i}."
        qkv_k = np.asarray(lp["attention"]["qkv"]["kernel"], np.float32)
        qkv_b = np.asarray(lp["attention"]["qkv"]["bias"], np.float32)
        for j, nm in enumerate(("query", "key", "value")):
            tensors[hfp + f"attention.self.{nm}.weight"] = (
                np.ascontiguousarray(qkv_k[:, j, :].T))
            tensors[hfp + f"attention.self.{nm}.bias"] = qkv_b[j]
        dense_out(hfp + "attention.output.dense", lp["attention"]["out"])
        ln_out(hfp + "attention.output.LayerNorm", lp["ln_att"])
        dense_out(hfp + "intermediate.dense", lp["mlp_in"])
        dense_out(hfp + "output.dense", lp["mlp_out"])
        ln_out(hfp + "output.LayerNorm", lp["ln_mlp"])
    if "pooler" in p:
        dense_out("bert.pooler.dense", p["pooler"])

    _write_safetensors(os.path.join(path, "model.safetensors"), tensors)
    c = cfg if isinstance(cfg, dict) else {
        "vocab_size": cfg.vocab_size, "hidden_size": cfg.hidden_size,
        "num_layers": cfg.num_layers, "num_heads": cfg.num_heads,
        "intermediate_size": cfg.intermediate_size,
        "max_position": cfg.max_position,
        "type_vocab_size": cfg.type_vocab_size,
    }
    hf_cfg = {
        "model_type": "bert",
        "vocab_size": c["vocab_size"],
        "hidden_size": c["hidden_size"],
        "num_hidden_layers": c["num_layers"],
        "num_attention_heads": c["num_heads"],
        "intermediate_size": c["intermediate_size"],
        "max_position_embeddings": c["max_position"],
        "type_vocab_size": c.get("type_vocab_size", 2),
    }
    with open(os.path.join(path, "config.json"), "w") as f:
        json.dump(hf_cfg, f, indent=1)
    with open(os.path.join(path, "vocab.txt"), "w", encoding="utf-8") as f:
        f.write("\n".join(vocab) + "\n")


def write_safetensors_shards(path: str, shards) -> int:
    """An HF-layout directory's safetensors from ``shards``, an iterable of
    lists of ``(name, array)``: one file a list, written as it arrives, and
    the index that names each tensor's file. Returns the bytes of tensors."""
    files, total = [], 0
    for tensors in shards:
        tensors = dict(tensors)
        files.append(sorted(tensors))
        _write_safetensors(os.path.join(path, f".shard-{len(files)}"), tensors)
        total += sum(int(a.nbytes) for a in tensors.values())
    weight_map = {}
    for i, names in enumerate(files):
        fname = f"model-{i + 1:05d}-of-{len(files):05d}.safetensors"
        os.replace(os.path.join(path, f".shard-{i + 1}"),
                   os.path.join(path, fname))
        weight_map.update({n: fname for n in names})
    with open(os.path.join(path, SAFETENSORS_INDEX), "w") as f:
        json.dump({"metadata": {"total_size": total},
                   "weight_map": weight_map}, f)
    return total


def _write_safetensors(path: str, tensors: Dict[str, np.ndarray]) -> None:
    import ml_dtypes

    _DT = {np.dtype(np.float32): "F32", np.dtype(np.float64): "F64",
           np.dtype(np.int64): "I64", np.dtype(np.int32): "I32",
           np.dtype(ml_dtypes.bfloat16): "BF16"}
    header: Dict[str, Any] = {}
    off = 0
    bufs = []
    for name in sorted(tensors):
        a = np.ascontiguousarray(tensors[name])
        raw = a.tobytes()
        header[name] = {"dtype": _DT[a.dtype], "shape": list(a.shape),
                        "data_offsets": [off, off + len(raw)]}
        off += len(raw)
        bufs.append(raw)
    hb = json.dumps(header).encode()
    with open(path, "wb") as f:
        f.write(struct.pack("<Q", len(hb)))
        f.write(hb)
        for b in bufs:
            f.write(b)
