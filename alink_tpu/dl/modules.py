"""Flax models: TransformerEncoder (BERT family) and KerasSequential.

Capability parity targets:
- BERT text classify/regress (reference: core/src/main/java/com/alibaba/alink/
  common/dl/BaseEasyTransferTrainBatchOp.java + akdl easytransfer models;
  params/tensorflow/bert/HasMaxSeqLength.java) — here a from-scratch flax
  encoder, bf16 compute / fp32 params, MXU-shaped matmuls.
- Keras-sequential layer specs (reference: operator/batch/classification/
  KerasSequentialClassifierTrainBatchOp.java + akdl keras_sequential model:
  core/src/main/python/akdl/akdl/models/tf/keras_sequential.py) — the same
  string layer grammar ("Dense(64)", "Relu()", "Dropout(0.1)", ...) parsed into
  a flax module.

Sharding hooks: parameter names follow fixed conventions matched by
``sharding.param_shardings`` (qkv/out kernels head-sharded on the ``model``
axis, MLP kernels sharded on the hidden dim, embeddings on vocab).
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Any, Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

from ..common.exceptions import AkIllegalArgumentException
from .attention import (blockwise_attention, packed_attention,
                        ring_attention)


@dataclass(frozen=True)
class BertConfig:
    vocab_size: int = 30522
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    intermediate_size: int = 3072
    max_position: int = 512
    type_vocab_size: int = 2
    dropout: float = 0.1
    num_labels: int = 2
    regression: bool = False
    dtype: Any = jnp.bfloat16  # compute dtype; params stay fp32
    use_ring_attention: bool = False  # seq-axis sequence parallelism
    remat: bool = False  # jax.checkpoint each layer (HBM <-> FLOPs trade)
    # "mean": masked mean-pool (robust for from-scratch training);
    # "cls": first-token pooling, matching the pretrained BERT pooler
    # (reference checkpoints are trained with NSP on the CLS slot)
    pool: str = "mean"
    # >0: single-device memory-efficient attention — K/V consumed in blocks
    # of this size under an online softmax, so the (S, S) score matrix never
    # materializes (long-context on one chip; composes with remat)
    attention_block_size: int = 0

    @staticmethod
    def base(**kw) -> "BertConfig":
        return BertConfig(**kw)

    @staticmethod
    def tiny(**kw) -> "BertConfig":
        d = dict(vocab_size=512, hidden_size=64, num_layers=2, num_heads=4,
                 intermediate_size=128, max_position=128, dropout=0.0)
        d.update(kw)
        return BertConfig(**d)


class SelfAttention(nn.Module):
    cfg: BertConfig
    mesh: Any = None

    @nn.compact
    def __call__(self, x, mask, deterministic: bool):
        c = self.cfg
        h, d = c.num_heads, c.hidden_size // c.num_heads
        qkv = nn.DenseGeneral((3, h * d), dtype=c.dtype, name="qkv")(x)

        def heads(i):
            return qkv[:, :, i].reshape(x.shape[0], x.shape[1], h, d)

        # scores, softmax and PV under one name in every operation's
        # op_name, whichever of the paths computes them
        with jax.named_scope("attention_core"):
            if c.use_ring_attention and self.mesh is not None:
                o = ring_attention(heads(0), heads(1), heads(2), mask,
                                   mesh=self.mesh)
            elif c.attention_block_size:
                o = blockwise_attention(heads(0), heads(1), heads(2), mask,
                                        block_size=c.attention_block_size)
            else:
                o = packed_attention(qkv, mask, num_heads=h)
        o = o.reshape(x.shape[0], x.shape[1], h * d)
        return nn.DenseGeneral(c.hidden_size, dtype=c.dtype, name="out")(o)


class TransformerLayer(nn.Module):
    cfg: BertConfig
    mesh: Any = None

    @nn.compact
    def __call__(self, x, mask, deterministic: bool):
        c = self.cfg
        a = SelfAttention(c, self.mesh, name="attention")(x, mask, deterministic)
        a = nn.Dropout(c.dropout)(a, deterministic=deterministic)
        x = nn.LayerNorm(dtype=c.dtype, name="ln_att")(x + a)
        f = nn.Dense(c.intermediate_size, dtype=c.dtype, name="mlp_in")(x)
        f = nn.gelu(f)
        f = nn.Dense(c.hidden_size, dtype=c.dtype, name="mlp_out")(f)
        f = nn.Dropout(c.dropout)(f, deterministic=deterministic)
        return nn.LayerNorm(dtype=c.dtype, name="ln_mlp")(x + f)


class TransformerEncoder(nn.Module):
    """BERT-style encoder + pooled classification/regression head."""

    cfg: BertConfig
    mesh: Any = None

    @nn.compact
    def __call__(self, input_ids, attention_mask=None, token_type_ids=None,
                 *, deterministic: bool = True, return_pooled: bool = False,
                 return_sequence: bool = False):
        c = self.cfg
        b, s = input_ids.shape
        if attention_mask is None:
            attention_mask = jnp.ones((b, s), jnp.int32)
        tok = nn.Embed(c.vocab_size, c.hidden_size, dtype=c.dtype,
                       name="tok_emb")(input_ids)
        pos = nn.Embed(c.max_position, c.hidden_size, dtype=c.dtype,
                       name="pos_emb")(jnp.arange(s)[None, :])
        x = tok + pos
        if token_type_ids is not None:
            x = x + nn.Embed(c.type_vocab_size, c.hidden_size, dtype=c.dtype,
                             name="type_emb")(token_type_ids)
        x = nn.LayerNorm(dtype=c.dtype, name="ln_emb")(x)
        x = nn.Dropout(c.dropout)(x, deterministic=deterministic)

        layer_cls = TransformerLayer
        if c.remat:
            layer_cls = nn.remat(TransformerLayer, static_argnums=(3,))
        for i in range(c.num_layers):
            x = layer_cls(c, self.mesh, name=f"layer_{i}")(
                x, attention_mask, deterministic
            )

        if return_sequence:  # token-level states (MLM pretraining heads)
            return x.astype(jnp.float32)
        if c.pool == "cls":  # pretrained BERT pooler input is the CLS slot
            pooled = x[:, 0]
        else:  # masked mean-pool (CLS-equivalent without a pretrained pooler)
            m = attention_mask.astype(x.dtype)[:, :, None]
            pooled = (x * m).sum(axis=1) / jnp.maximum(m.sum(axis=1), 1.0)
        pooled = jnp.tanh(nn.Dense(c.hidden_size, dtype=c.dtype, name="pooler")(pooled))
        if return_pooled:  # embedding serving (BertTextEmbeddingBatchOp)
            return pooled.astype(jnp.float32)
        out_dim = 1 if c.regression else c.num_labels
        logits = nn.Dense(out_dim, dtype=jnp.float32, name="head")(pooled)
        return logits


# ---------------------------------------------------------------------------
# KerasSequential analog
# ---------------------------------------------------------------------------

_LAYER_RE = re.compile(r"^\s*([A-Za-z_][A-Za-z0-9_]*)\s*(?:\((.*)\))?\s*$")


def _parse_args(argstr: str) -> Tuple[List[Any], dict]:
    args, kwargs = [], {}
    if not argstr or not argstr.strip():
        return args, kwargs
    for piece in argstr.split(","):
        piece = piece.strip()
        if not piece:
            continue
        if "=" in piece:
            k, v = piece.split("=", 1)
            kwargs[k.strip()] = _parse_val(v.strip())
        else:
            args.append(_parse_val(piece))
    return args, kwargs


def _parse_val(s: str):
    s = s.strip().strip("'\"")
    try:
        return int(s)
    except ValueError:
        pass
    try:
        return float(s)
    except ValueError:
        pass
    if s.lower() in ("true", "false"):
        return s.lower() == "true"
    return s


def parse_layers(specs: Sequence[str]) -> List[Tuple[str, list, dict]]:
    """Parse "Dense(64)" style layer specs (reference grammar:
    akdl keras_sequential — Dense/Relu/Sigmoid/Tanh/Softmax/Dropout/
    BatchNorm/Flatten; names case-insensitive)."""
    out = []
    for spec in specs:
        m = _LAYER_RE.match(spec)
        if not m:
            raise AkIllegalArgumentException(f"bad layer spec: {spec!r}")
        name = m.group(1).lower()
        args, kwargs = _parse_args(m.group(2) or "")
        out.append((name, args, kwargs))
    return out


class KerasSequential(nn.Module):
    """Sequential model from string layer specs + a task head."""

    layer_specs: Tuple[str, ...]
    out_dim: int = 1  # num classes (classification) or 1 (regression)
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, *, deterministic: bool = True):
        for i, (name, args, kwargs) in enumerate(parse_layers(self.layer_specs)):
            if name == "dense":
                x = nn.Dense(int(args[0]), dtype=self.dtype, name=f"dense_{i}")(x)
                act = kwargs.get("activation")
                if act:
                    x = _activation(act)(x)
            elif name in ("relu", "sigmoid", "tanh", "softmax", "gelu", "elu"):
                x = _activation(name)(x)
            elif name == "dropout":
                x = nn.Dropout(float(args[0]) if args else 0.5)(
                    x, deterministic=deterministic
                )
            elif name in ("batchnorm", "batchnormalization"):
                x = nn.BatchNorm(
                    use_running_average=deterministic,
                    dtype=self.dtype, name=f"norm_{i}",
                )(x)
            elif name in ("layernorm", "layernormalization"):
                x = nn.LayerNorm(dtype=self.dtype, name=f"norm_{i}")(x)
            elif name == "flatten":
                x = x.reshape(x.shape[0], -1)
            elif name == "reshape":
                x = x.reshape((x.shape[0],) + tuple(int(a) for a in args))
            elif name == "conv1d":
                filters = int(args[0])
                kernel = int(args[1]) if len(args) > 1 else 3
                strides = int(kwargs.get("strides", 1))
                x = nn.Conv(filters, kernel_size=(kernel,),
                            strides=(strides,), dtype=self.dtype,
                            name=f"conv_{i}")(x)
                act = kwargs.get("activation")
                if act:
                    x = _activation(act)(x)
            elif name == "maxpool1d":
                w = int(args[0]) if args else 2
                x = nn.max_pool(x, window_shape=(w,), strides=(w,))
            elif name == "globalavgpool1d":
                x = x.mean(axis=1)
            elif name in ("lstm", "gru"):
                units = int(args[0])
                cell = (nn.OptimizedLSTMCell(units, dtype=self.dtype)
                        if name == "lstm"
                        else nn.GRUCell(units, dtype=self.dtype))
                x = nn.RNN(cell, name=f"{name}_{i}")(x)
                if not kwargs.get("return_sequences"):
                    x = x[:, -1, :]
            else:
                raise AkIllegalArgumentException(f"unknown layer: {name!r}")
        return nn.Dense(self.out_dim, dtype=jnp.float32, name="head")(x)


def _activation(name: str) -> Callable:
    table = {
        "relu": nn.relu,
        "sigmoid": nn.sigmoid,
        "tanh": jnp.tanh,
        "softmax": nn.softmax,
        "gelu": nn.gelu,
        "elu": nn.elu,
    }
    if name.lower() not in table:
        raise AkIllegalArgumentException(f"unknown activation {name!r}")
    return table[name.lower()]
