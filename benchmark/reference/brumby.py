"""Plain reference for the Brumby cells: the decoder block with power-retention
layers in its attention form, in straightforward ``jax.numpy`` and float32
with every matrix product at ``highest`` precision. No cache, no state, no
chunks, no batching: one row at a time, the whole sequence at once, layer by
layer with that layer's weights read from the checkpoint file (the float32
weights of all layers do not fit beside anything). It imports nothing of
``alink_tpu``.

The layer, for a block with input ``x`` (ISSUE 28 has the same text):
``a = RMSNorm(x)``; ``q = a W_q`` (Hq heads), ``k = a W_k``, ``v = a W_v``
(Hkv heads), no biases; per-head RMS norm on q and k; rotary positions on q
and k (HF's half-split convention); a gate ``log g_t = logsigmoid(a W_g +
b_g)``, one scalar per key/value head and position, ``b_g`` one bias a head;

    o_t = sum_{s<=t} G_ts w_ts v_s / (sum_{s<=t} G_ts w_ts + eps)
    w_ts = (q_t.k_s / sqrt(d))^2        G_ts = prod_{r=s+1..t} g_r

each key/value head read by its Hq/Hkv query heads; ``x' = x + concat(o) W_o``;
``x'' = x' + W_down(silu(W_gate n) * W_up n)`` with ``n = RMSNorm(x')``; a
final RMS norm and the untied head. Departures from the published model, each
listed in the configuration's ``assumed``: the retention keys (degree 2, the
gate's form, the normaliser and its eps, the scale) come from the layer's
publications, not from ``config.json``, which has none of them; the gate's
bias is assumed beyond ISSUE 28's text (without one a seeded model's gates
have a mean log of -0.9 and forget a chunk's inherited state within a few
positions, where a trained model's sit near 1); the tensor names
``self_attn.g_proj.weight`` and ``.bias`` are this benchmark's; ``G_ts`` is computed as
``exp(cum_t - cum_s)`` of the summed log-gates, in float32.

``precision="fp8"`` is the control: the operands and the result of every
matrix product, the attention's among them, rounded to float8 (e4m3, one
scale per tensor), as the program's are to bfloat16. ``precision="bf16"``
rounds the same places to bfloat16: what the configuration's own precision
reads against float32 in this arithmetic, whatever the program does (printed
beside the control, never a limit). ``visible_from`` is the third control: a
position ``t`` sums over ``s >= visible_from[t]`` only, which is what a program
reads that loses the state it should carry from one prompt chunk to the next.
"""

from __future__ import annotations

import json
import math
import os
import struct
from functools import partial
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.bert import _round_fp8

PRECISIONS = ("f32", "bf16", "fp8")
HEAD_BLOCK = 32768


# -- the checkpoint file -------------------------------------------------------

class Checkpoint:
    """Tensors of an HF-layout sharded safetensors directory by name, read
    from the memory-mapped files when asked for."""

    def __init__(self, path: str):
        import ml_dtypes

        self._dtypes = {"BF16": np.dtype(ml_dtypes.bfloat16),
                        "F32": np.dtype(np.float32)}
        with open(os.path.join(path, "model.safetensors.index.json")) as f:
            files = sorted(set(json.load(f)["weight_map"].values()))
        self._where: Dict[str, Tuple[np.memmap, dict]] = {}
        for name in files:
            file = os.path.join(path, name)
            with open(file, "rb") as f:
                (hlen,) = struct.unpack("<Q", f.read(8))
                header = json.loads(f.read(hlen))
            blob = np.memmap(file, np.uint8, mode="r", offset=8 + hlen)
            for tensor, info in header.items():
                if tensor != "__metadata__":
                    self._where[tensor] = (blob, info)

    def host(self, name: str) -> np.ndarray:
        blob, info = self._where[name]
        a, b = info["data_offsets"]
        return blob[a:b].view(self._dtypes[info["dtype"]]).reshape(info["shape"])

    def f32(self, name: str) -> jax.Array:
        """On the device in float32 (widened there, not on the host)."""
        return jnp.asarray(self.host(name)).astype(jnp.float32)

    def layer(self, i: int) -> Dict[str, jax.Array]:
        """A layer's tensors by their name inside it, a weight's without
        its ``.weight`` (``self_attn.g_proj``, ``self_attn.g_proj.bias``)."""
        p = f"model.layers.{i}."
        return {n[len(p):].removesuffix(".weight"): self.f32(n)
                for n in self._where if n.startswith(p)}


# -- tokenizer -----------------------------------------------------------------

def encode_prompt(text: str, vocab: Dict[str, int]) -> List[int]:
    """The served tokenizer's rule for a prompt: lower-case, split on white
    space and punctuation, greedy longest-match word pieces, no special
    token added."""
    words, cur = [], []
    for ch in text.lower():
        if ch.isspace() or not ch.isalnum():
            if cur:
                words.append("".join(cur))
                cur = []
            if not ch.isspace():
                words.append(ch)
        else:
            cur.append(ch)
    if cur:
        words.append("".join(cur))
    ids: List[int] = []
    for w in words:
        start, sub = 0, []
        while start < len(w):
            end = len(w)
            while end > start and ("##" if start else "") + w[start:end] not in vocab:
                end -= 1
            if end == start:
                sub = [vocab["[UNK]"]]
                break
            sub.append(vocab[("##" if start else "") + w[start:end]])
            start = end
        ids += sub
    return ids


# -- arithmetic ----------------------------------------------------------------

def _mm(eq: str, a, b, precision: str):
    if precision == "fp8":
        return _round_fp8(jnp.einsum(eq, _round_fp8(a), _round_fp8(b)))
    if precision == "bf16":
        r = lambda x: x.astype(jnp.bfloat16).astype(jnp.float32)
        return r(jnp.einsum(eq, r(a), r(b)))
    return jnp.einsum(eq, a, b)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, pos, theta):
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = pos.astype(jnp.float32)[:, None, None] * inv
    cos = jnp.concatenate([jnp.cos(ang)] * 2, axis=-1)
    sin = jnp.concatenate([jnp.sin(ang)] * 2, axis=-1)
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], axis=-1) * sin


@partial(jax.jit, static_argnames=("hq", "hkv", "d", "eps", "theta", "ret_eps",
                                   "precision"))
def layer_forward(w: Dict[str, jax.Array], x, pos, visible_from, *, hq: int,
                  hkv: int, d: int, eps: float, theta: float, ret_eps: float,
                  precision: str):
    """One block over one row's whole sequence ``x (L, H)``; position ``t``
    sums over ``visible_from[t] <= s <= t`` (all zeros: the layer)."""
    L = x.shape[0]
    mm = partial(_mm, precision=precision)
    a = _rms(x, w["input_layernorm"], eps)
    q = mm("li,oi->lo", a, w["self_attn.q_proj"]).reshape(L, hq, d)
    k = mm("li,oi->lo", a, w["self_attn.k_proj"]).reshape(L, hkv, d)
    v = mm("li,oi->lo", a, w["self_attn.v_proj"]).reshape(L, hkv, d)
    log_g = jax.nn.log_sigmoid(mm("li,oi->lo", a, w["self_attn.g_proj"])
                               + w["self_attn.g_proj.bias"])
    q = _rope(_rms(q, w["self_attn.q_norm"], eps), pos, theta)
    k = _rope(_rms(k, w["self_attn.k_norm"], eps), pos, theta)
    s = mm("tjgd,sjd->jgts", q.reshape(L, hkv, hq // hkv, d), k) / math.sqrt(d)
    cum = jnp.cumsum(log_g, axis=0).T                             # (Hkv, L)
    seen = jnp.tril(jnp.ones((L, L), bool)) \
        & (jnp.arange(L)[None, :] >= visible_from[:, None])
    G = jnp.where(seen,
                  jnp.exp(jnp.minimum(cum[:, :, None] - cum[:, None, :], 0.0)),
                  0.0)
    weight = s * s * G[:, None]                                   # (Hkv,G,L,L)
    num = mm("jgts,sjd->tjgd", weight, v)
    den = weight.sum(-1).transpose(2, 0, 1)
    o = (num / (den[..., None] + ret_eps)).reshape(L, hq * d)
    x = x + mm("li,oi->lo", o, w["self_attn.o_proj"])
    n = _rms(x, w["post_attention_layernorm"], eps)
    hidden = jax.nn.silu(mm("li,oi->lo", n, w["mlp.gate_proj"])) \
        * mm("li,oi->lo", n, w["mlp.up_proj"])
    return x + mm("li,oi->lo", hidden, w["mlp.down_proj"])


def logits_at(ckpt: Checkpoint, cfg: Dict, rows: Sequence[Tuple[np.ndarray,
              np.ndarray, np.ndarray]], precision: str = "f32",
              visible_from: Sequence[np.ndarray] = None) -> List[np.ndarray]:
    """For each row ``(ids, positions, out)``: the logits ``(len(out), V)``
    at the sequence indices ``out`` of the full forward pass over ``ids``
    placed at rotary ``positions``. Layers outermost, so that each layer's
    weights are read once for all rows. ``visible_from``: per row, the first
    position each position still sees (the lost-state control); left out,
    every position sees all before it."""
    if precision not in PRECISIONS:
        raise ValueError(f"no precision {precision!r}")
    kw = dict(hq=cfg["num_attention_heads"], hkv=cfg["num_key_value_heads"],
              d=cfg["head_dim"], eps=cfg["rms_norm_eps"],
              theta=float(cfg["rope_theta"]),
              ret_eps=cfg["assumed"]["retention"]["normaliser_eps"],
              precision=precision)
    with jax.default_matmul_precision("highest"):
        embed = ckpt.host("model.embed_tokens.weight")
        xs = [jnp.asarray(embed[np.asarray(ids)]).astype(jnp.float32)
              for ids, _, _ in rows]
        if visible_from is None:
            visible_from = [np.zeros(len(ids), np.int32) for ids, _, _ in rows]
        for i in range(cfg["num_hidden_layers"]):
            w = ckpt.layer(i)
            xs = [layer_forward(w, x, jnp.asarray(pos), jnp.asarray(vis), **kw)
                  for x, (_, pos, _), vis in zip(xs, rows, visible_from)]
            del w
        norm = ckpt.f32("model.norm.weight")
        hs = [_rms(x[jnp.asarray(at)], norm, cfg["rms_norm_eps"])
              for x, (_, _, at) in zip(xs, rows)]
        del xs
        # the head in blocks of the vocabulary: whole, in float32 and with a
        # rounded copy beside it, it does not fit (a rounded block takes its
        # own scale)
        head = ckpt.host("lm_head.weight")
        out = [[] for _ in rows]
        for lo in range(0, head.shape[0], HEAD_BLOCK):
            block = jnp.asarray(head[lo:lo + HEAD_BLOCK]).astype(jnp.float32)
            for r, h in enumerate(hs):
                out[r].append(np.asarray(_mm("li,oi->lo", h, block, precision)))
    return [np.concatenate(parts, axis=1) for parts in out]
