"""Plain reference for the ``bailing_hybrid`` cells (Ling-3.0-flash): the
pre-norm decoder block with KDA and latent-attention layers and an expert
layer, in straightforward ``jax.numpy`` and float32 with every matrix product
at ``highest`` precision. No cache, no state carried between calls, no chunks,
no batching, no grouped products: one row at a time, the whole sequence at
once, layer by layer with that layer's weights read from the checkpoint file.
It imports nothing of ``alink_tpu``; the checkpoint reader, the tokenizer's
rule and the rounded products are ``reference/brumby.py``'s.

Every block is ``x + mixer(RMSNorm(x))`` then ``x + ffn(RMSNorm(x))``. Layer
``i`` is latent attention where ``(i + 1) % layer_group_size == 0`` and KDA
elsewhere; layers before ``first_k_dense_replace`` have the dense SwiGLU, the
rest the expert layer (ISSUE 32 has the same equations).

KDA (Kimi Linear, arXiv:2510.26692), ``a = RMSNorm(x)``, per head, d_k = d_v:
``q~, k~, v~ = silu(conv(W a))``, a causal depthwise convolution of
``short_conv_kernel_size`` taps; ``q = q~ / |q~| / sqrt(d)``, ``k = k~ / |k~|``;
``g_t = kda_lower_bound * sigmoid(exp(A_log) * (W_f a + dt_bias))``;
``b_t = sigmoid(W_b a)``; the recurrence, one position at a time::

    S_t = (I - b_t k_t k_t^T) Diag(exp g_t) S_{t-1} + b_t k_t v_t^T     o_t = S_t^T q_t

``y = W_o (RMSNorm_head(o_t) * sigmoid(W_g a))``.

Latent attention (DeepSeek-V2, arXiv:2405.04434 section 2.1), expanded:
``q = W_q a`` split per head into ``qk_nope_head_dim`` + ``qk_rope_head_dim``;
``[c, k_r] = W_kva a``, ``c = RMSNorm(c)``; rotary positions (interleaved
pairs) on ``q_r`` and ``k_r``, ``k_r`` shared by the heads; ``[k_n, v] = W_kvb
c``; causal softmax of ``(q_n.k_n + q_r.k_r) / sqrt(d_n + d_r)``; ``o_h *=
sigmoid(w_gate,h . a)``; ``y = W_o o``.

Expert layer (DeepSeek-V3, arXiv:2412.19437 section 2.1.2), ``n =
RMSNorm(x)``: ``s = sigmoid(W_r n)``; choice by ``s + b``: ``n_group`` groups,
a group's score the sum of its two largest, the best ``topk_group`` groups,
the best ``num_experts_per_tok`` among them; weights ``s_i / sum(s chosen) *
routed_scaling_factor``; ``y = sum_i w_i E_i(n) + E_shared(n)`` over the
experts ``deployment.experts_held`` alone: a loop over them, each applied to
every position and weighted by what the router gave it there (0 where it was
not chosen). What the experts held elsewhere would add is left out, as the
program leaves it out.

Controls: ``precision="fp8"`` (every product's operands and result in
float8, the router's, the recurrence's reads and the attention's among them);
``visible_from`` (a position sees back to ``visible_from[t]`` only: the KDA
state is emptied there, the convolution reads zeros before it, attention
masks what lies before it: a program that loses what it carries between
prompt chunks); ``grouped=False`` (the best 8 of all experts, no group limit).
"""

from __future__ import annotations

import math
from functools import partial
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.brumby import (HEAD_BLOCK, PRECISIONS, Checkpoint,
                                        _mm, _rms, encode_prompt)

__all__ = ["Checkpoint", "encode_prompt", "PRECISIONS", "logits_at",
           "layer_kinds"]


def layer_kinds(cfg: Dict) -> List[Tuple[str, str]]:
    """(mixer, ffn) of every layer: mla where ``(i + 1) % layer_group_size
    == 0``, kda elsewhere; dense before ``first_k_dense_replace``."""
    return [("mla" if (i + 1) % cfg["layer_group_size"] == 0 else "kda",
             "dense" if i < cfg["first_k_dense_replace"] else "experts")
            for i in range(cfg["num_hidden_layers"])]


def _rope_pairs(x, pos, theta):
    """Rotary positions on the pairs ``(x[2i], x[2i+1])``; x ``(L, ..., D)``."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = pos.astype(jnp.float32).reshape((-1,) + (1,) * (x.ndim - 1)) * inv
    a, b = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([a * jnp.cos(ang) - b * jnp.sin(ang),
                     a * jnp.sin(ang) + b * jnp.cos(ang)], axis=-1)
    return out.reshape(x.shape)


def _conv(x, w, visible_from):
    """``y_t = sum_j w[:, K-1-j] x_{t-j}`` over the taps that lie at or after
    ``visible_from[t]``; x ``(L, C)``, w ``(C, 1, K)``."""
    L, K = x.shape[0], w.shape[-1]
    t = jnp.arange(L)
    y = jnp.zeros_like(x)
    for j in range(K):
        src = t - j
        seen = (src >= 0) & (src >= visible_from)
        y = y + jnp.where(seen[:, None], x[jnp.maximum(src, 0)], 0.0) \
            * w[:, 0, K - 1 - j]
    return y


def _kda(w, a, visible_from, c, mm):
    L = a.shape[0]
    H, d = c["num_attention_heads"], c["head_dim"]
    lin = lambda name: mm("li,oi->lo", a, w[f"self_attn.{name}"])
    q, k, v = (jax.nn.silu(_conv(lin(p + "_proj"),
                                 w[f"self_attn.{p}_conv1d"], visible_from)
                           ).reshape(L, H, d) for p in "qkv")
    unit = lambda x: x * jax.lax.rsqrt((x * x).sum(-1, keepdims=True) + 1e-6)
    q, k = unit(q) / math.sqrt(d), unit(k)
    g = c["kda_lower_bound"] * jax.nn.sigmoid(
        jnp.exp(w["self_attn.A_log"])[:, None]
        * (lin("f_proj").reshape(L, H, d) + w["self_attn.dt_bias"].reshape(H, d)))
    beta = jax.nn.sigmoid(lin("b_proj"))                              # (L,H)
    empty = jnp.concatenate([jnp.zeros((1,), bool),
                             visible_from[1:] != visible_from[:-1]])

    def step(S, at):
        qt, kt, vt, gt, bt, drop = at
        S = jnp.where(drop, 0.0, S) * jnp.exp(gt)[..., None]          # (H,dk,dv)
        u = (vt - mm("hkv,hk->hv", S, kt)) * bt[:, None]
        S = S + kt[..., None] * u[:, None, :]
        return S, mm("hkv,hk->hv", S, qt)

    _, o = jax.lax.scan(step, jnp.zeros((H, d, d), jnp.float32),
                        (q, k, v, g, beta, empty))
    o = _rms(o, w["self_attn.o_norm"], c["rms_norm_eps"]) \
        * jax.nn.sigmoid(lin("g_proj").reshape(L, H, d))
    return mm("li,oi->lo", o.reshape(L, H * d), w["self_attn.o_proj"])


def _mla(w, a, pos, visible_from, c, mm):
    L = a.shape[0]
    H, r = c["num_attention_heads"], c["kv_lora_rank"]
    dn, dr, dv = c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"]
    theta = float(c["rope_theta"])
    lin = lambda name, x=a: mm("li,oi->lo", x, w[f"self_attn.{name}"])
    q = lin("q_proj").reshape(L, H, dn + dr)
    q_n, q_r = q[..., :dn], _rope_pairs(q[..., dn:], pos, theta)
    kv = lin("kv_a_proj_with_mqa")
    lat = _rms(kv[:, :r], w["self_attn.kv_a_layernorm"], c["rms_norm_eps"])
    k_r = _rope_pairs(kv[:, r:], pos, theta)                          # (L,dr)
    kv_b = lin("kv_b_proj", lat).reshape(L, H, dn + dv)
    k_n, v = kv_b[..., :dn], kv_b[..., dn:]
    s = (mm("thd,shd->hts", q_n, k_n) + mm("thd,sd->hts", q_r, k_r)) \
        / math.sqrt(dn + dr)
    t = jnp.arange(L)
    seen = (t[None, :] <= t[:, None]) & (t[None, :] >= visible_from[:, None])
    p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
    o = mm("hts,shd->thd", p, v) * jax.nn.sigmoid(lin("g_proj"))[..., None]
    return mm("li,oi->lo", o.reshape(L, H * dv), w["self_attn.o_proj"])


def _swiglu(n, gate, up, down, mm):
    return mm("li,oi->lo", jax.nn.silu(mm("li,oi->lo", n, gate))
              * mm("li,oi->lo", n, up), down)


def _choose(s, bias, c, grouped: bool):
    """The chosen experts ``(L, k)`` by ``s + bias``."""
    L, E = s.shape
    k = c["num_experts_per_tok"]
    choice = s + bias
    if grouped:
        G, keep = c["n_group"], c["topk_group"]
        by_group = choice.reshape(L, G, E // G)
        score = jnp.sort(by_group, axis=-1)[..., -2:].sum(-1)        # (L,G)
        rank = jnp.argsort(jnp.argsort(-score, axis=-1), axis=-1)
        choice = jnp.where((rank < keep)[..., None], by_group,
                           -jnp.inf).reshape(L, E)
    return jnp.argsort(-choice, axis=-1)[:, :k]


def _experts(w, n, c, mm, grouped: bool):
    lo, hi = c["experts_held"]
    s = jax.nn.sigmoid(mm("li,oi->lo", n, w["mlp.gate"]))
    idx = _choose(s, w["mlp.gate.expert_bias"], c, grouped)           # (L,k)
    chosen = jnp.take_along_axis(s, idx, axis=-1)
    weight = chosen / (chosen.sum(-1, keepdims=True) + 1e-20) \
        * c["routed_scaling_factor"]

    def one(y, held):
        e, gate, up, down = held
        mine = jnp.where(idx == e, weight, 0.0).sum(-1)               # (L,)
        return y + mine[:, None] * _swiglu(n, gate, up, down, mm), None

    stack = lambda name: jnp.stack([w[f"mlp.experts.{e}.{name}"]
                                    for e in range(lo, hi)])
    y, _ = jax.lax.scan(one, jnp.zeros_like(n), (
        jnp.arange(lo, hi), stack("gate_proj"), stack("up_proj"),
        stack("down_proj")))
    shared = _swiglu(n, w["mlp.shared_experts.gate_proj"],
                     w["mlp.shared_experts.up_proj"],
                     w["mlp.shared_experts.down_proj"], mm)
    return y + shared, idx


@partial(jax.jit, static_argnames=("mixer", "ffn", "spec", "precision", "grouped"))
def layer_forward(w: Dict[str, jax.Array], x, pos, visible_from, *, mixer: str,
                  ffn: str, spec: Tuple, precision: str, grouped: bool = True):
    """One block over one row's whole sequence ``x (L, H)``; position ``t``
    sees ``visible_from[t] <= s <= t`` (all zeros: the layer). Returns the
    block's output and, of an expert layer, the chosen experts ``(L, k)``."""
    c = dict(spec)
    mm = partial(_mm, precision=precision)
    a = _rms(x, w["input_layernorm"], c["rms_norm_eps"])
    x = x + (_kda(w, a, visible_from, c, mm) if mixer == "kda"
             else _mla(w, a, pos, visible_from, c, mm))
    n = _rms(x, w["post_attention_layernorm"], c["rms_norm_eps"])
    if ffn == "dense":
        return x + _swiglu(n, w["mlp.gate_proj"], w["mlp.up_proj"],
                           w["mlp.down_proj"], mm), None
    y, idx = _experts(w, n, c, mm, grouped)
    return x + y, idx


def _spec(cfg: Dict) -> Tuple:
    keys = ("num_attention_heads", "head_dim", "rms_norm_eps", "rope_theta",
            "kda_lower_bound", "kv_lora_rank", "qk_nope_head_dim",
            "qk_rope_head_dim", "v_head_dim", "num_experts_per_tok", "n_group",
            "topk_group", "routed_scaling_factor")
    return tuple((k, cfg[k]) for k in keys) + (
        ("experts_held", tuple(cfg["deployment"]["experts_held"])),)


def logits_at(ckpt: Checkpoint, cfg: Dict, rows: Sequence[Tuple[np.ndarray,
              np.ndarray, np.ndarray]], precision: str = "f32",
              visible_from: Sequence[np.ndarray] = None, grouped: bool = True):
    """For each row ``(ids, positions, out)``: the logits ``(len(out), V)``
    at the sequence indices ``out`` of the full forward pass over ``ids``
    placed at rotary ``positions``; and, per row, the experts each position
    chose in each expert layer, ``(expert layers, L, k)``. Layers outermost,
    so that each layer's weights are read once for all rows."""
    if precision not in PRECISIONS:
        raise ValueError(f"no precision {precision!r}")
    spec = _spec(cfg)
    with jax.default_matmul_precision("highest"):
        embed = ckpt.host("model.embed_tokens.weight")
        xs = [jnp.asarray(embed[np.asarray(ids)]).astype(jnp.float32)
              for ids, _, _ in rows]
        if visible_from is None:
            visible_from = [np.zeros(len(ids), np.int32) for ids, _, _ in rows]
        chosen: List[List[np.ndarray]] = [[] for _ in rows]
        for i, (mixer, ffn) in enumerate(layer_kinds(cfg)):
            w = ckpt.layer(i)
            for r, ((_, pos, _), vis) in enumerate(zip(rows, visible_from)):
                xs[r], idx = layer_forward(
                    w, xs[r], jnp.asarray(pos), jnp.asarray(vis), mixer=mixer,
                    ffn=ffn, spec=spec, precision=precision, grouped=grouped)
                if idx is not None:
                    chosen[r].append(np.asarray(idx))
            del w
        norm = ckpt.f32("model.norm.weight")
        hs = [_rms(x[jnp.asarray(at)], norm, cfg["rms_norm_eps"])
              for x, (_, _, at) in zip(xs, rows)]
        del xs
        head = ckpt.host("lm_head.weight")
        out = [[] for _ in rows]
        for lo in range(0, head.shape[0], HEAD_BLOCK):
            block = jnp.asarray(head[lo:lo + HEAD_BLOCK]).astype(jnp.float32)
            for r, h in enumerate(hs):
                out[r].append(np.asarray(_mm("li,oi->lo", h, block, precision)))
    return ([np.concatenate(parts, axis=1) for parts in out],
            [np.stack(c) if c else np.zeros((0, 0, 0), np.int64) for c in chosen])
