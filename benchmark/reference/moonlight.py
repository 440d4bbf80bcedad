"""Plain reference for the ``deepseek_v3`` training cell (Moonlight-16B-A3B):
the decoder stack, its loss, the gradient, AdamW and the router's bias rule in
straightforward ``jax.numpy`` and float32 with every matrix product at
``highest`` precision, written from the equations of ISSUE 34 and not from
``alink_tpu``, of which it imports nothing. One row at a time, the whole
sequence at once; the gradient is ``jax.grad``'s; the optimizer is numpy's, on
the host.

Every block is ``x + mixer(RMSNorm(x))`` then ``x + ffn(RMSNorm(x))``, the
first ``first_k_dense_replace`` layers with the dense SwiGLU, the rest with
the expert layer; untied embedding and head.

Latent attention (DeepSeek-V2, arXiv:2405.04434 section 2.1), expanded, ``a =
RMSNorm(x)``: ``q_h = W_q,h a``, its last ``qk_rope_head_dim`` values rotated
(interleaved pairs); ``[c', k_r'] = W_kva a``, ``c = RMSNorm(c')``, ``k_r =
rope(k_r')`` shared by the heads; ``[k_n, v]_h = W_kvb,h c``; causal softmax
of ``(q_n.k_n + q_r.k_r) / sqrt(d_n + d_r)``; ``y = W_o concat(o_h)``. The
scores are materialised, ``block`` query positions of all heads at a time,
each block's computed again when the gradient needs them (``jax.checkpoint``
changes what is kept, not what is computed).

Expert layer (DeepSeek-V3, arXiv:2412.19437 sections 2.1.2 and 4.2), ``n =
RMSNorm(x)``: ``s = sigmoid(W_r n)``; the ``num_experts_per_tok`` largest of
``s + b`` are chosen; ``w_i = s_i / sum(s chosen) * routed_scaling_factor``;
``y = sum_{i chosen and held} w_i E_i(n) + E_shared(n)``: a loop over the held
experts, each over every position under a mask. The row's balance term is
``sum_i f_i P_i``, ``f_i = E / (K T) #{t: i chosen at t}``, ``P_i = mean_t s_i,t /
sum_j s_j,t``.

The loss of a step is the mean over its rows of the mean over a row's
positions but the last of the cross-entropy of position ``t``'s logits against
token ``t + 1``, plus ``alpha`` times the mean over rows and expert layers of
the balance term. After the optimizer's step, ``b_i += u sign(mean(c) -
c_i)`` from the step's count ``c_i`` of assignments to each router output.

``precision="fp8"`` is the control: the operands and the result of every
matrix product rounded to float8 (``reference/bert.py``'s, cotangents too).
``fault`` plants what a broken step would do: ``router_grad_dropped`` treats
the chosen experts' weights as constants, ``bias_frozen`` leaves ``b`` as it
was.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.bert import PRECISIONS, _mm, lr_at

__all__ = ["PRECISIONS", "layer_kinds", "spec_of", "row_loss", "follow_steps",
           "logits"]

FAULTS = ("router_grad_dropped", "bias_frozen")
Params = Dict[str, jax.Array]


def layer_kinds(cfg: Dict) -> List[str]:
    """The feed-forward of every layer: dense before
    ``first_k_dense_replace``, experts after."""
    return ["dense" if i < cfg["first_k_dense_replace"] else "experts"
            for i in range(cfg["num_hidden_layers"])]


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope_pairs(x, pos, theta):
    """Rotary positions on the pairs ``(x[2i], x[2i+1])``; x ``(L, ..., D)``."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = pos.astype(jnp.float32).reshape((-1,) + (1,) * (x.ndim - 1)) * inv
    a, b = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([a * jnp.cos(ang) - b * jnp.sin(ang),
                     a * jnp.sin(ang) + b * jnp.cos(ang)], axis=-1)
    return out.reshape(x.shape)


def _attention(w: Params, p: str, a, c: Dict, mm, block: int):
    L = a.shape[0]
    H, r = c["num_attention_heads"], c["kv_lora_rank"]
    dn, dr, dv = c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"]
    theta, pos = float(c["rope_theta"]), jnp.arange(L)
    lin = lambda name, x=a: mm("li,oi->lo", x, w[f"{p}self_attn.{name}.weight"])
    q = lin("q_proj").reshape(L, H, dn + dr)
    q_n, q_r = q[..., :dn], _rope_pairs(q[..., dn:], pos, theta)
    kv = lin("kv_a_proj_with_mqa")
    lat = _rms(kv[:, :r], w[f"{p}self_attn.kv_a_layernorm.weight"],
               c["rms_norm_eps"])
    k_r = _rope_pairs(kv[:, r:], pos, theta)                          # (L,dr)
    kv_b = lin("kv_b_proj", lat).reshape(L, H, dn + dv)
    k_n, v = kv_b[..., :dn], kv_b[..., dn:]
    block = min(block, L)
    pad = (-L) % block

    @jax.checkpoint
    def rows(k_n, k_r, v, part):
        qn, qr, t = part                    # a block of query positions
        s = (mm("thd,shd->hts", qn, k_n) + mm("thd,sd->hts", qr, k_r)) \
            / math.sqrt(dn + dr)
        seen = pos[None, :] <= t[:, None]
        # the scores stand in memory before the softmax reads them: fused
        # with the float8 control's rounding, the softmax's reduction asks
        # the TPU's compiler for more scoped memory than a fusion may have
        s = jax.lax.optimization_barrier(s)
        prob = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return mm("hts,shd->thd", prob, v)

    cut = lambda x: jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1)).reshape(
        (L + pad) // block, block, *x.shape[1:])
    o = jax.lax.map(partial(rows, k_n, k_r, v), (cut(q_n), cut(q_r), cut(pos)))
    o = o.reshape(L + pad, H * dv)[:L]
    return mm("li,oi->lo", o, w[f"{p}self_attn.o_proj.weight"])


def _swiglu(n, gate, up, down, mm):
    return mm("li,oi->lo", jax.nn.silu(mm("li,oi->lo", n, gate))
              * mm("li,oi->lo", n, up), down)


def _experts(w: Params, p: str, n, bias, c: Dict, mm, fault: Optional[str]):
    """The expert layer's output over one row ``n (L,H)``, the row's balance
    term, and the count of assignments to each router output."""
    L = n.shape[0]
    lo, hi = c["experts_held"]
    E, k = c["n_routed_experts"], c["num_experts_per_tok"]
    s = jax.nn.sigmoid(jnp.einsum("li,oi->lo", n, w[f"{p}mlp.gate.weight"],
                                  precision=jax.lax.Precision.HIGHEST))
    idx = jnp.argsort(-jax.lax.stop_gradient(s + bias), axis=-1)[:, :k]
    chosen = jnp.take_along_axis(s, idx, axis=-1)
    weight = chosen / (chosen.sum(-1, keepdims=True) + 1e-20) \
        * c["routed_scaling_factor"]
    if fault == "router_grad_dropped":
        weight = jax.lax.stop_gradient(weight)
    y = jnp.zeros_like(n)
    for e in range(lo, hi):
        mine = jnp.where(idx == e, weight, 0.0).sum(-1)               # (L,)
        q = f"{p}mlp.experts.{e}."
        one = jax.checkpoint(partial(_swiglu, mm=mm))(
            n, w[q + "gate_proj.weight"], w[q + "up_proj.weight"],
            w[q + "down_proj.weight"])
        y = y + mine[:, None] * one
    q = f"{p}mlp.shared_experts."
    y = y + _swiglu(n, w[q + "gate_proj.weight"], w[q + "up_proj.weight"],
                    w[q + "down_proj.weight"], mm)
    counts = (idx[..., None] == jnp.arange(E)).sum(axis=(0, 1))       # (E,)
    f = counts.astype(jnp.float32) * (E / (k * L))
    balance = (f * (s / s.sum(-1, keepdims=True)).mean(axis=0)).sum()
    return y, balance, counts


def spec_of(cfg: Dict) -> Tuple:
    """What the layers read of a configuration file (the router's published
    width and the experts held, where the file states a share) or of a
    checkpoint's ``config.json``, as a hashable."""
    keys = ("num_attention_heads", "rms_norm_eps", "rope_theta", "kv_lora_rank",
            "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
            "num_experts_per_tok", "routed_scaling_factor",
            "first_k_dense_replace", "num_hidden_layers")
    routed = (cfg.get("published") or {}).get("n_routed_experts",
                                              cfg["n_routed_experts"])
    held = cfg.get("experts_held") or (cfg.get("deployment") or {}).get(
        "experts_held") or (0, routed)
    return tuple((k, cfg[k]) for k in keys) + (
        ("n_routed_experts", routed), ("experts_held", tuple(held)))


def _hidden(w: Params, bias, ids, c: Dict, mm, block: int,
            fault: Optional[str]):
    """The residual stream after the last layer for one row ``ids (L,)``, the
    mean over the expert layers of its balance term and each expert layer's
    counts ``(expert layers, E)``."""
    x = w["model.embed_tokens.weight"][ids]
    balances, counts, e = [], [], 0
    for i, ffn in enumerate(layer_kinds(c)):
        p = f"model.layers.{i}."

        @jax.checkpoint
        def layer(w, b, x, p=p, ffn=ffn):
            a = _rms(x, w[p + "input_layernorm.weight"], c["rms_norm_eps"])
            x = x + _attention(w, p, a, c, mm, block)
            n = _rms(x, w[p + "post_attention_layernorm.weight"],
                     c["rms_norm_eps"])
            if ffn == "dense":
                return x + _swiglu(n, w[p + "mlp.gate_proj.weight"],
                                   w[p + "mlp.up_proj.weight"],
                                   w[p + "mlp.down_proj.weight"], mm), None, None
            y, balance, count = _experts(w, p, n, b, c, mm, fault)
            return x + y, balance, count

        mine = {k: v for k, v in w.items() if k.startswith(p)}
        x, balance, count = layer(mine, None if ffn == "dense" else bias[e], x)
        if ffn == "experts":
            balances.append(balance)
            counts.append(count)
            e += 1
    balance = jnp.stack(balances).mean() if balances else jnp.float32(0)
    E = c["n_routed_experts"]
    return x, balance, (jnp.stack(counts) if counts
                        else jnp.zeros((0, E), jnp.int32))


def _logits(w: Params, x, c: Dict, mm):
    n = _rms(x, w["model.norm.weight"], c["rms_norm_eps"])
    return mm("li,oi->lo", n, w["lm_head.weight"])


@partial(jax.jit, static_argnames=("spec", "precision", "block", "fault",
                                   "alpha"))
def row_loss(w: Params, bias, ids, *, spec: Tuple, precision: str = "f32",
             block: int = 512, fault: Optional[str] = None,
             alpha: float = 1e-4):
    """One row's loss (cross-entropy of positions but the last against the
    next token, plus ``alpha`` times its balance term), its cross-entropy
    alone, its balance term, and its counts; with the gradient of the first
    to every tensor."""
    c = dict(spec)
    mm = partial(_mm, precision=precision)

    def loss(w):
        x, balance, counts = _hidden(w, bias, ids, c, mm, block, fault)
        logp = jax.nn.log_softmax(_logits(w, x[:-1], c, mm), axis=-1)
        ce = -jnp.take_along_axis(logp, ids[1:, None], axis=1).mean()
        return ce + alpha * balance, (ce, balance, counts)

    with jax.default_matmul_precision("highest"):
        (total, aux), grad = jax.value_and_grad(loss, has_aux=True)(w)
    return total, aux, grad


@partial(jax.jit, static_argnames=("spec", "block"))
def logits(w: Params, bias, ids, *, spec: Tuple, block: int = 512):
    """The full forward pass of one row: every position's logits."""
    c = dict(spec)
    mm = partial(_mm, precision="f32")
    with jax.default_matmul_precision("highest"):
        x, _, _ = _hidden(w, bias, ids, c, mm, block, None)
        return _logits(w, x, c, mm)


def follow_steps(params: Dict[str, np.ndarray], bias: np.ndarray,
                 batches: Sequence[np.ndarray], cfg: Dict, opt: Dict,
                 total_steps: int, *, precision: str = "f32", block: int = 512,
                 fault: Optional[str] = None, keep_params: bool = False,
                 on_first_grad: Optional[Callable] = None) -> Dict:
    """Follow the job's first ``len(batches)`` optimizer steps from the host
    tensors ``params`` (HF names, float32) and the routers' biases ``bias``
    ``(expert layers, E)``; a batch is ``(rows, T)`` token ids. Returns each
    step's loss, the first gradient, every tensor's change after the last
    step (squared norm), the biases after it and, if asked for, the
    parameters. The parameters live on the
    device, the gradient is summed a row at a time, and AdamW runs in numpy
    on the host, where its two moments stay. ``params`` may be of any float
    dtype (a bfloat16 checkpoint as it lies); ``on_first_grad``, if given, is
    handed the first gradient, which is then not kept (2.7 GB at the cell's
    size, beside the parameters, both moments and the next gradient)."""
    if precision not in PRECISIONS or (fault and fault not in FAULTS):
        raise ValueError(f"precision {precision!r}, fault {fault!r}")
    spec = spec_of(cfg)
    alpha, rate = cfg["assumed"]["balance_alpha"], cfg["assumed"]["bias_update_rate"]
    b1, b2, eps, wd = opt["b1"], opt["b2"], opt["eps"], opt["weight_decay"]
    p = {k: np.array(v, np.float32) for k, v in params.items()}
    m = {k: np.zeros_like(v) for k, v in p.items()}
    v2 = {k: np.zeros_like(v) for k, v in p.items()}
    bias = np.array(bias, np.float32)
    losses, ces, first_grad = [], [], None
    for t, batch in enumerate(batches):
        on_device = {k: jnp.asarray(v) for k, v in p.items()}
        g, loss, ce = None, 0.0, 0.0
        counts = np.zeros(bias.shape, np.int64)
        for row in np.asarray(batch):
            total, (ce_row, _, c_row), g_row = row_loss(
                on_device, jnp.asarray(bias), jnp.asarray(row, jnp.int32),
                spec=spec, precision=precision, block=block, fault=fault,
                alpha=alpha)
            g = g_row if g is None else jax.tree.map(jnp.add, g, g_row)
            loss, ce = loss + float(total), ce + float(ce_row)
            counts += np.asarray(c_row, np.int64)
        rows = len(batch)
        del on_device
        # a tensor at a time, each let go on the device as it arrives: the
        # host never holds the gradient twice
        g = {k: np.asarray(g.pop(k)) / rows for k in list(g)}
        losses.append(loss / rows)
        ces.append(ce / rows)
        if t == 0 and on_first_grad is not None:
            on_first_grad(g)
        elif t == 0:
            first_grad = g
        lr, c1, c2 = lr_at(t, opt, total_steps), 1 - b1 ** (t + 1), 1 - b2 ** (t + 1)
        for k in p:
            m[k] = b1 * m[k] + (1 - b1) * g[k]
            v2[k] = b2 * v2[k] + (1 - b2) * g[k] * g[k]
            p[k] = (p[k] - np.float32(lr) * ((m[k] / c1) / (np.sqrt(v2[k] / c2)
                                                            + eps) + wd * p[k])
                    ).astype(np.float32)
        if fault != "bias_frozen" and bias.size:
            mean = counts.mean(axis=1, keepdims=True)
            bias = (bias + np.float32(rate) * np.sign(mean - counts)
                    ).astype(np.float32)
    delta_sq = {k: float(np.sum(np.square(
        p[k].astype(np.float64) - np.asarray(params[k], np.float32)))) for k in p}
    return {"loss": losses, "ce": ces, "first_grad": first_grad,
            "delta_sq": delta_sq, "bias": bias,
            "params": p if keep_params else None}
