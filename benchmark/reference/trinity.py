"""Plain reference for the ``afmoe`` cells (Trinity-Large-Preview): the block
with gated grouped-query attention over a sliding window or over every
earlier position, four norms a layer and an expert layer beside a shared
expert, in straightforward ``jax.numpy`` and float32 with every matrix
product at ``highest`` precision. No cache, no ring, no kernel, no chunks, no
batching, no grouped products: one row at a time, the whole sequence at
once, layer by layer with that layer's weights read from the checkpoint file
and widened to float32 there. The window is an explicit mask over whole
sequences, and the attention is computed a block of queries at a time
against every key of the row, so that the scores of a 16k-token row fit. It
imports nothing of ``alink_tpu``; the checkpoint reader, the tokenizer's
rule, half-split rotary and the rounded products are
``reference/brumby.py``'s.

The block (every weight ``(out, in)``,
``RMS`` an RMS norm with eps ``rms_norm_eps`` and a scale a channel):

- ``x0 = E[tok] sqrt(hidden_size)`` (``mup_enabled``).
- ``a = RMS_in(x)``; ``q = RMS_q(a W_q)``, ``k = RMS_k(a W_k)`` (a norm over
  each head's 128 channels, 48 query heads, 8 key/value heads, 6 query heads
  sharing one, here by repeating them), ``v = a W_v``; rotary (base
  ``rope_theta``, half-split) on q and k in ``sliding_attention`` layers
  alone: ``full_attention`` layers carry no positions; ``o = softmax(q.k /
  sqrt(128))`` over ``s <= t`` (full) or ``t - sliding_window < s <= t``
  (sliding), no sink; ``y = W_o (o * sigmoid(a W_g))``, the gate on each of
  the 48 x 128 channels; ``x <- x + RMS_post_attn(y)``.
- ``n = RMS_pre_mlp(x)``; the first ``num_dense_layers`` layers ``f =
  SwiGLU(n)`` (width ``intermediate_size``), the others ``s = sigmoid(W_r
  n)`` over all 256 router outputs, the best ``num_experts_per_tok`` by ``s
  + b`` (``b`` the expert bias, for the choice only), weights ``route_scale
  s_i / (sum(s chosen) + 1e-20)``, ``f = sum_i w_i SwiGLU_i(n) +
  SwiGLU_shared(n)``; ``x <- x + RMS_post_mlp(f)``.
- ``logits = W_head RMS_final(x)``, untied.

Departures from the published model, as the program makes them: the
experts are the configuration's ``deployment.experts_held`` alone (one
chip's share): the router routes over all 256 outputs and what the experts
held elsewhere would add is left out, here as in the program. A held expert
is applied to the positions whose choice holds it (gathered, up to a
capacity the row's own choices set, and added back), not to every position
weighted by zero. The embedding is scaled in float32, not in the
checkpoint's bfloat16.

Controls: ``precision="fp8"`` (every product's operands and result in
float8); ``visible_from`` (a position sees back to ``visible_from[t]`` only:
every cache emptied at a prompt chunk's start); ``window_as_full`` (the
sliding layers attend every earlier position); ``gate_dropped`` (no output
gate); ``rope_on_full`` (rotary in the full layers too);
``post_norms_dropped`` (the two post-norms left out: the sub-layers' outputs
join the residual stream as they are).
"""

from __future__ import annotations

import math
from functools import partial
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.brumby import (HEAD_BLOCK, PRECISIONS, Checkpoint,
                                        _mm, _rms, _rope, encode_prompt)

__all__ = ["Checkpoint", "encode_prompt", "PRECISIONS", "FAULTS", "logits_at",
           "layer_kinds"]

# the controls :func:`logits_at` takes as flags
FAULTS = ("window_as_full", "gate_dropped", "rope_on_full",
          "post_norms_dropped")
# queries a block of the attention: 48 heads x 128 x 16,511 keys of float32
# scores are 0.41 GB, and the softmax holds a few such
QUERY_BLOCK = 128


def layer_kinds(cfg: Dict) -> List[Tuple[str, str]]:
    """(mixer, ffn) of every kept layer: ``window`` where the published
    ``layer_types`` entry is ``sliding_attention``, ``attention`` where it is
    ``full_attention``; ``dense`` for the first ``num_dense_layers``,
    ``experts`` after."""
    n, dense = cfg["num_hidden_layers"], cfg["num_dense_layers"]
    mixer = {"sliding_attention": "window", "full_attention": "attention"}
    return [(mixer[t], "dense" if i < dense else "experts")
            for i, t in enumerate(cfg["layer_types"][:n])]


def _attention(w, a, pos, visible_from, window, gate_on, rope_on, c, mm):
    L = a.shape[0]
    hq, hkv, d = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    eps = c["rms_norm_eps"]
    lin = lambda name: mm("li,oi->lo", a, w[f"self_attn.{name}"])
    q = _rms(lin("q_proj").reshape(L, hq, d), w["self_attn.q_norm"], eps)
    k = _rms(lin("k_proj").reshape(L, hkv, d), w["self_attn.k_norm"], eps)
    theta = float(c["rope_theta"])
    q = jnp.where(rope_on, _rope(q, pos, theta), q)
    k = jnp.repeat(jnp.where(rope_on, _rope(k, pos, theta), k), hq // hkv, axis=1)
    v = jnp.repeat(lin("v_proj").reshape(L, hkv, d), hq // hkv, axis=1)
    pad = (-L) % QUERY_BLOCK
    blocks = lambda x: jnp.pad(x, [(0, pad)] + [(0, 0)] * (x.ndim - 1)).reshape(
        (L + pad) // QUERY_BLOCK, QUERY_BLOCK, *x.shape[1:])
    j = jnp.arange(L)

    def one(args):
        qb, t, vis = args
        s = mm("thd,shd->hts", qb, k) / math.sqrt(d)             # (H,Q,L)
        seen = ((j[None] <= t[:, None]) & (j[None] >= vis[:, None])
                & (t[:, None] - j[None] < window))
        p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
        return mm("hts,shd->thd", p, v)

    o = jax.lax.map(one, (blocks(q), blocks(jnp.arange(L)), blocks(visible_from)))
    o = o.reshape(L + pad, hq * d)[:L]
    gate = jax.nn.sigmoid(lin("g_proj"))
    o = jnp.where(gate_on, o * gate, o)
    return mm("li,oi->lo", o, w["self_attn.o_proj"])


def _swiglu(n, gate, up, down, mm):
    return mm("li,oi->lo", jax.nn.silu(mm("li,oi->lo", n, gate))
              * mm("li,oi->lo", n, up), down)


def _joined(x, y, scale, post_on, eps):
    return x + jnp.where(post_on, _rms(y, scale, eps), y)


@partial(jax.jit, static_argnames=("spec", "precision"))
def mixer_forward(w: Dict[str, jax.Array], x, pos, visible_from, window,
                  gate_on, rope_on, post_on, *, spec: Tuple, precision: str):
    """The attention half of a block over one row's whole sequence ``x (L,
    H)``: the residual after it, and the FFN's normed input. Position ``t``
    sees ``visible_from[t] <= s <= t`` within ``window`` positions; the gate,
    rotary and the post-norm where their flags say (all traced, so that the
    controls share the program)."""
    c = dict(spec)
    mm = partial(_mm, precision=precision)
    eps = c["rms_norm_eps"]
    a = _rms(x, w["input_layernorm"], eps)
    y = _attention(w, a, pos, visible_from, window, gate_on, rope_on, c, mm)
    x = _joined(x, y, w["post_attention_layernorm"], post_on, eps)
    return x, _rms(x, w["pre_mlp_layernorm"], eps)


@partial(jax.jit, static_argnames=("spec", "precision"))
def dense_forward(w: Dict[str, jax.Array], x, n, post_on, *, spec: Tuple,
                  precision: str):
    c = dict(spec)
    mm = partial(_mm, precision=precision)
    f = _swiglu(n, w["mlp.gate_proj"], w["mlp.up_proj"], w["mlp.down_proj"], mm)
    return _joined(x, f, w["post_mlp_layernorm"], post_on, c["rms_norm_eps"])


@partial(jax.jit, static_argnames=("spec", "precision"))
def route(w: Dict[str, jax.Array], n, *, spec: Tuple, precision: str):
    """The router over every position: the chosen experts ``(L, k)``, their
    weights, and the tie ``(L,)``: how far the last chosen expert's score
    ``s + b`` lies above the best one left out (a choice that rounding can
    flip where it is small)."""
    c = dict(spec)
    k = c["num_experts_per_tok"]
    s = jax.nn.sigmoid(_mm("li,oi->lo", n, w["mlp.gate"], precision))
    score = s + w["mlp.gate.expert_bias"]
    order = jnp.argsort(-score, axis=-1)
    idx = order[:, :k]
    edge = jnp.take_along_axis(score, order[:, k - 1:k + 1], axis=-1)
    chosen = jnp.take_along_axis(s, idx, axis=-1)
    return idx, c["route_scale"] * chosen / (chosen.sum(-1, keepdims=True)
                                             + 1e-20), edge[:, 0] - edge[:, 1]


@partial(jax.jit, static_argnames=("spec", "precision", "capacity"))
def experts_forward(w: Dict[str, jax.Array], x, n, idx, weight, post_on, *,
                    spec: Tuple, precision: str, capacity: int):
    """The held experts and the shared expert over one row, the post-norm,
    and the residual. Each held expert takes the positions whose choice
    holds it (``capacity``: at least the most any held expert got), and its
    weighted output is added back at them."""
    c = dict(spec)
    mm = partial(_mm, precision=precision)
    lo, hi = c["experts_held"]
    L = n.shape[0]

    def one(y, held):
        e, gate, up, down = held
        mine = jnp.where(idx == e, weight, 0.0).sum(-1)                # (L,)
        at = jnp.nonzero((idx == e).any(-1), size=capacity, fill_value=L)[0]
        rows = jnp.take(n, at, axis=0, mode="fill", fill_value=0.0)
        out = _swiglu(rows, gate, up, down, mm) * jnp.take(
            mine, at, mode="fill", fill_value=0.0)[:, None]
        return y.at[at].add(out, mode="drop"), None

    stack = lambda name: jnp.stack([w[f"mlp.experts.{e}.{name}"]
                                    for e in range(lo, hi)])
    f, _ = jax.lax.scan(one, jnp.zeros_like(n), (
        jnp.arange(lo, hi), stack("gate_proj"), stack("up_proj"),
        stack("down_proj")))
    f = f + _swiglu(n, w["mlp.shared_experts.gate_proj"],
                    w["mlp.shared_experts.up_proj"],
                    w["mlp.shared_experts.down_proj"], mm)
    return _joined(x, f, w["post_mlp_layernorm"], post_on, c["rms_norm_eps"])


def _spec(cfg: Dict) -> Tuple:
    keys = ("num_attention_heads", "num_key_value_heads", "head_dim",
            "rms_norm_eps", "rope_theta", "num_experts_per_tok", "route_scale")
    return tuple((k, cfg[k]) for k in keys) + (
        ("experts_held", tuple(cfg["deployment"]["experts_held"])),)


def _capacity(idx: np.ndarray, lo: int, hi: int) -> int:
    """The most positions any held expert got, rounded up to a power of two
    (so that few programs are compiled)."""
    got = (idx[None] == np.arange(lo, hi)[:, None, None]).any(-1).sum(-1)
    return 1 << (max(int(got.max()), 1) - 1).bit_length()


def logits_at(ckpt: Checkpoint, cfg: Dict, rows: Sequence[Tuple[np.ndarray,
              np.ndarray, np.ndarray]], precision: str = "f32",
              visible_from: Sequence[np.ndarray] = None,
              window_as_full: bool = False, gate_dropped: bool = False,
              rope_on_full: bool = False, post_norms_dropped: bool = False):
    """For each row ``(ids, positions, out)``: the logits ``(len(out), V)``
    at the sequence indices ``out`` of the full forward pass over ``ids``
    placed at rotary ``positions``; per row, the experts each position
    chose in each expert layer, ``(expert layers, L, k)``; and per row each
    position's routing tie (:func:`route`) in each expert layer,
    ``(expert layers, L)``. Layers outermost, so that each layer's weights
    are read once for all rows."""
    if precision not in PRECISIONS:
        raise ValueError(f"no precision {precision!r}")
    spec = _spec(cfg)
    lo, hi = cfg["deployment"]["experts_held"]
    post_on = jnp.bool_(not post_norms_dropped)
    gate_on = jnp.bool_(not gate_dropped)
    with jax.default_matmul_precision("highest"):
        embed = ckpt.host("model.embed_tokens.weight")
        scale = math.sqrt(cfg["hidden_size"])
        xs = [jnp.asarray(embed[np.asarray(ids)]).astype(jnp.float32) * scale
              for ids, _, _ in rows]
        if visible_from is None:
            visible_from = [np.zeros(len(ids), np.int32) for ids, _, _ in rows]
        chosen: List[List[np.ndarray]] = [[] for _ in rows]
        ties: List[List[np.ndarray]] = [[] for _ in rows]
        for i, (mixer, ffn) in enumerate(layer_kinds(cfg)):
            w = ckpt.layer(i)
            sliding = mixer == "window"
            for r, ((ids, pos, _), vis) in enumerate(zip(rows, visible_from)):
                window = (cfg["sliding_window"] if sliding and not window_as_full
                          else len(ids))
                x, n = mixer_forward(
                    w, xs[r], jnp.asarray(pos), jnp.asarray(vis),
                    jnp.int32(window), gate_on, jnp.bool_(sliding or rope_on_full),
                    post_on, spec=spec, precision=precision)
                if ffn == "dense":
                    xs[r] = dense_forward(w, x, n, post_on, spec=spec,
                                          precision=precision)
                    continue
                idx, weight, tie = route(w, n, spec=spec, precision=precision)
                idx_host = np.asarray(idx)
                chosen[r].append(idx_host)
                ties[r].append(np.asarray(tie))
                xs[r] = experts_forward(
                    w, x, n, idx, weight, post_on, spec=spec,
                    precision=precision, capacity=_capacity(idx_host, lo, hi))
            del w
        norm = ckpt.f32("model.norm.weight")
        hs = [_rms(x[jnp.asarray(at)], norm, cfg["rms_norm_eps"])
              for x, (_, _, at) in zip(xs, rows)]
        del xs
        head = ckpt.host("lm_head.weight")
        out = [[] for _ in rows]
        for start in range(0, head.shape[0], HEAD_BLOCK):
            block = jnp.asarray(head[start:start + HEAD_BLOCK]).astype(jnp.float32)
            for r, h in enumerate(hs):
                out[r].append(np.asarray(_mm("li,oi->lo", h, block, precision)))
    return ([np.concatenate(parts, axis=1) for parts in out],
            [np.stack(c) if c else np.zeros((0, 0, 0), np.int64) for c in chosen],
            [np.stack(t) if t else np.zeros((0, 0), np.float32) for t in ties])
