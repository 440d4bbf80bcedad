"""Sparse experts with group-limited token-choice routing, for a layer that is
told which of the experts it holds.

The layer (DeepSeek-V3, arXiv:2412.19437 section 2.1.2): a token's scores are
``s = sigmoid(W_r n)`` over all ``E`` experts, the product in float32. The
choice is by ``s + b`` (``b`` the expert bias, which steers the load and never
weighs an output): the experts lie in ``n_group`` groups, a group's score is
the sum of its two largest, the best ``topk_group`` groups are kept and the
best ``top_k`` experts among them chosen. Their weights are ``s_i / sum(s
chosen) * routed_scaling_factor``::

    y = sum_i w_i E_i(n) + E_shared(n)        E(n) = W_d (silu(W_g n) * W_u n)

:func:`route` routes over all ``E`` outputs whatever is held. The expert layer
(:func:`routed_experts`) holds the experts ``[lo, hi)`` and adds only their
terms: an assignment to an expert elsewhere is that expert's chip's to
compute, and nothing stands in for it here. No token is dropped: the
assignments held are sorted by expert and go through grouped matrix products
(``jax.lax.ragged_dot``) whose groups are as long as the router made them.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

ROUTE_SCOPE = "moe_route"
EXPERTS_SCOPE = "moe_experts"


def route(logits, bias, *, n_group: int, topk_group: int, top_k: int,
          scale: float):
    """logits ``(N,E)`` float32, bias ``(E,)``. Returns the chosen experts
    ``(N,top_k)`` int32 and their weights ``(N,top_k)`` float32."""
    N, E = logits.shape
    s = jax.nn.sigmoid(logits.astype(jnp.float32))
    choice = (s + bias.astype(jnp.float32)).reshape(N, n_group, E // n_group)
    group_score = jax.lax.top_k(choice, 2)[0].sum(-1)            # (N,n_group)
    kept = jax.lax.top_k(group_score, topk_group)[1]             # (N,topk_group)
    keep = (kept[..., None] == jnp.arange(n_group)).any(axis=1)  # (N,n_group)
    choice = jnp.where(keep[..., None], choice, -jnp.inf).reshape(N, E)
    idx = jax.lax.top_k(choice, top_k)[1]
    w = jnp.take_along_axis(s, idx, axis=-1)
    w = w / (w.sum(-1, keepdims=True) + 1e-20) * scale
    return idx.astype(jnp.int32), w


def held_load(idx, valid, held):
    """Where each assignment goes here: ``local`` ``(B,T,K)``, the expert's
    index among those held or ``hi - lo`` for one held elsewhere or made by
    a padding position; and ``(B,hi-lo)``, each row's count a held expert."""
    lo, hi = held
    here = (idx >= lo) & (idx < hi) & valid[..., None]
    local = jnp.where(here, idx - lo, hi - lo)
    B = idx.shape[0]
    load = (local.reshape(B, -1, 1) == jnp.arange(hi - lo)).sum(axis=1)
    return local, load.astype(jnp.int32)


def routed_experts(n, local, w, sizes, gate_up, down, *, dtype):
    """The held experts' part of the layer's output.

    n ``(N,H)`` float32; local, w ``(N,K)``: each assignment's expert among
    the ``E`` held (``E``: not held) and its weight; sizes ``(E,)`` int32:
    assignments an expert; gate_up ``(E,H,2F)``, down ``(E,F,H)``."""
    N, K = local.shape
    E = gate_up.shape[0]
    F = down.shape[1]
    flat = local.reshape(-1)
    order = jnp.argsort(flat, stable=True)       # held first, by expert
    back = jnp.argsort(order)
    ours = (flat[order] < E)[:, None]
    with jax.named_scope(EXPERTS_SCOPE):
        xs = n.astype(dtype)[order // K]                         # (NK,H)
        if xs.dtype != jnp.float32 and jax.default_backend() == "cpu":
            xs, gate_up, down = (a.astype(jnp.float32)
                                 for a in (xs, gate_up, down))
        dot = lambda a, b, to: jax.lax.ragged_dot(
            a, b, sizes, preferred_element_type=to)
        gu = dot(xs, gate_up, jnp.float32)
        # an assignment's weight goes in before the way down (the product is
        # linear), so that what comes back is summed as it lies, and comes
        # back in the operands' dtype: in float32 the six layers' sorted
        # outputs of a 128-row prompt chunk are 4 GB of live buffers
        hidden = jax.nn.silu(gu[:, :F]) * gu[:, F:] \
            * w.reshape(-1)[order][:, None]
        out = dot(hidden.astype(dtype).astype(xs.dtype), down,
                  xs.dtype).astype(dtype)                        # (NK,H)
        # a row past the last group belongs to no expert held here
        out = jnp.where(ours, out, 0)
        return out[back].reshape(N, K, -1).astype(jnp.float32).sum(axis=1)
