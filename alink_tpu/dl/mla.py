"""Multi-head latent attention (MLA): softmax attention whose keys and values
are one compressed vector a position.

The layer (DeepSeek-V2, arXiv:2405.04434 section 2.1) projects a position to
a latent ``c`` (``kv_lora_rank`` values, RMS-normed) and one rotary key
``k_r`` shared by all heads; a head's keys and values are linear in ``c``::

    [k_n, v]_h = W_kvb,h c        score_ts = (q_n,t . k_n,s + q_r,t . k_r,s) * scale

so a sequence's cache is ``[c, k_r]`` alone, 576 values a position here,
whatever the number of heads, appended after the positions the cache holds
(:func:`cache_write`; what the cache's length does not cover is never read).
:func:`attend` reads it in the absorbed form: ``q_n . (W_kb c) = (W_kb^T q_n)
. c``, so the query is taken into the latent space once, scores and the
weighted sum run over the cache as it lies, and ``W_vb`` brings ``sum_s p_ts
c_s`` out to a head's values. The same function serves a chunk of a prompt
(``T`` queries a row, the chunk's own positions already written) and a decode
step (``T = 1``): nothing is ever expanded to keys and values a head.

Rotary positions here are the interleaved pairs (``rope_interleave``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .retention import einsum_f32

MLA_SCOPE = "mla_core"
# scores of one call of the attention core, float32, above which the rows are
# taken a group at a time (a prompt chunk of 128 rows against 2,176 cached
# positions would hold 2.3 GB of scores at once)
_SCORE_BYTES = 256 << 20


def rope_interleaved(x, pos, theta: float):
    """Rotary positions on pairs ``(x[2i], x[2i+1])``: ``x`` ``(..., D)``
    float32 with ``pos`` shaped as its leading dimensions but the heads'."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = pos.astype(jnp.float32)[..., None] * inv               # (..., D/2)
    while ang.ndim < x.ndim:                                     # the heads
        ang = ang[..., None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    pairs = x.reshape(*x.shape[:-1], d // 2, 2)
    a, b = pairs[..., 0], pairs[..., 1]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     axis=-1).reshape(x.shape)


def cache_write(latent, length, new, valid):
    """``new`` ``(B,T,C)`` appended to each row's cache ``latent``
    ``(B,P,C)`` after the ``length`` ``(B,)`` positions it holds; an invalid
    position (a row's trailing padding) writes nothing. Returns the cache
    and its new lengths."""
    B, T = valid.shape
    at = jnp.where(valid, length[:, None] + jnp.arange(T)[None, :],
                   latent.shape[1])                              # dropped
    rows = jnp.broadcast_to(jnp.arange(B)[:, None], (B, T))
    latent = latent.at[rows, at].set(new.astype(latent.dtype), mode="drop")
    return latent, length + valid.sum(axis=1).astype(length.dtype)


def attend(q_n, q_r, latent, start, w_kb, w_vb, *, scale: float, dtype):
    """Causal attention of ``T`` queries a row over the row's cache.

    q_n ``(B,T,H,Dn)``, q_r ``(B,T,H,Dr)`` (rotated) float32; latent
    ``(B,P,R+Dr)``: the normed latent and the rotated shared key, the
    queries' own positions written; start ``(B,)``: the cache's length
    before them, so query ``t`` sees the cached positions ``<= start + t``;
    w_kb ``(H,Dn,R)``, w_vb ``(H,Dv,R)``: the key and value halves of
    ``W_kvb``. Returns ``(B,T,H,Dv)`` float32."""
    B, T, H, _ = q_n.shape
    P = latent.shape[1]
    R = w_kb.shape[-1]

    def core(args):
        qn, qr, lat, first = args
        with jax.named_scope(MLA_SCOPE):
            q_abs = einsum_f32("bthn,hnr->bthr", qn.astype(dtype),
                               w_kb.astype(dtype))
            q_all = jnp.concatenate([q_abs, qr], axis=-1).astype(dtype)
            s = einsum_f32("bthc,bpc->bhtp", q_all, lat.astype(dtype)) * scale
            last = first[:, None] + jnp.arange(T)[None, :]           # (b,T)
            seen = jnp.arange(P)[None, None, :] <= last[:, :, None]  # (b,T,P)
            s = jnp.where(seen[:, None], s, -jnp.inf)
            w = jax.nn.softmax(s, axis=-1)
            mix = einsum_f32("bhtp,bpr->bthr", w.astype(dtype),
                             lat[..., :R].astype(dtype))
            return einsum_f32("bthr,hvr->bthv", mix.astype(dtype),
                              w_vb.astype(dtype))

    group = max(1, _SCORE_BYTES // (H * T * P * 4))
    if group >= B:
        return core((q_n, q_r, latent, start))
    while B % group:
        group -= 1
    split = lambda x: x.reshape(B // group, group, *x.shape[1:])
    out = jax.lax.map(core, tuple(split(x) for x in (q_n, q_r, latent, start)))
    return out.reshape(B, T, H, -1)
