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

Training has no cache and whole sequences, so there the layer runs in its
expanded form: keys ``[k_n, k_r]`` and values a head, and
:func:`causal_core` over them: blocks of queries against blocks of keys with
a running maximum and sum, no block above the diagonal, per row and head only
the output and the log-sum kept, a block's probabilities computed again in the
backward pass. Nothing with two sequence-length dimensions is written. In a
one-chip TPU process, at a length that is a multiple of their block, that is
the two kernels of ``attn_pallas.causal_attention``, which keep a block's
scores in VMEM; everywhere else it is the XLA loops of :func:`_causal` here,
which write a block's scores to memory and are the kernels' reference.

Rotary positions here are the interleaved pairs (``rope_interleave``).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from ..common.metrics import metrics
from ..native.kernels import interpret_mode
from .attn_pallas import causal_attention, use_causal_attention
from .retention import einsum_f32

MLA_SCOPE = "mla_core"
# scores of one call of the attention core, float32, above which the rows are
# taken a group at a time (a prompt chunk of 128 rows against 2,176 cached
# positions would hold 2.3 GB of scores at once)
_SCORE_BYTES = 256 << 20
# positions a block of :func:`causal_core`: the scores of one block pair are
# (rows, heads, block, block) float32, 134 MB at 2 rows of 16 heads
CAUSAL_BLOCK = 1024


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


def causal_core(q, k, v, *, scale: float, dtype):
    """Causal softmax attention of whole sequences, in blocks.

    q, k ``(B,T,H,D)``, v ``(B,T,H,Dv)`` float32 or ``dtype``; position
    ``t`` sees ``s <= t``. Returns ``(B,T,H,Dv)`` float32.

    One algorithm, two programs, chosen by the call's own shapes and the
    kernel's gate (``attn_pallas.use_causal_attention``): in a one-chip TPU
    process, at a length that is a multiple of the kernels' block,
    ``attn_pallas.causal_attention``, one kernel each way with a block's
    scores in VMEM only; everywhere else (the CPU, a mesh, any other length)
    XLA's loops over blocks of ``CAUSAL_BLOCK`` below, for which ``T`` need
    not be a multiple of the block: the padding lies after every real
    position, so no real query sees it. Which one a layer was traced down is
    counted (``attention.causal_fused_traces`` /
    ``attention.causal_block_traces``)."""
    B, T, H, D = q.shape
    fused = use_causal_attention(T, D, v.shape[-1])
    block = min(CAUSAL_BLOCK, T)
    pad = 0 if fused else (-T) % block
    heads_first = lambda x: jnp.pad(
        x.astype(dtype), ((0, 0), (0, pad), (0, 0), (0, 0))).transpose(0, 2, 1, 3)
    q, k, v = heads_first(q), heads_first(k), heads_first(v)
    with jax.named_scope(MLA_SCOPE):
        if fused:
            metrics.incr("attention.causal_fused_traces")
            o = causal_attention(q, k, v, scale=scale,
                                 interpret=interpret_mode())
        else:
            metrics.incr("attention.causal_block_traces")
            o = _causal(q, k, v, float(scale), block)
    return o.transpose(0, 2, 1, 3)[:, :T]


def _block_of(x, i, block):
    return jax.lax.dynamic_slice_in_dim(x, i * block, block, axis=2)


def _scores(q_i, k_j, i, j, scale, block):
    """The masked scores of query block ``i`` against key block ``j``."""
    s = einsum_f32("bhqd,bhkd->bhqk", q_i, k_j) * scale
    at = jnp.arange(block)
    seen = (j * block + at)[None, :] <= (i * block + at)[:, None]
    return jnp.where(seen, s, -jnp.inf)


@partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _causal(q, k, v, scale, block):
    return _causal_fwd(q, k, v, scale, block)[0]


def _causal_fwd(q, k, v, scale, block):
    B, H, T, _ = q.shape
    Dv = v.shape[-1]

    def rows(i):
        q_i = _block_of(q, i, block)

        def keys(j, carry):
            m, l, acc = carry
            s = _scores(q_i, _block_of(k, j, block), i, j, scale, block)
            m_new = jnp.maximum(m, s.max(-1))
            p = jnp.exp(s - m_new[..., None])
            fix = jnp.exp(m - m_new)
            acc = acc * fix[..., None] + einsum_f32(
                "bhqk,bhkd->bhqd", p.astype(v.dtype), _block_of(v, j, block))
            return m_new, l * fix + p.sum(-1), acc

        m, l, acc = jax.lax.fori_loop(0, i + 1, keys, (
            jnp.full((B, H, block), -jnp.inf, jnp.float32),
            jnp.zeros((B, H, block), jnp.float32),
            jnp.zeros((B, H, block, Dv), jnp.float32)))
        return acc / l[..., None], m + jnp.log(l)

    o, lse = jax.lax.map(rows, jnp.arange(T // block))           # (nb,B,H,..)
    o = jnp.moveaxis(o, 0, 2).reshape(B, H, T, Dv)
    lse = jnp.moveaxis(lse, 0, 2).reshape(B, H, T)
    return o, (q, k, v, o, lse)


def _causal_bwd(scale, block, kept, do):
    q, k, v, o, lse = kept
    B, H, T, D = q.shape
    nb = T // block
    delta = (o * do).sum(-1)                                     # (B,H,T)
    do = do.astype(v.dtype)

    def keys(dq, j):
        k_j, v_j = _block_of(k, j, block), _block_of(v, j, block)

        def rows(i, carry):
            dq, dk_j, dv_j = carry
            q_i, do_i = _block_of(q, i, block), _block_of(do, i, block)
            s = _scores(q_i, k_j, i, j, scale, block)
            p = jnp.exp(s - _block_of(lse, i, block)[..., None])
            dv_j = dv_j + einsum_f32("bhqk,bhqd->bhkd", p.astype(v.dtype), do_i)
            dp = einsum_f32("bhqd,bhkd->bhqk", do_i, v_j)
            ds = (p * (dp - _block_of(delta, i, block)[..., None])
                  * scale).astype(q.dtype)
            dk_j = dk_j + einsum_f32("bhqk,bhqd->bhkd", ds, q_i)
            dq_i = _block_of(dq, i, block) + einsum_f32(
                "bhqk,bhkd->bhqd", ds, k_j)
            return (jax.lax.dynamic_update_slice_in_dim(dq, dq_i, i * block, 2),
                    dk_j, dv_j)

        dq, dk_j, dv_j = jax.lax.fori_loop(j, nb, rows, (
            dq, jnp.zeros(k_j.shape, jnp.float32),
            jnp.zeros(v_j.shape, jnp.float32)))
        return dq, (dk_j, dv_j)

    dq, (dk, dv) = jax.lax.scan(keys, jnp.zeros(q.shape, jnp.float32),
                                jnp.arange(nb))
    whole = lambda x: jnp.moveaxis(x, 0, 2).reshape(B, H, T, -1)
    return (dq.astype(q.dtype), whole(dk).astype(k.dtype),
            whole(dv).astype(v.dtype))


_causal.defvjp(_causal_fwd, _causal_bwd)
