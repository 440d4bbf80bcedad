"""Power retention: linear-cost attention with a fixed-size state.

The layer (Manifest AI, "Scaling Context Requires Rethinking Attention",
arXiv:2507.04239; the gate is the Brumby-14B-Base release's) replaces the
softmax weight of a query on a key by ``w_ts = (q_t.k_s / sqrt(d))^2`` and a
data-dependent decay ``G_ts = prod_{r=s+1..t} g_r``::

    o_t = sum_{s<=t} G_ts w_ts v_s / (sum_{s<=t} G_ts w_ts + eps)

Because ``w_ts = <phi(q_t), phi(k_s)>`` for the symmetric degree-2 power
embedding ``phi: R^d -> R^{d(d+1)/2}``, the sums are a recurrence over a state
``S`` (``d(d+1)/2 x d``) and a normaliser ``z`` per key/value head::

    S_t = g_t S_{t-1} + phi(k_t) v_t^T     z_t = g_t z_{t-1} + phi(k_t)
    o_t = phi(q_t)^T S_t / (phi(q_t)^T z_t + eps)

This module holds the two forms of that one layer that a served generator
runs, over grouped heads (``G`` query heads read each key/value head's state):

- :func:`retention_chunk`: a chunk of a prompt, quadratic inside the chunk
  (the attention form, no ``phi``) and through the state between chunks;
  ``phi`` exists for one key/value head of one chunk at a time.
- :func:`retention_step`: one new token for a batch of states.

A position marked invalid (padding) leaves a row's state untouched: its gate
is 1 and its ``phi(k)`` is 0. State and normaliser are float32; ``dtype`` is
that of the matrix products' operands (bfloat16 on the chip).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

RETENTION_SCOPE = "retention_core"


def einsum_f32(eq: str, a, b):
    """``einsum`` of two operands of one dtype with a float32 result: the
    products exact, the sum in float32. XLA's CPU backend has no bfloat16
    product with a float32 result, so there the operands are widened first,
    which computes the same numbers."""
    if a.dtype != jnp.float32 and jax.default_backend() == "cpu":
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    return jnp.einsum(eq, a, b, preferred_element_type=jnp.float32)


def phi_dim(d: int) -> int:
    return d * (d + 1) // 2


def power_embed(x: jax.Array) -> jax.Array:
    """``phi(x)``, scaled so that ``<phi(q), phi(k)> = (q.k / sqrt(d))^2``.

    The pairs ``a <= b`` are laid out by cyclic distance: row ``s`` of
    ``x * roll(x, -s)`` holds the pairs ``(a, a+s mod d)``, for ``s`` from 0
    (the squares, coefficient 1) to ``d/2`` (coefficient sqrt 2; the last row
    holds each pair twice and only its first half is kept). The rolled rows
    are one reshape of ``x`` tiled: row ``s`` of ``tile(x)`` cut into rows of
    ``d + 1`` starts ``s`` places further round, so the embedding is one
    fused pass with no gather (a stack of ``d/2`` rolls compiles to as many
    small operations, which a decode step pays for in every layer)."""
    d = x.shape[-1]
    if d % 2:
        raise ValueError("power_embed needs an even head width")
    half = d // 2
    rolled = jnp.tile(x, half + 2)[..., :(half + 1) * (d + 1)].reshape(
        *x.shape[:-1], half + 1, d + 1)[..., :d]
    coef = jnp.asarray([1.0] + [math.sqrt(2.0)] * half, x.dtype) / math.sqrt(d)
    y = x[..., None, :] * rolled * coef[:, None]
    return y.reshape(*x.shape[:-1], (half + 1) * d)[..., :phi_dim(d)]


def _mask_padding(k, log_g, valid):
    """Gate 1 and key 0 where ``valid`` is false."""
    if valid is None:
        return k, log_g
    return (jnp.where(valid[..., None, None], k, 0),
            jnp.where(valid[..., None], log_g, 0))


def retention_chunk(q, k, v, log_g, valid, S, z, *, eps: float,
                    dtype=jnp.float32):
    """One chunk of ``T`` prompt positions for ``B`` rows.

    q ``(B,T,Hq,D)``; k, v ``(B,T,Hkv,D)``; log_g ``(B,T,Hkv)`` (log of the
    gate, <= 0); valid ``(B,T)`` bool or None; S ``(B,Hkv,P,D)`` and z
    ``(B,Hkv,P)`` float32, the state before the chunk. Returns the outputs
    ``(B,T,Hq,D)`` float32 and the state after the chunk."""
    B, T, Hq, D = q.shape
    Hkv = k.shape[2]
    G = Hq // Hkv
    k, log_g = _mask_padding(k, log_g.astype(jnp.float32), valid)
    cum = jnp.cumsum(log_g, axis=1)                       # (B,T,Hkv)
    causal = jnp.tril(jnp.ones((T, T), bool))
    f32 = jnp.float32

    def one_head(args):
        qj, kj, vj, cj, Sj, zj = args     # (B,T,G,D) (B,T,D) (B,T,D) (B,T) ..
        with jax.named_scope(RETENTION_SCOPE):
            s = einsum_f32("btgd,bsd->bgts", qj.astype(dtype),
                           kj.astype(dtype)) / math.sqrt(D)
            decay = jnp.where(causal, jnp.exp(jnp.minimum(
                cj[:, :, None] - cj[:, None, :], 0.0)), 0.0)      # (B,T,T)
            # numerator and normaliser sum the same rounded weights, so that
            # an output stays a convex combination of the values
            a = (s * s * decay[:, None]).astype(dtype)
            num = einsum_f32("bgts,bsd->btgd", a, vj.astype(dtype))
            den = a.astype(f32).sum(-1).transpose(0, 2, 1)        # (B,T,G)
            # what the state before the chunk adds, decayed to each position
            pq = (power_embed(qj.astype(f32))
                  * jnp.exp(cj)[:, :, None, None]).astype(dtype)
            num = num + einsum_f32("btgp,bpd->btgd", pq, Sj.astype(dtype))
            den = den + einsum_f32("btgp,bp->btg", pq, zj.astype(dtype))
            o = num / (den[..., None] + eps)
            # the state after the chunk: every key decayed to the chunk's end
            total = cj[:, -1]
            pk = (power_embed(kj.astype(f32)) * jnp.exp(
                total[:, None] - cj)[..., None]).astype(dtype)
            Sn = jnp.exp(total)[:, None, None] * Sj + einsum_f32(
                "btp,btd->bpd", pk, vj.astype(dtype))
            zn = jnp.exp(total)[:, None] * zj + pk.astype(f32).sum(1)
        return o, Sn, zn

    heads_first = (q.reshape(B, T, Hkv, G, D).transpose(2, 0, 1, 3, 4),
                   k.transpose(2, 0, 1, 3), v.transpose(2, 0, 1, 3),
                   cum.transpose(2, 0, 1), S.transpose(1, 0, 2, 3),
                   z.transpose(1, 0, 2))
    # one key/value head at a time: phi of a chunk's queries for all heads
    # at once would be the largest tensor of the model
    o, Sn, zn = jax.lax.map(one_head, heads_first)
    o = o.transpose(1, 2, 0, 3, 4).reshape(B, T, Hq, D)
    return o, Sn.transpose(1, 0, 2, 3), zn.transpose(1, 0, 2)


def retention_step(q, k, v, log_g, valid, S, z, *, eps: float):
    """One new position for ``B`` rows through their states.

    q ``(B,Hq,D)``; k, v ``(B,Hkv,D)``; log_g ``(B,Hkv)``; valid ``(B,)``
    bool or None; S, z as in :func:`retention_chunk`. Returns the outputs
    ``(B,Hq,D)`` float32 and the updated state."""
    B, Hq, D = q.shape
    Hkv = k.shape[1]
    f32 = jnp.float32
    k, log_g = _mask_padding(k, log_g.astype(f32), valid)
    with jax.named_scope(RETENTION_SCOPE):
        g = jnp.exp(log_g)
        pk = power_embed(k.astype(f32))                          # (B,Hkv,P)
        S = g[..., None, None] * S + pk[..., None] * v.astype(f32)[..., None, :]
        z = g[..., None] * z + pk
        pq = power_embed(q.astype(f32)).reshape(B, Hkv, Hq // Hkv, -1)
        # three bfloat16 passes, not one: a weight <phi(q), phi(k)> is what
        # is left of 8256 products that cancel a hundredfold and more, and
        # rounding S and phi(q) to bfloat16 leaves an error in it that does
        # not shrink with the weight, so a position whose weights are all
        # small (one in a few hundred) would read a wrong output, where the
        # chunk's squared score keeps its relative error
        hi = jax.lax.Precision.HIGH
        num = jnp.einsum("bjgp,bjpd->bjgd", pq, S, precision=hi)
        den = jnp.einsum("bjgp,bjp->bjg", pq, z, precision=hi)
        o = num / (den[..., None] + eps)
    return o.reshape(B, Hq, D), S, z
