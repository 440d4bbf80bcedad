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
  (the attention form, no ``phi``) and through the state between chunks.
  Through the state it has two programs. In a one-chip TPU process, at a
  head width of 128, one kernel (:mod:`alink_tpu.dl.retention_pallas`):
  ``phi`` exists in VMEM alone, one cyclic distance (128 of its 8,256
  entries) of one row and key/value head at a time, and the state is read
  and written once, in place. Everywhere else (the CPU, a mesh, another
  width) XLA's form, a loop over key/value heads: ``phi`` of a chunk's
  queries is written to memory for one key/value head at a time. The XLA
  form is what the kernel is held to.
- :func:`retention_step`: one new token for a batch of states; ``phi`` of
  one position a head is written whole (XLA on every backend).

A position marked invalid (padding) leaves a row's state untouched: its gate
is 1 and its ``phi(k)`` is 0. State and normaliser are float32; ``dtype`` is
that of the matrix products' operands (bfloat16 on the chip).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from ..common.metrics import metrics
from ..native.kernels import interpret_mode
from .retention_pallas import chunk_through_state, use_chunk_kernel

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


def _inside_chunk(q, k, v, cum, dtype):
    """The attention form inside a chunk, with no ``phi``: q ``(...,G,T,D)``,
    k, v ``(...,T,D)``, cum ``(...,T)``. Returns the numerators
    ``(...,G,T,D)`` and the normalisers ``(...,G,T)``, float32."""
    T, D = q.shape[-2:]
    s = einsum_f32("...gtd,...sd->...gts", q.astype(dtype),
                   k.astype(dtype)) / math.sqrt(D)
    decay = jnp.where(jnp.tril(jnp.ones((T, T), bool)), jnp.exp(jnp.minimum(
        cum[..., :, None] - cum[..., None, :], 0.0)), 0.0)    # (...,T,T)
    # numerator and normaliser sum the same rounded weights, so that an
    # output stays a convex combination of the values
    a = (s * s * decay[..., None, :, :]).astype(dtype)
    return (einsum_f32("...gts,...sd->...gtd", a, v.astype(dtype)),
            a.astype(jnp.float32).sum(-1))


def _through_state_xla(q, k, v, cum, S, z, *, dtype):
    """What the state before the chunk adds to each position, and the state
    after the chunk, for one key/value head: q ``(B,G,T,D)``, k, v
    ``(B,T,D)``, cum ``(B,T)``, S ``(B,P,D)``, z ``(B,P)``."""
    f32 = jnp.float32
    # decayed to each position
    pq = (power_embed(q.astype(f32))
          * jnp.exp(cum)[:, None, :, None]).astype(dtype)
    num = einsum_f32("bgtp,bpd->bgtd", pq, S.astype(dtype))
    den = einsum_f32("bgtp,bp->bgt", pq, z.astype(dtype))
    # every key decayed to the chunk's end
    total = cum[:, -1]
    pk = (power_embed(k.astype(f32))
          * jnp.exp(total[:, None] - cum)[..., None]).astype(dtype)
    Sn = jnp.exp(total)[:, None, None] * S + einsum_f32(
        "btp,btd->bpd", pk, v.astype(dtype))
    zn = jnp.exp(total)[:, None] * z + pk.astype(f32).sum(1)
    return num, den, Sn, zn


def retention_chunk(q, k, v, log_g, valid, S, z, *, eps: float,
                    dtype=jnp.float32):
    """One chunk of ``T`` prompt positions for ``B`` rows.

    q ``(B,T,Hq,D)``; k, v ``(B,T,Hkv,D)``; log_g ``(B,T,Hkv)`` (log of the
    gate, <= 0); valid ``(B,T)`` bool or None; S ``(B,Hkv,P,D)`` and z
    ``(B,Hkv,P)`` float32, the state before the chunk. Returns the outputs
    ``(B,T,Hq,D)`` float32 and the state after the chunk.

    One layer, two programs, chosen by the call's own shapes and the
    kernel's gate (``retention_pallas.use_chunk_kernel``); which one a layer
    was traced down is counted (``retention.chunk_fused_traces`` /
    ``retention.chunk_xla_traces``)."""
    B, T, Hq, D = q.shape
    Hkv = k.shape[2]
    G = Hq // Hkv
    k, log_g = _mask_padding(k, log_g.astype(jnp.float32), valid)
    cum = jnp.cumsum(log_g, axis=1)                       # (B,T,Hkv)
    heads = lambda x: x.transpose(0, 2, 1, 3)             # (B,Hkv,T,D)
    small = (q.reshape(B, T, Hkv, G, D).transpose(0, 2, 3, 1, 4), heads(k),
             heads(v), cum.transpose(0, 2, 1))

    def attend(q, k, v, cum, S, z, through_state):
        """Any leading dimensions: all heads at once or one of them."""
        with jax.named_scope(RETENTION_SCOPE):
            num, den = _inside_chunk(q, k, v, cum, dtype)
            num_s, den_s, S, z = through_state(q, k, v, cum, S, z, dtype=dtype)
            return (num + num_s) / ((den + den_s)[..., None] + eps), S, z

    if use_chunk_kernel(T, D):
        metrics.incr("retention.chunk_fused_traces")
        o, S, z = attend(*small, S, z, functools.partial(
            chunk_through_state, interpret=interpret_mode()))
    else:
        metrics.incr("retention.chunk_xla_traces")
        # one key/value head at a time: phi of a chunk's queries for all
        # heads at once would be the largest tensor of the model
        o, S, z = (jnp.moveaxis(x, 0, 1) for x in jax.lax.map(
            lambda head: attend(*head, _through_state_xla),
            tuple(jnp.moveaxis(x, 1, 0) for x in small + (S, z))))
    # (B,Hkv,G,T,D) -> (B,T,Hq,D)
    return o.transpose(0, 3, 1, 2, 4).reshape(B, T, Hq, D), S, z


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
