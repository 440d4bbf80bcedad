"""Attention kernels: full (single-device / GSPMD) and ring (sequence-parallel).

The reference has NO long-context machinery (SURVEY.md §5 "Long-context /
sequence parallelism: absent") — this module is the TPU-first addition that
makes sequence length a shardable dimension. Ring attention passes K/V shards
around the ``seq`` mesh axis with ``ppermute`` (one ICI hop per step) while
accumulating the softmax online, so no device ever materializes the full
(S, S) score matrix or the full K/V.

Design notes:
- ``full_attention`` is plain jnp — under jit with head-sharded params XLA
  partitions it over the ``model`` axis (tensor parallelism) for free.
- ``ring_attention`` is a ``shard_map`` manual only over the ``seq`` axis
  (``axis_names={'seq'}``): the data/model axes stay in GSPMD auto mode, so
  dp and tp compose with it without hand-written collectives.
- Online-softmax accumulation in fp32 regardless of input dtype (bf16 inputs
  stay bf16 through the matmuls — MXU — but m/l/o accumulate fp32).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from ..parallel.mesh import AXIS_SEQ
from ..parallel.shardmap import axis_size, pvary, shard_map
from ..common.metrics import metrics
from .attn_pallas import (flash_block_update, fused_attention,
                          use_attn_pallas, use_fused_attention)

_NEG_INF = -1e30


def full_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mask: Optional[jax.Array] = None,
    *,
    causal: bool = False,
) -> jax.Array:
    """Standard scaled dot-product attention.

    q, k, v: (B, S, H, D); mask: (B, S) with 1 = valid key. Returns (B, S, H, D).
    """
    d = q.shape[-1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(jnp.asarray(d, q.dtype))
    s = s.astype(jnp.float32)
    if mask is not None:
        s = jnp.where(mask[:, None, None, :] > 0, s, _NEG_INF)
    if causal:
        sq, sk = q.shape[1], k.shape[1]
        cm = jnp.tril(jnp.ones((sq, sk), bool))
        s = jnp.where(cm[None, None], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def packed_attention(qkv: jax.Array, mask: Optional[jax.Array] = None, *,
                     num_heads: int) -> jax.Array:
    """The default attention of an encoder layer, from the packed projection.

    qkv: (B, S, 3, H*D), q, k and v side by side, each with its heads side
    by side; mask: (B, S) with 1 = valid key. Returns (B, S, H*D).

    One algorithm, two programs: the fused core (attn_pallas.py: one kernel
    each way, scores in VMEM only) where its gate and the call's own shapes
    allow it (``use_fused_attention``: a one-chip TPU process, a lane-aligned
    length from the measured threshold up, head dimension 64 or 128), else
    :func:`full_attention`. Which one a layer was traced down is counted
    (``attention.fused_traces`` / ``attention.xla_traces``, at trace time)."""
    b, s, _, hd = qkv.shape
    d = hd // num_heads
    if use_fused_attention(s, num_heads, d):
        from ..native.kernels import interpret_mode

        metrics.incr("attention.fused_traces")
        return fused_attention(qkv, mask, num_heads=num_heads,
                               interpret=interpret_mode())
    metrics.incr("attention.xla_traces")
    q, k, v = (qkv[:, :, i].reshape(b, s, num_heads, d) for i in range(3))
    return full_attention(q, k, v, mask).reshape(b, s, hd)


def _online_softmax_update(o, m, l, s, v, p_dtype):
    """One online-softmax accumulation step over a new score block ``s``
    (B, H, Q, K) — shared by the ring and blockwise kernels so their
    numerics cannot diverge. Accumulators o/m/l stay fp32."""
    m_new = jnp.maximum(m, s.max(axis=-1))
    # guard fully-masked rows: exp(-inf - -inf) -> exp(0) must not fire
    corr = jnp.exp(jnp.maximum(m - m_new, _NEG_INF))
    p = jnp.exp(s - m_new[..., None])
    l = l * corr + p.sum(axis=-1)
    pv = jnp.einsum("bhqk,bkhd->bqhd", p.astype(p_dtype), v)
    o = o * corr.transpose(0, 2, 1)[..., None] + pv.astype(jnp.float32)
    return o, m_new, l


def blockwise_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mask: Optional[jax.Array] = None,
    *,
    block_size: int = 512,
    causal: bool = False,
) -> jax.Array:
    """Memory-efficient attention: the (S, S) score matrix never
    materializes — K/V are consumed in ``block_size`` chunks under a
    ``lax.scan`` with the same online-softmax update ring attention uses
    (block axis instead of device axis). The single-device long-context
    complement to :func:`ring_attention`: O(S*block) live memory, fully
    static shapes, XLA-schedulable.

    q, k, v: (B, S, H, D); mask: (B, S) with 1 = valid key.
    """
    b, sq, h, d = q.shape
    sk = k.shape[1]
    nb = -(-sk // block_size)
    pad = nb * block_size - sk
    if pad:
        zpad = ((0, 0), (0, pad), (0, 0), (0, 0))
        k = jnp.pad(k, zpad)
        v = jnp.pad(v, zpad)
    # padded keys are always masked off
    kmask = jnp.ones((b, sk), jnp.int32) if mask is None else mask
    kmask = jnp.pad(kmask, ((0, 0), (0, pad)))
    kb = k.reshape(b, nb, block_size, h, d).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(b, nb, block_size, h, d).transpose(1, 0, 2, 3, 4)
    mb = kmask.reshape(b, nb, block_size).transpose(1, 0, 2)

    scale = 1.0 / jnp.sqrt(jnp.asarray(d, jnp.float32))
    q_pos = jnp.arange(sq)

    if use_attn_pallas():
        # fused flash path: same scan, but the score block + online-softmax
        # update run inside one Pallas program (attn_pallas.py) with
        # (B, H, ...) accumulator layout. Knob read at trace time; knob-off
        # compiles the scan below untouched.
        from ..native.kernels import interpret_mode

        interp = interpret_mode()
        scale_f = float(d) ** -0.5
        qf = q.transpose(0, 2, 1, 3)              # (B, H, Q, D)
        ok_all = jnp.ones((sq, block_size), jnp.int32)

        def fstep(carry, blk):
            o, m, l = carry
            kk, vv, mm, i = blk
            if causal:
                k_pos = i * block_size + jnp.arange(block_size)
                ok = (q_pos[:, None] >= k_pos[None, :]).astype(jnp.int32)
            else:
                ok = ok_all
            o, m, l = flash_block_update(
                qf, kk.transpose(0, 2, 1, 3), vv.transpose(0, 2, 1, 3),
                mm, ok, o, m, l, scale=scale_f, interpret=interp)
            return (o, m, l), None

        of0 = jnp.zeros((b, h, sq, d), jnp.float32)
        m0 = jnp.full((b, h, sq), _NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, h, sq), jnp.float32)
        (o, m, l), _ = jax.lax.scan(
            fstep, (of0, m0, l0), (kb, vb, mb, jnp.arange(nb)))
        l = jnp.maximum(l, 1e-30)
        return (o / l[..., None]).transpose(0, 2, 1, 3).astype(q.dtype)

    def step(carry, blk):
        o, m, l = carry
        kk, vv, mm, i = blk
        s = jnp.einsum("bqhd,bkhd->bhqk", q, kk).astype(jnp.float32) * scale
        s = jnp.where(mm[:, None, None, :] > 0, s, _NEG_INF)
        if causal:
            k_pos = i * block_size + jnp.arange(block_size)
            s = jnp.where(
                q_pos[None, None, :, None] >= k_pos[None, None, None, :],
                s, _NEG_INF)
        o, m, l = _online_softmax_update(o, m, l, s, vv, q.dtype)
        return (o, m, l), None

    o0 = jnp.zeros((b, sq, h, d), jnp.float32)
    m0 = jnp.full((b, h, sq), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, sq), jnp.float32)
    (o, m, l), _ = jax.lax.scan(
        step, (o0, m0, l0), (kb, vb, mb, jnp.arange(nb)))
    l = jnp.maximum(l, 1e-30)
    return (o / l.transpose(0, 2, 1)[..., None]).astype(q.dtype)


def _ring_body(q, k, v, mask, axis_name: str, causal: bool):
    """Manual kernel: local q against the rotating ring of k/v shards."""
    n = axis_size(axis_name)
    my = jax.lax.axis_index(axis_name)
    b, sq, h, d = q.shape
    scale = 1.0 / jnp.sqrt(jnp.asarray(d, jnp.float32))
    perm = [(i, (i + 1) % n) for i in range(n)]

    # initial accumulators must carry the same varying-over-seq type as the
    # loop outputs (check_vma-tracked), hence pvary
    def _varying(x):
        return pvary(x, axis_name)

    if use_attn_pallas():
        # fused flash path: per-shard score block + online-softmax update as
        # one Pallas program; the ppermute ring around it is unchanged.
        from ..native.kernels import interpret_mode

        interp = interpret_mode()
        scale_f = float(d) ** -0.5
        sk = k.shape[1]
        qf = q.transpose(0, 2, 1, 3)              # (B, H, Q, D)
        of0 = _varying(jnp.zeros((b, h, sq, d), jnp.float32))
        mf0 = _varying(jnp.full((b, h, sq), _NEG_INF, jnp.float32))
        lf0 = _varying(jnp.zeros((b, h, sq), jnp.float32))
        kv_all = jnp.ones((b, sk), jnp.int32)
        ok_all = jnp.ones((sq, sk), jnp.int32)

        def fstep(i, carry):
            o, m, l, k, v, kmask = carry
            src = jnp.mod(my - i, n)
            if causal:
                q_pos = my * sq + jnp.arange(sq)
                k_pos = src * sk + jnp.arange(sk)
                ok = (q_pos[:, None] >= k_pos[None, :]).astype(jnp.int32)
            else:
                ok = ok_all
            o, m, l = flash_block_update(
                qf, k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3),
                kv_all if kmask is None else kmask, ok, o, m, l,
                scale=scale_f, interpret=interp)
            k = jax.lax.ppermute(k, axis_name, perm)
            v = jax.lax.ppermute(v, axis_name, perm)
            if kmask is not None:
                kmask = jax.lax.ppermute(kmask, axis_name, perm)
            return o, m, l, k, v, kmask

        o, m, l, *_ = jax.lax.fori_loop(0, n, fstep, (of0, mf0, lf0, k, v, mask))
        l = jnp.maximum(l, 1e-30)
        return (o / l[..., None]).transpose(0, 2, 1, 3).astype(q.dtype)

    o0 = _varying(jnp.zeros((b, sq, h, d), jnp.float32))
    m0 = _varying(jnp.full((b, h, sq), _NEG_INF, jnp.float32))
    l0 = _varying(jnp.zeros((b, h, sq), jnp.float32))

    def step(i, carry):
        o, m, l, k, v, kmask = carry
        # the shard we hold at step i originated at device (my - i) mod n
        src = jnp.mod(my - i, n)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
        if kmask is not None:
            s = jnp.where(kmask[:, None, None, :] > 0, s, _NEG_INF)
        if causal:
            sk = k.shape[1]
            q_pos = my * sq + jnp.arange(sq)
            k_pos = src * sk + jnp.arange(sk)
            s = jnp.where(q_pos[None, None, :, None] >= k_pos[None, None, None, :],
                          s, _NEG_INF)
        o, m, l = _online_softmax_update(o, m, l, s, v, q.dtype)
        k = jax.lax.ppermute(k, axis_name, perm)
        v = jax.lax.ppermute(v, axis_name, perm)
        if kmask is not None:
            kmask = jax.lax.ppermute(kmask, axis_name, perm)
        return o, m, l, k, v, kmask

    o, m, l, *_ = jax.lax.fori_loop(0, n, step, (o0, m0, l0, k, v, mask))
    l = jnp.maximum(l, 1e-30)
    return (o / l.transpose(0, 2, 1)[..., None]).astype(q.dtype)


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mask: Optional[jax.Array] = None,
    *,
    mesh=None,
    axis: str = AXIS_SEQ,
    causal: bool = False,
) -> jax.Array:
    """Sequence-parallel attention: q/k/v sharded (B, S/axis, H, D) over `axis`.

    Runs as a shard_map manual over ONLY the seq axis; data/model sharding is
    left to GSPMD (``axis_names={axis}``), so tensor-parallel heads and
    data-parallel batch pass straight through.
    """
    if mesh is None or mesh.shape.get(axis, 1) == 1:
        return full_attention(q, k, v, mask, causal=causal)

    from jax.sharding import PartitionSpec as P

    qkv_spec = P(None, axis, None, None)
    body = functools.partial(_ring_body, axis_name=axis, causal=causal)
    args, in_specs = (q, k, v), (qkv_spec,) * 3
    if mask is None:
        body = functools.partial(body, mask=None)
    else:
        args, in_specs = args + (mask,), in_specs + (P(None, axis),)
    kwargs = {"axis_names": {axis}}
    if use_attn_pallas():
        from ..native.kernels import interpret_mode

        if interpret_mode():
            # the Pallas interpreter (CPU tests) evaluates the kernel body
            # with unvarying grid indices against varying blocks, which the
            # vma checker rejects, and jax 0.9 runs an unchecked shard_map
            # eagerly only when it is manual over every axis (the other
            # axes then see replicated operands: redundant, not wrong). A
            # compiled kernel (the chip) is one typed primitive and keeps
            # both the check and the partial-manual form.
            kwargs = {"check_vma": False}
    f = shard_map(body, mesh=mesh, in_specs=in_specs, out_specs=qkv_spec,
                  **kwargs)
    return f(*args)
