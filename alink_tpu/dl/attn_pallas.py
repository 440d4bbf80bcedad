"""Pallas TPU kernel: fused flash-attention block update.

The blockwise/ring attention inner step (dl/attention.py) computes a score
block ``s = q·kᵀ`` of shape (B, H, Q, K) with an einsum, masks it, and
feeds it to ``_online_softmax_update`` — XLA materializes that score block
(plus the ``exp`` probabilities) in HBM between the two matmuls. This
kernel is the FlashAttention formulation (Dao et al., 2022) of the same
step: one grid cell = one (batch, head); the (Q, K) score tile, its
softmax statistics, and the correction of the running accumulators all
live in VMEM between the q·kᵀ and p·v matmuls, so the (B, H, Q, K) block
never touches HBM.

Shared by ``blockwise_attention`` (scan over K/V blocks) and
``ring_attention``'s per-shard body (fori_loop over devices) — both call
:func:`flash_block_update` with the exact accumulator semantics of
``_online_softmax_update`` (fp32 o/m/l, ``exp(max(m − m_new, −1e30))``
correction guarding fully-masked rows).

Numerics: the row-max, ``p.sum``, and matmul reductions run per-(b, h)
tile here but over the 4D block in XLA — deterministic both ways, not the
same float reduction order, so the parity contract is a pinned fp32
tolerance (atol=1e-5), not bit-equality (tests/test_kernels.py). Knob-off
compiles the untouched XLA scan — byte-identical to pre-kernel builds.

The tests run the same program under the Pallas interpreter on the
8-virtual-device CPU mesh. Gated by ``ALINK_ATTN_PALLAS`` through the
shared registry gate (native/kernels.py).
"""

from __future__ import annotations

import functools

_NEG_INF = -1e30
_SUBLANE = 8     # fp32 sublane tile; Q pads up to a multiple
_LANES = 128     # lane width; K and D pad up to a multiple


def use_attn_pallas() -> bool:
    """Gate for the flash block-update kernel: ``ALINK_ATTN_PALLAS``
    through the registry's shared parser (on by default in a one-chip TPU
    process; see ``single_device_only`` there)."""
    from ..native.kernels import kernel_enabled

    return kernel_enabled("ALINK_ATTN_PALLAS")


def _pad_axis(x, mult: int, axis: int, value=0):
    import jax.numpy as jnp

    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


def _xla_block_update(q, k, v, kvalid, qk_ok, o, m, l, scale: float):
    """The XLA form of the same update, in this module's (B, H, ...) layout:
    the parity reference (tests/test_kernels.py) and the function whose VJP
    is the kernel's backward pass."""
    import jax.numpy as jnp

    # attention.py imports this module at load; import back lazily
    from .attention import _online_softmax_update

    s = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * scale
    s = jnp.where(kvalid[:, None, None, :] > 0, s, _NEG_INF)
    s = jnp.where(qk_ok[None, None] > 0, s, _NEG_INF)
    o2, m2, l2 = _online_softmax_update(
        o.transpose(0, 2, 1, 3), m, l, s, v.transpose(0, 2, 1, 3), q.dtype)
    return o2.transpose(0, 2, 1, 3), m2, l2


def _flash_forward(q, k, v, kvalid, qk_ok, o, m, l, scale: float,
                   interpret: bool):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    B, H, Q, D = q.shape
    K = k.shape[2]
    p_dtype = q.dtype

    q_p = _pad_axis(_pad_axis(q, _SUBLANE, 2), _LANES, 3)
    k_p = _pad_axis(_pad_axis(k, _SUBLANE, 2), _LANES, 3)
    v_p = _pad_axis(_pad_axis(v, _SUBLANE, 2), _LANES, 3)
    # padded keys carry kvalid=0 (scores pin to -inf) AND are zeroed out
    # of p in-kernel, so even fully-masked rows match the XLA path.
    # Mosaic wants the last two block dims to be (8, 128)-aligned or the
    # array's own: kvalid rides as (B, 1, K) and m/l as (B, H, Q, 1), so
    # every per-(b, h) block is a full trailing 2-D slab and every value in
    # the kernel body stays 2-D.
    kv_p = _pad_axis(kvalid.astype(jnp.int32), _SUBLANE, 1)[:, None, :]
    ok_p = _pad_axis(_pad_axis(qk_ok.astype(jnp.int32), _SUBLANE, 0),
                     _SUBLANE, 1)
    o_p = _pad_axis(_pad_axis(o, _SUBLANE, 2), _LANES, 3)
    m_p = _pad_axis(m, _SUBLANE, 2, value=_NEG_INF)[..., None]
    l_p = _pad_axis(l, _SUBLANE, 2)[..., None]
    q_pad, d_pad = q_p.shape[2], q_p.shape[3]
    k_pad = k_p.shape[2]

    def kernel(q_ref, k_ref, v_ref, kv_ref, ok_ref, o_ref, m_ref, l_ref,
               oo_ref, mo_ref, lo_ref):
        qb = q_ref[0, 0]                                   # (Q, D)
        kb = k_ref[0, 0]                                   # (K, D)
        s = jax.lax.dot_general(
            qb, kb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        s = jnp.where(kv_ref[0] > 0, s, _NEG_INF)          # (1, K) bcast
        s = jnp.where(ok_ref[:] > 0, s, _NEG_INF)          # (Q, K)
        m_old = m_ref[0, 0]                                # (Q, 1)
        m_new = jnp.maximum(m_old, s.max(axis=-1, keepdims=True))
        corr = jnp.exp(jnp.maximum(m_old - m_new, _NEG_INF))
        p = jnp.exp(s - m_new)
        # drop the kernel's own K-padding columns from p outright: on a
        # fully-masked row every s is -1e30, so exp(s - m_new) = 1 for ALL
        # columns (the XLA path counts its K real columns there — padded
        # ones must not join, or l disagrees by k_pad - K)
        pad_ok = jax.lax.broadcasted_iota(jnp.int32, (1, k_pad), 1) < K
        p = jnp.where(pad_ok, p, 0.0)
        lo_ref[0, 0] = l_ref[0, 0] * corr + p.sum(axis=-1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(p_dtype), v_ref[0, 0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        oo_ref[0, 0] = o_ref[0, 0] * corr + pv
        mo_ref[0, 0] = m_new

    # inside shard_map(check_vma=True) the outputs must declare the manual
    # axes they vary over: the union of the inputs'
    args = (q_p, k_p, v_p, kv_p, ok_p, o_p, m_p, l_p)
    vma = frozenset().union(*(jax.typeof(a).vma for a in args))
    args = tuple(
        jax.lax.pcast(a, tuple(vma - jax.typeof(a).vma), to="varying")
        if vma - jax.typeof(a).vma else a for a in args)

    qk4 = lambda b, h: (b, h, 0, 0)
    oo, mo, lo = pl.pallas_call(
        kernel,
        grid=(B, H),
        in_specs=[
            pl.BlockSpec((1, 1, q_pad, d_pad), qk4),
            pl.BlockSpec((1, 1, k_pad, d_pad), qk4),
            pl.BlockSpec((1, 1, k_pad, d_pad), qk4),
            pl.BlockSpec((1, 1, k_pad), lambda b, h: (b, 0, 0)),
            pl.BlockSpec((q_pad, k_pad), lambda b, h: (0, 0)),
            pl.BlockSpec((1, 1, q_pad, d_pad), qk4),
            pl.BlockSpec((1, 1, q_pad, 1), qk4),
            pl.BlockSpec((1, 1, q_pad, 1), qk4),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, q_pad, d_pad), qk4),
            pl.BlockSpec((1, 1, q_pad, 1), qk4),
            pl.BlockSpec((1, 1, q_pad, 1), qk4),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, q_pad, d_pad), jnp.float32, vma=vma),
            jax.ShapeDtypeStruct((B, H, q_pad, 1), jnp.float32, vma=vma),
            jax.ShapeDtypeStruct((B, H, q_pad, 1), jnp.float32, vma=vma),
        ],
        interpret=interpret,
    )(*args)
    return oo[:, :, :Q, :D], mo[:, :, :Q, 0], lo[:, :, :Q, 0]


@functools.cache
def _flash():
    """The differentiable kernel call, built on first use (jax is imported
    lazily across this package)."""
    import jax

    @functools.partial(jax.custom_vjp, nondiff_argnums=(8, 9))
    def flash(q, k, v, kvalid, qk_ok, o, m, l, scale, interpret):
        return _flash_forward(q, k, v, kvalid, qk_ok, o, m, l, scale,
                              interpret)

    def fwd(q, k, v, kvalid, qk_ok, o, m, l, scale, interpret):
        out = _flash_forward(q, k, v, kvalid, qk_ok, o, m, l, scale,
                             interpret)
        return out, (q, k, v, kvalid, qk_ok, o, m, l)

    def bwd(scale, interpret, res, g):
        q, k, v, kvalid, qk_ok, o, m, l = res
        _, vjp = jax.vjp(
            lambda q, k, v, o, m, l: _xla_block_update(
                q, k, v, kvalid, qk_ok, o, m, l, scale), q, k, v, o, m, l)
        dq, dk, dv, do, dm, dl = vjp(g)
        return dq, dk, dv, None, None, do, dm, dl   # masks: no cotangent

    flash.defvjp(fwd, bwd)
    return flash


def flash_block_update(q, k, v, kvalid, qk_ok, o, m, l, *, scale: float,
                       interpret: bool = False):
    """One online-softmax accumulation over a K/V block, fused.

    q: (B, H, Q, D); k, v: (B, H, K, D); kvalid: (B, K) with 1 = valid
    key; qk_ok: (Q, K) with 1 = position allowed (the causal triangle, or
    all-ones); o/m/l: fp32 running accumulators (B, H, Q, D) / (B, H, Q) /
    (B, H, Q). Returns the updated ``(o, m, l)`` — the same update
    ``_online_softmax_update`` applies to the XLA score block.

    Differentiable: the forward pass is the kernel, the backward pass is
    the VJP of :func:`_xla_block_update` at the same inputs (the XLA form
    the kernel is pinned against), so training steps can ride the kernel
    before a backward kernel exists."""
    return _flash()(q, k, v, kvalid, qk_ok, o, m, l, float(scale),
                     bool(interpret))
