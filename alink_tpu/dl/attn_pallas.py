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


# ---------------------------------------------------------------------------
# fused attention core: whole sequence in one cell, forward and backward
# ---------------------------------------------------------------------------

# The default (non-blockwise, non-ring) attention of ``SelfAttention`` takes
# the fused core from this length on: the shortest at which it won both
# directions on the chip. One TPU v5e, the BERT-base encoder
# (``TransformerEncoder``, default configuration, bfloat16) at 16,384 tokens a
# step, XLA -> kernel in ms, two calls (docs/chip_calls/pr31/threshold.py;
# PERF.md section 6, PR 31):
#   rows x positions   forward                       forward + backward
#   128 x 128          22.42 -> 22.06, 22.32 -> 21.95   68.42 -> 66.43, 67.56 -> 65.57
#    64 x 256          25.72 -> 21.66, 25.56 -> 21.53   79.65 -> 64.60, 78.79 -> 63.63
#    32 x 512          32.63 -> 21.19, 32.49 -> 21.06  109.31 -> 65.57, 108.60 -> 64.68
#    16 x 1024         48.85 -> 24.83, 48.71 -> 24.70  163.95 -> 76.07, 163.24 -> 75.21
# At 128 the core alone is even forward (0.488 -> 0.489 ms a layer) and what
# the encoder gains is the copies of q, k, v that go with it. 128 is also the
# shortest lane-aligned length, so today the rule below takes every length it
# can hold; the constant is where a later reading would move it.
_FUSED_MIN_SEQ = 128
# one head's float32 score tile is S*S*4 bytes and the backward pass holds
# four of them: 16 MB at 1024 positions, inside the limit asked for below
_FUSED_MAX_SEQ = 1024
_FUSED_VMEM_BYTES = 100 * 1024 * 1024
_STAT_ROWS = 8   # sublane tile of the (heads-in-cell, S) statistics block


def use_fused_attention(seq_len: int, num_heads: int, head_dim: int, *,
                        causal: bool = False) -> bool:
    """Whether the default attention takes the fused core: decided from the
    call's own shapes and the kernel's gate, nothing else. Head dimension 64
    (two heads fill the 128 lanes) or 128; a lane-aligned length between the
    threshold and what one cell's VMEM holds; not causal."""
    return (not causal
            and head_dim in (64, 128)
            and (num_heads * head_dim) % _LANES == 0
            and seq_len % _LANES == 0
            and _FUSED_MIN_SEQ <= seq_len <= _FUSED_MAX_SEQ
            and use_attn_pallas())


def _head_lanes(lane, h: int, d: int):
    """Lanes of head ``h`` in a 128-lane group of ``128 // d`` heads; None
    where one head fills the group."""
    if d == _LANES:
        return None
    return (lane >= h * d) & (lane < (h + 1) * d)


def _only(x, lanes):
    import jax.numpy as jnp

    return x if lanes is None else jnp.where(lanes, x, jnp.zeros_like(x))


def _pick(lanes, new, old):
    import jax.numpy as jnp

    return new if lanes is None or old is None else jnp.where(lanes, new, old)


def _mask_fill(mrow):
    """What a masked score is set to: the path's -1e30, or 0 for a row whose
    keys are all masked, so that such a row attends evenly over every key as
    ``full_attention``'s softmax of equal scores does, and its log-sum-exp
    stays a number float32 holds."""
    import jax.numpy as jnp

    return jnp.where(mrow.max(axis=-1, keepdims=True) > 0, _NEG_INF, 0.0)


def _rows_of(cols):
    """Per-head column statistics, packed one head a lane in ``(S, 128)``,
    as rows ``(8, S)``: lane-dense in memory, and the form the backward
    kernel reads, which works on transposed scores."""
    return cols.T[:_STAT_ROWS]


def _fused_fwd_kernel(qkv_ref, m_ref, o_ref, lse_ref, *, d: int,
                      scale: float):
    import jax
    import jax.numpy as jnp

    q, k, v = qkv_ref[0], qkv_ref[1], qkv_ref[2]          # (S, 128)
    s_len = q.shape[0]
    mrow = m_ref[...]                                      # (1, S)
    fill = _mask_fill(mrow)
    lane = jax.lax.broadcasted_iota(jnp.int32, (s_len, _LANES), 1)
    out, stats = None, jnp.zeros((s_len, _LANES), jnp.float32)
    for h in range(_LANES // d):
        lanes = _head_lanes(lane, h, d)
        # the other head's lanes zeroed: the 128-deep contraction is this
        # head's 64-deep one, at the same cost on the matrix unit
        s = jax.lax.dot_general(
            _only(q, lanes), k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale    # (Sq, Sk)
        s = jnp.where(mrow > 0, s, fill)
        m = s.max(axis=-1, keepdims=True)
        p = jnp.exp(s - m)
        l = p.sum(axis=-1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)            # (Sq, 128)
        out = _pick(lanes, pv / l, out)
        stats = jnp.where(lane == h, m + jnp.log(l), stats)
    o_ref[...] = out.astype(o_ref.dtype)
    lse_ref[...] = _rows_of(stats)


def _fused_bwd_kernel(qkv_ref, m_ref, o_ref, do_ref, lse_ref, dqkv_ref, *,
                      d: int, scale: float):
    """Scores transposed, keys down and queries across: the log-sum-exp and
    rowsum(dO o O) are rows, dV = P^T dO and dK = dS^T Q are plain products,
    and only dQ contracts over the leading axis."""
    import jax
    import jax.numpy as jnp

    q, k, v = qkv_ref[0], qkv_ref[1], qkv_ref[2]          # (S, 128)
    do = do_ref[...]
    s_len, dtype = q.shape[0], q.dtype
    mrow = m_ref[...]                                      # (1, S)
    fill = _mask_fill(mrow)
    # the key mask down the sublanes, equal in every lane
    kcol = jnp.broadcast_to(mrow.astype(jnp.float32), (_LANES, s_len)).T
    key_ok, key_ok1 = kcol > 0, kcol[:, :1] > 0            # (Sk, 128), (Sk, 1)
    lane = jax.lax.broadcasted_iota(jnp.int32, (s_len, _LANES), 1)
    dd = do.astype(jnp.float32) * o_ref[...].astype(jnp.float32)
    delta = jnp.zeros((s_len, _LANES), jnp.float32)
    for h in range(_LANES // d):
        lanes = _head_lanes(lane, h, d)
        delta = jnp.where(
            lane == h, _only(dd, lanes).sum(axis=-1, keepdims=True), delta)
    delta = _rows_of(delta)                                # (8, Sq)
    lse = lse_ref[...]                                     # (8, Sq)
    # a masked key's score does not depend on q or k (full_attention's
    # `where`); p is 0 there but for a row with every key masked
    k_valid = jnp.where(key_ok, k, jnp.zeros_like(k))
    dq = dk = dv = None
    for h in range(_LANES // d):
        lanes = _head_lanes(lane, h, d)
        st = jax.lax.dot_general(
            _only(k, lanes), q, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale    # (Sk, Sq)
        st = jnp.where(key_ok1, st, fill)
        pt = jnp.exp(st - lse[h:h + 1])
        dv = _pick(lanes, jax.lax.dot_general(
            pt.astype(dtype), do, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32), dv)
        dpt = jax.lax.dot_general(
            _only(v, lanes), do, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)            # (Sk, Sq)
        dst = (pt * (dpt - delta[h:h + 1])).astype(dtype)
        dk = _pick(lanes, jax.lax.dot_general(
            dst, q, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32), dk)
        dq = _pick(lanes, jax.lax.dot_general(
            dst, k_valid, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32), dq)
    dk = jnp.where(key_ok, dk * scale, 0.0)
    for i, part in enumerate((dq * scale, dk, dv)):
        dqkv_ref[i] = part.astype(dqkv_ref.dtype)


def _fused_call(name: str, kernel, qkv, num_heads: int, interpret: bool,
                ins, out):
    """One of the two kernels over the grid (batch row b, lane group g).
    ``ins`` and ``out`` name each operand's block: ``packed``, q, k and v (or
    their gradients) as one (3, S, 128) block of (B, 3, S, H*D); ``slab``, o
    or dO (S, 128) of (B, S, H*D); ``mask``, the keys' (1, S); ``stat``, the
    cell's (8, S) statistics of (B, H*D/128, 8, S). ``out`` pairs each
    block's name with the array's ``ShapeDtypeStruct``."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, _, s_len, hd = qkv.shape
    d = hd // num_heads
    specs = {
        "packed": pl.BlockSpec((None, 3, s_len, _LANES),
                               lambda b, g: (b, 0, 0, g)),
        "slab": pl.BlockSpec((None, s_len, _LANES), lambda b, g: (b, 0, g)),
        "mask": pl.BlockSpec((None, 1, s_len), lambda b, g: (b, 0, 0)),
        "stat": pl.BlockSpec((None, None, _STAT_ROWS, s_len),
                             lambda b, g: (b, g, 0, 0)),
    }
    return pl.pallas_call(
        functools.partial(kernel, d=d, scale=float(d) ** -0.5),
        grid=(b, hd // _LANES),
        in_specs=[specs[n] for n, _ in ins],
        out_specs=[specs[n] for n, _ in out],
        out_shape=[shape for _, shape in out],
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=_FUSED_VMEM_BYTES),
        interpret=interpret,
        name=name,
    )(*(x for _, x in ins))


def _fused_forward(qkv, mask, num_heads: int, interpret: bool):
    """qkv: (B, 3, S, H*D), heads side by side in each of q, k, v; mask:
    (B, 1, S) int32. Returns o (B, S, H*D) and the rows' log-sum-exp
    (B, H*D/128, 8, S), one head a row."""
    import jax
    import jax.numpy as jnp

    b, _, s_len, hd = qkv.shape
    return _fused_call(
        "attn_pallas_fwd", _fused_fwd_kernel, qkv, num_heads, interpret,
        [("packed", qkv), ("mask", mask)],
        [("slab", jax.ShapeDtypeStruct((b, s_len, hd), qkv.dtype)),
         ("stat", jax.ShapeDtypeStruct((b, hd // _LANES, _STAT_ROWS, s_len),
                                       jnp.float32))])


def _fused_backward(qkv, mask, o, lse, do, num_heads: int, interpret: bool):
    import jax

    return _fused_call(
        "attn_pallas_bwd", _fused_bwd_kernel, qkv, num_heads, interpret,
        [("packed", qkv), ("mask", mask), ("slab", o), ("slab", do),
         ("stat", lse)],
        [("packed", jax.ShapeDtypeStruct(qkv.shape, qkv.dtype))])[0]


@functools.cache
def _build_fused():
    """The differentiable core under a jit of its own, built once: the layers
    of a model call one function object with equal shapes, so a program's
    trace holds the two kernels' traces and lowerings once, not once a layer
    (the compiler inlines the calls: the compiled program is the same)."""
    import jax

    @functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
    def fused(qkv, mask, num_heads, interpret):
        return _fused_forward(qkv, mask, num_heads, interpret)[0]

    def fwd(qkv, mask, num_heads, interpret):
        o, lse = _fused_forward(qkv, mask, num_heads, interpret)
        return o, (qkv, mask, o, lse)

    def bwd(num_heads, interpret, res, do):
        qkv, mask, o, lse = res
        return _fused_backward(qkv, mask, o, lse, do, num_heads,
                               interpret), None

    fused.defvjp(fwd, bwd)
    return jax.jit(fused, static_argnums=(2, 3))


def fused_attention(qkv, mask=None, *, num_heads: int,
                    interpret: bool = False):
    """The whole attention core as one kernel each way, from the packed
    projection to the output projection's input.

    qkv: (B, S, 3, H*D), the q, k and v projections side by side, each with
    its heads side by side (what ``SelfAttention``'s ``qkv`` layer writes);
    mask: (B, S) with 1 = valid key, or None. Returns (B, S, H*D).

    One grid cell is one batch row and one 128-lane group of heads (two of
    dimension 64, one of 128), read from ``qkv`` in place through the block
    index maps (as (B, 3, S, H*D): the layout the compiler gives the
    projection's result anyway, so the transpose moves nothing). The (S, S)
    scores, their softmax and, in the backward pass, dP and dS live in VMEM
    only. Products take the inputs' dtype with float32 accumulation, the
    softmax is float32, P and dS are cast to the inputs' dtype for their
    products: ``full_attention``'s arithmetic, with float32 scores. The
    backward kernel recomputes P from q, k and the saved log-sum-exp and
    writes dq, dk, dv into the packed gradient. The caller checks
    :func:`use_fused_attention` first."""
    import jax.numpy as jnp

    b, s_len, three, hd = qkv.shape
    assert three == 3, qkv.shape
    mask = jnp.ones((b, s_len), jnp.int32) if mask is None else mask
    return _build_fused()(qkv.transpose(0, 2, 1, 3),
                    mask.astype(jnp.int32)[:, None, :], int(num_heads),
                    bool(interpret))


# ---------------------------------------------------------------------------
# causal core in blocks: whole sequences, q and k of one width, v of another
# ---------------------------------------------------------------------------

# Positions a block, of queries and of keys alike; a length the kernels take
# is a multiple of it. One TPU v5e, one layer's core of 2 rows x 16 heads x
# 8,192 positions, 192 / 128 wide, bfloat16, ms a call (PERF.md section 6,
# PR 35; docs/chip_calls/pr35/tune.py): forward 22.1 / 11.3 / 7.35 at blocks
# of 256 / 512 / 1,024 (XLA's loops 23.0), forward + backward 41.7 / 26.3 /
# 21.9 (64.8). A larger block reads k and v fewer times and pays the
# accumulator's correction less often; 2,048 would hold 16 MB a float32 tile
# and compute 25% over the causal half (the diagonal blocks are computed
# whole: 12.5% at 1,024). Taking the forward block's keys 512 or 256 at a
# time read slower (+4 and +9 ms).
_CAUSAL_BLOCK = 1024
# the backward kernel keeps dq of one row and head, (T, D) float32, in VMEM
# while the key blocks pass: 16,384 x 256 lanes x 4 bytes is 16.8 MB
_CAUSAL_MAX_SEQ = 16384


def use_causal_attention(seq_len: int, qk_dim: int, v_dim: int) -> bool:
    """Whether ``dl/mla.causal_core`` takes the fused causal core: decided
    from the call's own shapes and the kernel's gate, as
    :func:`use_fused_attention` is. A length that is a multiple of the
    kernels' block and that the backward kernel's VMEM holds; queries and
    keys of whole or half lane groups, values of whole ones, two groups at
    most."""
    return (seq_len % _CAUSAL_BLOCK == 0
            and seq_len <= _CAUSAL_MAX_SEQ
            and qk_dim % (_LANES // 2) == 0 and v_dim % _LANES == 0
            and max(qk_dim, v_dim) <= 2 * _LANES
            and use_attn_pallas())


_NT = (((1,), (1,)), ((), ()))      # a . b^T
_NN = (((1,), (0,)), ((), ()))      # a . b
_TN = (((0,), (0,)), ((), ()))      # a^T . b


def _dot(a, b, dims):
    import jax
    import jax.numpy as jnp

    return jax.lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


def _index(shape, axis: int):
    import jax
    import jax.numpy as jnp

    return jax.lax.broadcasted_iota(jnp.int32, shape, axis)


def _causal_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref,
                       acc_ref, *, scale: float):
    """Query block ``i`` against key block ``j <= i``, the key blocks in
    turn: the running maximum, sum and weighted values of the query block
    stay in VMEM scratch; the diagonal block is the last and the only one
    masked."""
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    i, j = pl.program_id(2), pl.program_id(3)
    block = q_ref.shape[0]

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full(m_ref.shape, -jnp.inf, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    def update(diagonal: bool):
        s = _dot(q_ref[...], k_ref[...], _NT) * scale          # (Q, K)
        if diagonal:
            s = jnp.where(_index(s.shape, 1) <= _index(s.shape, 0), s,
                          -jnp.inf)
        m = m_ref[...]                                          # (Q, 1)
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        fix = jnp.exp(m - m_new)
        l_ref[...] = l_ref[...] * fix + p.sum(axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * fix + _dot(
            p.astype(v_ref.dtype), v_ref[...], _NN)
        m_ref[...] = m_new

    pl.when(j < i)(lambda: update(False))

    @pl.when(j == i)
    def _():
        update(True)
        l = l_ref[...]
        o_ref[...] = (acc_ref[...] / l).astype(o_ref.dtype)
        lse = m_ref[...] + jnp.log(l)
        # the rows' log-sum as a row: lane-dense in memory, and the form
        # the backward kernel reads, which works on transposed scores
        lse_ref[...] = _rows_of(jnp.broadcast_to(lse, (block, _LANES)))


def _causal_bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                       dq_ref, dk_ref, dv_ref, dq_acc, dk_acc, dv_acc, *,
                       scale: float):
    """Key block ``j`` against query block ``i >= j``, the query blocks in
    turn, scores transposed (keys down, queries across) as in
    :func:`_fused_bwd_kernel`: P is computed once for dv, dk and dq. dk and
    dv of the key block gather in VMEM scratch over the query blocks; dq of
    the whole row and head gathers there over the key blocks, a query
    block's rows complete once its diagonal block has passed."""
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    j, i = pl.program_id(2), pl.program_id(3)
    block, dtype = k_ref.shape[0], k_ref.dtype
    rows = pl.ds(pl.multiple_of(i * block, block), block)

    @pl.when((j == 0) & (i == 0))
    def _():
        dq_acc[...] = jnp.zeros(dq_acc.shape, jnp.float32)

    def update(diagonal: bool):
        q, k, do = q_ref[...], k_ref[...], do_ref[...]
        st = _dot(k, q, _NT) * scale                           # (K, Q)
        if diagonal:
            st = jnp.where(_index(st.shape, 0) <= _index(st.shape, 1), st,
                           -jnp.inf)
        pt = jnp.exp(st - lse_ref[:1])
        dv = _dot(pt.astype(dtype), do, _NN)                   # (K, Dv)
        dpt = _dot(v_ref[...], do, _NT)                        # (K, Q)
        dst = (pt * (dpt - delta_ref[...]) * scale).astype(dtype)
        dk = _dot(dst, q, _NN)                                 # (K, D)
        dq = dq_acc[rows] + _dot(dst, k, _TN)                  # (Q, D)
        if diagonal:        # the key block's first query block, and the
            dk_acc[...] = dk        # last key block of these queries
            dv_acc[...] = dv
            dq_ref[...] = dq.astype(dq_ref.dtype)
        else:
            dk_acc[...] += dk
            dv_acc[...] += dv
            dq_acc[rows] = dq

    pl.when(i > j)(lambda: update(False))
    pl.when(i == j)(lambda: update(True))

    @pl.when(i == pl.num_programs(3) - 1)
    def _():
        dk_ref[...] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)


def _causal_call(name: str, kernel, grid, ins, out, scratch, scale: float,
                 interpret: bool):
    """One of the two kernels over (row, head, outer block, inner block),
    the inner blocks in turn. ``ins`` and ``out`` pair each operand's block
    (its trailing two dimensions and the index map of the two block
    indices) with the array or its ``ShapeDtypeStruct``."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    spec = lambda dims, at: pl.BlockSpec(
        (None, None) + dims, lambda b, h, x, y: (b, h) + at(x, y))
    return pl.pallas_call(
        functools.partial(kernel, scale=scale),
        grid=grid,
        in_specs=[spec(*s) for s, _ in ins],
        out_specs=[spec(*s) for s, _ in out],
        out_shape=[shape for _, shape in out],
        scratch_shapes=[pltpu.VMEM(dims, dt) for dims, dt in scratch],
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
            vmem_limit_bytes=_FUSED_VMEM_BYTES),
        interpret=interpret,
        name=name,
    )(*(x for _, x in ins))


def _causal_forward(q, k, v, scale: float, interpret: bool):
    """q, k (B, H, T, D), v (B, H, T, Dv). Returns o (B, H, T, Dv) float32
    and the rows' log-sum (B, H, 8, T), eight equal rows."""
    import jax
    import jax.numpy as jnp

    b, h, t, d = q.shape
    dv, blk = v.shape[-1], _CAUSAL_BLOCK
    n = t // blk
    mine = lambda i, j: (i, 0)
    # a key block above the diagonal is not computed: it names the last
    # block needed, so nothing is fetched for it
    seen = lambda i, j: (jnp.minimum(j, i), 0)
    f32 = jnp.float32
    return _causal_call(
        "mla_causal_fwd", _causal_fwd_kernel, (b, h, n, n),
        [(((blk, d), mine), q), (((blk, d), seen), k),
         (((blk, dv), seen), v)],
        [(((blk, dv), mine), jax.ShapeDtypeStruct((b, h, t, dv), f32)),
         (((_STAT_ROWS, blk), lambda i, j: (0, i)),
          jax.ShapeDtypeStruct((b, h, _STAT_ROWS, t), f32))],
        [((blk, 1), f32), ((blk, 1), f32), ((blk, dv), f32)],
        scale, interpret)


def _causal_backward(q, k, v, do, lse, delta, scale: float, interpret: bool):
    """``do`` (B, H, T, Dv) in the inputs' dtype, ``lse`` as the forward
    kernel wrote it, ``delta`` (B, H, 1, T). Returns dq, dk, dv."""
    import jax
    import jax.numpy as jnp

    b, h, t, d = q.shape
    dv, blk = v.shape[-1], _CAUSAL_BLOCK
    n = t // blk
    mine = lambda j, i: (j, 0)
    # a query block above the diagonal names the key block's first
    live = lambda j, i: (jnp.maximum(i, j), 0)
    stat = lambda j, i: (0, jnp.maximum(i, j))
    f32 = jnp.float32
    like = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype)
    return _causal_call(
        "mla_causal_bwd", _causal_bwd_kernel, (b, h, n, n),
        [(((blk, d), live), q), (((blk, d), mine), k), (((blk, dv), mine), v),
         (((blk, dv), live), do), (((_STAT_ROWS, blk), stat), lse),
         (((1, blk), stat), delta)],
        [(((blk, d), mine), like(q)), (((blk, d), mine), like(k)),
         (((blk, dv), mine), like(v))],
        [((t, d), f32), ((blk, d), f32), ((blk, dv), f32)],
        scale, interpret)


@functools.cache
def _build_causal():
    """The differentiable causal core under a jit of its own, built once, as
    :func:`_build_fused` is: the layers of a model and their rematerialised
    copies trace and lower the two kernels once."""
    import jax

    @functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
    def causal(q, k, v, scale, interpret):
        return _causal_forward(q, k, v, scale, interpret)[0]

    def fwd(q, k, v, scale, interpret):
        o, lse = _causal_forward(q, k, v, scale, interpret)
        return o, (q, k, v, o, lse)

    def bwd(scale, interpret, kept, do):
        q, k, v, o, lse = kept
        delta = (o * do).sum(-1)[:, :, None, :]                # (B, H, 1, T)
        return _causal_backward(q, k, v, do.astype(v.dtype), lse, delta,
                                scale, interpret)

    causal.defvjp(fwd, bwd)
    return jax.jit(causal, static_argnums=(3, 4))


def causal_attention(q, k, v, *, scale: float, interpret: bool = False):
    """Causal softmax attention of whole sequences as one kernel each way.

    q, k ``(B, H, T, D)``, v ``(B, H, T, Dv)``, heads first, in one dtype;
    position ``t`` sees ``s <= t``. Returns ``(B, H, T, Dv)`` float32. The
    arithmetic of ``dl/mla._causal``, the XLA block loops this replaces on
    the chip: products in the inputs' dtype with float32 accumulation;
    scores, maximum, sum and log-sum in float32; P and dS cast to the
    inputs' dtype for their products; no block above the diagonal computed;
    the output and the log-sum kept for the backward pass, which computes a
    block's probabilities again. A block's scores, P, dP and dS live in VMEM
    only. The caller checks :func:`use_causal_attention` first."""
    return _build_causal()(q, k, v, float(scale), bool(interpret))
