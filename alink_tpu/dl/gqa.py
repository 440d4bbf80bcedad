"""Grouped-query softmax attention over a key/value cache, in two kinds.

``attention`` (full): a query at position ``t`` sees every earlier position
of its sequence. Its cache holds each position's keys and values a key/value
head, keys transposed ``(slots, heads, width, positions)`` (a width of 192
along the lanes would be laid out by XLA with the positions minor, which the
kernel would have copied every call) and values ``(slots, heads, positions,
width)``, up to a row's length, and grows
with the sequence: :func:`cache_write` appends a chunk's positions after the
``length`` a row holds, then :func:`full_attend` reads the cache in blocks of
``KV_BLOCK`` positions with a running maximum and sum, so no score tensor
over the whole cache is ever written. A key/value head's queries are read as
one block of rows (``G`` query heads x ``T`` positions): keys and values are
never repeated. In a one-chip TPU process, at a cache length that is a
multiple of the block, that is one kernel (``attn_pallas.gqa_attention``,
``gqa_attend`` in a device trace) that reads each row's blocks up to its
length and no further; everywhere else it is XLA's loop over blocks here
(:func:`_blocks`), the kernel's reference.

Both kernels take a key/value head's query rows as whole sublane tiles of 8.
Where ``G x T`` is not one (a decode step of 6 query heads a key/value head:
6 rows), the rows are padded to the next tile with rows whose outputs are
dropped (:func:`tile_rows`); the rows added are counted on the host
(``attention.rows_padded``, :func:`rows_added`). Where ``G x T`` is whole
tiles, the call is as it was.

``window``: a query at ``t`` sees ``t - W + 1 .. t`` (``W`` the window) and,
where the layer has one, a learned sink logit a head joins the softmax's
denominator and weighs no value::

    o = sum_j e^{l_j} v_j / (e^{s_h} + sum_j e^{l_j})

Its cache is a ring of the last ``W`` positions, position ``p`` in slot ``p
% W``, laid out as the full layers' cache is; what a slot holds is known from
the row's length alone. A prompt chunk
(:func:`window_attend`) reads the ring and its own positions, never more,
then writes its own positions into their slots (:func:`ring_write`), which
leaves the ring holding the last ``W`` positions of the row, whatever the
chunk's length and wherever its edges fall; a decode step is a chunk of one.
The ring is never copied: a ring wider than the lane tiles a chunk reaches
has the chunk's slots alone written in place, a narrower one one select.

On one chip the window core is a kernel too (``attn_pallas.window_attention``,
``window_attend`` in a device trace), XLA's form everywhere else. Operands are
bfloat16 (``dtype``), scores and sums float32. The two cores run under the
device scopes ``gqa_core`` and ``window_core``, and which form each layer was
traced down is counted (``attention.gqa_fused_traces`` /
``attention.gqa_block_traces``, ``attention.window_fused_traces`` /
``attention.window_block_traces``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..common.metrics import metrics
from ..native.kernels import interpret_mode
from .attn_pallas import (_LANES, _SUBLANE, gqa_attention, use_gqa_attention,
                          use_window_attention, window_attention)
from .retention import einsum_f32

GQA_SCOPE = "gqa_core"
WINDOW_SCOPE = "window_core"
# cache positions a block of the full core: the scores of one block are
# (rows, key/value heads, query heads a group x chunk, block) float32, 268 MB
# at 32 rows of 4 x 16 x 128 queries; a full layer's cache is allocated in
# whole blocks
KV_BLOCK = 1024
# a masked score: finite, so that a block no key of which is seen leaves
# the running sums as they were instead of making them NaN
_NEG = -1e30


def tile_rows(rows: int) -> int:
    """A key/value head's ``rows`` query rows as the kernels take them: in
    whole sublane tiles."""
    return -(-rows // _SUBLANE) * _SUBLANE


def rows_added(kind: str, rows: int, qk_dim: int, v_dim: int,
               positions: int) -> int:
    """Query rows a key/value head's block gains in one call of ``kind``'s
    core (``attention`` over a cache of ``positions``, or ``window``) to
    reach a whole tile: 0 where the rows are whole tiles or no kernel takes
    the call. The kernels' own gates decide, as in the call."""
    tile = tile_rows(rows)
    on = (use_window_attention(tile, qk_dim, v_dim) if kind == "window" else
          use_gqa_attention(tile, qk_dim, v_dim, positions, KV_BLOCK))
    return tile - rows if on else 0


def _pad_rows(x, tile: int, axis: int, **how):
    """``x`` padded along ``axis`` (a head's query rows) to ``tile``."""
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, tile - x.shape[axis])
    return jnp.pad(x, pad, **how)


def cache_positions(positions: int, chunk: int) -> int:
    """What a full layer's cache allocates for ``positions`` asked and
    prompt chunks of ``chunk``: room for a chunk past the last position
    (:func:`cache_write` writes whole chunks), in whole blocks of
    :data:`KV_BLOCK`, or as asked below one."""
    need = positions + chunk
    return -(-need // KV_BLOCK) * KV_BLOCK if need > KV_BLOCK else need


def cache_write(K, V, length, k, v, valid):
    """A chunk's keys ``k (B,T,Hkv,D)`` and values ``v (B,T,Hkv,Dv)``
    written to each row's cache ``K (B,Hkv,D,P)``, ``V (B,Hkv,P,Dv)`` at the
    ``length (B,)`` positions it holds. Returns the caches and the new
    lengths, which count the valid positions alone. A row's chunk goes in
    whole, one update of a slice a row, in place: a row's trailing padding
    lands past its length, where nothing reads it and its later positions
    overwrite it, and the allocation (:func:`cache_positions`) leaves room
    for a chunk past the last position, so no slice is moved back over
    what the row holds. (A scatter of the positions alone was relaid out by
    XLA, whole cache and back, in every step.)"""
    B, T = valid.shape
    kt = k.astype(K.dtype).transpose(0, 2, 3, 1)                 # (B,h,D,T)
    vt = v.astype(V.dtype).transpose(0, 2, 1, 3)                 # (B,h,T,Dv)
    zero = jnp.zeros((), length.dtype)
    for b in range(B):
        K = jax.lax.dynamic_update_slice(K, kt[b:b + 1], (b, zero, zero, length[b]))
        V = jax.lax.dynamic_update_slice(V, vt[b:b + 1], (b, zero, length[b], zero))
    return K, V, length + valid.sum(axis=1).astype(length.dtype)


def _grouped(q, hkv: int, dtype):
    """``q (B,T,Hq,D)`` as a key/value head's block of rows ``(B,Hkv,G*T,D)``,
    row ``g*T + t``."""
    B, T, Hq, D = q.shape
    return q.astype(dtype).reshape(B, T, hkv, Hq // hkv, D).transpose(
        0, 2, 3, 1, 4).reshape(B, hkv, Hq // hkv * T, D)


def _ungrouped(o, T: int):
    """The cores' ``(B,Hkv,G*T,Dv)`` back to ``(B,T,Hq,Dv)``."""
    B, hkv, R, Dv = o.shape
    return o.reshape(B, hkv, R // T, T, Dv).transpose(0, 3, 1, 2, 4).reshape(
        B, T, hkv * (R // T), Dv)


def full_attend(q, k, v, K, V, length, valid, *, scale: float, dtype):
    """One full-attention layer's core over a chunk (``T`` positions a row;
    a decode step is ``T = 1``): the chunk's keys and values written to the
    cache, then each query over the cache up to and including itself.

    q ``(B,T,Hq,D)`` rotated, k ``(B,T,Hkv,D)``, v ``(B,T,Hkv,Dv)``;
    K, V, length: the cache (:func:`cache_write`); valid ``(B,T)``. Returns
    ``(B,T,Hq,Dv)`` float32 and the cache."""
    B, T, Hq, _ = q.shape
    hkv, P = V.shape[1], V.shape[2]
    start = length
    K, V, length = cache_write(K, V, length, k, v, valid)
    # what a row's queries read: up to its chunk's last valid position, and
    # nothing where the chunk holds none of its positions
    hi = jnp.where(valid.any(axis=1), length, 0).astype(jnp.int32)
    rows = _grouped(q, hkv, dtype)
    R = rows.shape[2]
    tile = tile_rows(R)
    fused = use_gqa_attention(tile, q.shape[-1], V.shape[-1], P, KV_BLOCK)
    with jax.named_scope(GQA_SCOPE):
        if fused:
            metrics.incr("attention.gqa_fused_traces")
            if tile != R:   # row r >= R reads as row r % T: dropped below
                rows = _pad_rows(rows, tile, 2)
            o = gqa_attention(rows, K, V, start.astype(jnp.int32), hi, T,
                              scale=scale, block=KV_BLOCK,
                              interpret=interpret_mode())
            if tile != R:
                o = o[:, :, :R]
        else:
            metrics.incr("attention.gqa_block_traces")
            o = _blocks(rows, K.astype(dtype), V.astype(dtype),
                        start.astype(jnp.int32), hi, T, float(scale),
                        min(KV_BLOCK, P))
    return _ungrouped(o, T), K, V, length


def _blocks(q, K, V, start, hi, T: int, scale: float, block: int):
    """The full core in XLA: ``q (B,Hkv,R,D)`` (row ``r`` at position
    ``start + r % T``) against the cache ``K (B,Hkv,D,P)``, ``V
    (B,Hkv,P,Dv)``, its positions ``< hi (B,)`` and at or
    before its own, the cache's blocks in turn as far as the longest row
    needs (a loop whose length the batch decides), a running maximum, sum
    and weighted values a row. Returns ``(B,Hkv,R,Dv)`` float32."""
    B, H, R, _ = q.shape
    P, Dv = V.shape[2], V.shape[-1]
    qpos = start[:, None] + jnp.arange(R)[None, :] % T             # (B,R)
    pad = (-P) % block
    if pad:
        K = jnp.pad(K, ((0, 0), (0, 0), (0, 0), (0, pad)))
        V = jnp.pad(V, ((0, 0), (0, 0), (0, pad), (0, 0)))

    def one(j, carry):
        m, l, acc = carry
        k_j = jax.lax.dynamic_slice_in_dim(K, j * block, block, axis=3)
        v_j = jax.lax.dynamic_slice_in_dim(V, j * block, block, axis=2)
        s = einsum_f32("bhrd,bhdk->bhrk", q, k_j) * scale
        kpos = j * block + jnp.arange(block)
        ok = ((kpos[None, None, :] <= qpos[:, :, None])
              & (kpos[None, None, :] < hi[:, None, None]))[:, None]
        s = jnp.where(ok, s, _NEG)
        m_new = jnp.maximum(m, s.max(-1))
        p = jnp.where(ok, jnp.exp(s - m_new[..., None]), 0.0)
        fix = jnp.exp(m - m_new)
        acc = acc * fix[..., None] + einsum_f32("bhrk,bhkd->bhrd",
                                                p.astype(V.dtype), v_j)
        return m_new, l * fix + p.sum(-1), acc

    f32 = jnp.float32
    n = (jnp.max(hi) + block - 1) // block
    m, l, acc = jax.lax.fori_loop(0, n, one, (
        jnp.full((B, H, R), _NEG, f32), jnp.zeros((B, H, R), f32),
        jnp.zeros((B, H, R, Dv), f32)))
    return acc / jnp.where(l > 0, l, 1.0)[..., None]


def ring_positions(length, W: int):
    """The position each of a ring's ``W`` slots holds for rows of
    ``length (B,)`` positions: the latest ``p < length`` with ``p % W ==
    slot``, negative where the row is shorter than that. ``(B,W)``."""
    last = length[:, None] - 1
    return last - jnp.mod(last - jnp.arange(W)[None, :], W)


def ring_write(ring_k, ring_v, length, k, v, n):
    """A chunk's keys ``k (B,Hkv,D,T)`` and values ``v (B,Hkv,T,Dv)``, in
    the ring's own layout, written to slots ``p % W`` of each row's ring
    ``ring_k (B,Hkv,D,W)``, ``ring_v (B,Hkv,W,Dv)``: the first ``n (B,)``
    positions from ``length (B,)`` on, the latest where two take one slot.
    Only those slots change: the chunk's positions past a row's ``n`` leave
    theirs as they were (in a ring they hold live keys).

    A ring wider than the lane tiles a chunk's slots can reach has those
    slots alone written in place, a row at a time (:func:`_ring_slices`).
    A narrower one (MiMo's 128) is written whole by one select, the chunk
    moved to its slots by a product with a one-hot matrix of its positions,
    exact in any precision: a slice from a run-time slot runs at 15-35 GB/s
    on a v5e, so there it cost more than the pass (a MiMo prompt chunk's
    core 1.56 against 1.07 ms), where over Trinity's 4,096 the pass is the
    cost (1.89 against 1.38 ms)."""
    W, T = ring_k.shape[3], k.shape[3]
    length, n = length.astype(jnp.int32), jnp.minimum(n.astype(jnp.int32), T)
    s0 = jnp.mod(length, W)
    if (min(T, W) + 2 * _LANES - 2) // _LANES * _LANES < W:
        return (_ring_slices(ring_k, k, s0, n, axis=3),
                _ring_slices(ring_v, v, s0, n, axis=2))
    # the chunk's latest valid position that lands on each slot
    last = n[:, None] - 1
    j = last - jnp.mod(last - jnp.arange(W)[None, :] + s0[:, None], W)  # (B,W)
    ok = j >= 0
    hot = (ok[..., None] & (j[..., None] == jnp.arange(T))).astype(k.dtype)
    moved = lambda spec, x, ring: jnp.einsum(
        spec, x, hot, precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32).astype(ring.dtype)
    return (jnp.where(ok[:, None, None, :], moved("bhdt,bwt->bhdw", k, ring_k),
                      ring_k),
            jnp.where(ok[:, None, :, None], moved("bhtd,bwt->bhwd", v, ring_v),
                      ring_v))


def _ring_slices(ring, x, s0, m, *, axis: int):
    """``x``'s positions ``j < m (B,)`` along ``axis`` (``T < W`` of them)
    written to slots ``(s0 + j) % W`` of ``ring`` along it, in place, a row
    at a time: a read-modify-write of ``T`` slots from ``s0``, the start
    held where the slice would pass the ring's end (``dynamic_update_slice``
    would clamp it there unasked), then of slots ``0 .. T - 2`` for the
    positions that wrap round."""
    B, W, T = ring.shape[0], ring.shape[axis], x.shape[axis]
    pad = [(0, 0)] * ring.ndim
    pad[axis] = (T, T)
    x = jnp.pad(x.astype(ring.dtype), pad)       # position j now at j + T
    zero = jnp.zeros((), jnp.int32)

    def write(ring, b, at, j0, size):
        """Slots ``at .. at + size`` of row ``b`` take the chunk's positions
        from ``j0`` on, where they are valid."""
        j = j0 + jnp.arange(size)
        mask = [1] * ring.ndim
        mask[axis] = size
        ok = ((j >= 0) & (j < m[b])).reshape(mask)
        new = jax.lax.dynamic_slice_in_dim(x[b:b + 1], jnp.clip(j0, -T, T) + T,
                                           size, axis)
        start = [jnp.asarray(b, jnp.int32)] + [zero] * (ring.ndim - 1)
        start[axis] = at
        old = jax.lax.dynamic_slice(ring, start, new.shape)
        return jax.lax.dynamic_update_slice(ring, jnp.where(ok, new, old),
                                            start)

    first = jnp.minimum(s0, W - T)
    for b in range(B):
        ring = write(ring, b, first[b], first[b] - s0[b], T)
    if T > 1:   # one position never wraps round
        for b in range(B):
            ring = write(ring, b, zero, W - s0[b], T - 1)
    return ring


def window_attend(q, k, v, ring_k, ring_v, length, valid, sink, *,
                  window: int, scale: float, dtype):
    """One window layer's core over a chunk (``T = 1``: a decode step): each
    query over the ring's positions and the chunk's own that lie in its
    window, the sink (``(Hq,)`` float32, or None) in the denominator; then
    the chunk's valid positions written into the ring (:func:`ring_write`),
    which then holds the row's last ``W`` positions. The core reads the ring
    as it stood before the chunk: written first, a chunk's later positions
    would take the slots of keys still inside its earlier queries' window.

    q ``(B,T,Hq,D)`` rotated, k ``(B,T,Hkv,D)``, v ``(B,T,Hkv,Dv)``;
    ring_k ``(B,Hkv,D,W)``, ring_v ``(B,Hkv,W,Dv)``; length ``(B,)``: the
    row's positions before the chunk; valid ``(B,T)``, a prefix of each
    row. Returns ``(B,T,Hq,Dv)`` float32, the ring and the new lengths.

    One algorithm, two programs, chosen by the call's own shapes and the
    kernel's gate: in a one-chip TPU process
    ``attn_pallas.window_attention`` (``window_attend`` in a device trace),
    the ring and the chunk as operands of their own and the scores in VMEM;
    everywhere else XLA's form here (:func:`_window_xla`), the kernel's
    reference. Which one a layer was traced down is counted
    (``attention.window_fused_traces`` / ``attention.window_block_traces``)."""
    B, T, Hq, D = q.shape
    hkv, W = ring_v.shape[1], ring_v.shape[2]
    G = Hq // hkv
    tile = tile_rows(G * T)
    fused = use_window_attention(tile, D, v.shape[-1])
    # the chunk in the ring's layout: the kernel's operands and what is written
    kt = k.astype(dtype).transpose(0, 2, 3, 1)                     # (B,h,D,T)
    vt = v.astype(dtype).transpose(0, 2, 1, 3)                     # (B,h,T,Dv)
    n = valid.sum(axis=1).astype(length.dtype)
    # a decode step's one position takes the slot of the one W back, which
    # its query does not see: written first, the ring the core reads is the
    # written one, its layout pinned by the core (written after, XLA laid the
    # whole ring out anew round the one-slot update, in and out, every step)
    before = T == 1
    with jax.named_scope(WINDOW_SCOPE):
        if before:
            ring_k, ring_v = ring_write(ring_k, ring_v, length, kt, vt, n)
        qpos = length[:, None] + jnp.arange(T)[None, :]                # (B,T)
        held = ring_positions(length + n if before else length, W)     # (B,W)
        own = valid & (not before)              # a key the ring holds: once
        if fused:
            metrics.incr("attention.window_fused_traces")
            sinks = jnp.full((hkv, G), _NEG, jnp.float32) if sink is None \
                else sink.astype(jnp.float32).reshape(hkv, G)
            rows = _grouped(q, hkv, dtype)
            rows_at = jnp.tile(qpos, (1, G))[..., None]
            rows_sink = jnp.repeat(sinks, T, axis=1)[..., None]
            if tile != G * T:   # rows added: zero queries at the last row's
                rows = _pad_rows(rows, tile, 2)      # position with no sink,
                rows_at = _pad_rows(rows_at, tile, 1, mode="edge")  # dropped
                rows_sink = _pad_rows(rows_sink, tile, 1, constant_values=_NEG)
            o = window_attention(
                rows, ring_k, ring_v, kt, vt, rows_at,
                jnp.where(held >= 0, held, -1)[:, None],
                jnp.where(own, qpos, -1)[:, None], rows_sink, scale=scale,
                window=window, interpret=interpret_mode())
            o = _ungrouped(o if tile == G * T else o[:, :, :G * T], T)
        else:
            metrics.incr("attention.window_block_traces")
            o = _window_xla(q, k, v, ring_k, ring_v, qpos, held, own, sink,
                            window=window, scale=scale, dtype=dtype)
        if not before:
            ring_k, ring_v = ring_write(ring_k, ring_v, length, kt, vt, n)
    return o, ring_k, ring_v, length + n


def _window_xla(q, k, v, ring_k, ring_v, qpos, held, valid, sink, *,
                window: int, scale: float, dtype):
    """The window core in XLA: the ring's scores and the chunk's own apart,
    the sink's term added to the denominator. Returns ``(B,T,Hq,Dv)``."""
    B, T, Hq, D = q.shape
    hkv = ring_v.shape[1]
    G = Hq // hkv
    f32 = jnp.float32
    qg = q.astype(dtype).reshape(B, T, hkv, G, D)
    seen_ring = (held[:, None, :] >= 0) & (
        qpos[:, :, None] - held[:, None, :] < window)                  # (B,T,W)
    own = jnp.arange(T)
    seen_own = ((own[None, :] <= own[:, None])
                & (own[:, None] - own[None, :] < window))[None] \
        & valid[:, None, :]                                             # (B,T,T)
    s_ring = einsum_f32("bthgd,bhdw->bhgtw", qg, ring_k.astype(dtype))
    s_own = einsum_f32("bthgd,bshd->bhgts", qg, k.astype(dtype))
    mask = lambda s, ok: jnp.where(ok[:, None, None], s * scale, _NEG)
    s_ring, s_own = mask(s_ring, seen_ring), mask(s_own, seen_own)
    m = jnp.maximum(s_ring.max(-1), s_own.max(-1))                     # (B,h,g,T)
    if sink is not None:
        s_sink = sink.astype(f32).reshape(1, hkv, G, 1)
        m = jnp.maximum(m, s_sink)
    p_ring = jnp.where(seen_ring[:, None, None], jnp.exp(s_ring - m[..., None]),
                       0.0)
    p_own = jnp.where(seen_own[:, None, None], jnp.exp(s_own - m[..., None]), 0.0)
    den = p_ring.sum(-1) + p_own.sum(-1)
    if sink is not None:
        den = den + jnp.exp(s_sink - m)
    num = (einsum_f32("bhgtw,bhwd->bthgd", p_ring.astype(dtype),
                      ring_v.astype(dtype))
           + einsum_f32("bhgts,bshd->bthgd", p_own.astype(dtype),
                        v.astype(dtype)))
    o = num / jnp.where(den > 0, den, 1.0).transpose(0, 3, 1, 2)[..., None]
    return o.reshape(B, T, Hq, -1)
