"""Pallas TPU kernel: a prompt chunk's passage through the retention state.

What :func:`alink_tpu.dl.retention.retention_chunk` sends through the state
of one row and key/value head is a read for the chunk's queries and an update
from its keys::

    num_t = phi(q_t) e^{c_t} S        den_t = phi(q_t) e^{c_t} z
    S' = e^{c_T} S + sum_t phi(k_t) e^{c_T - c_t} v_t^T
    z' = e^{c_T} z + sum_t phi(k_t) e^{c_T - c_t}

``phi`` of a 128-wide vector has 8,256 entries, so in XLA's form phi of a
chunk's queries is the largest tensor of the model and is written to memory
four times to feed one matrix product. ``power_embed`` lays the pairs out by
cyclic distance: rows ``s*D .. s*D + D - 1`` of the state hold the pairs
``(a, a + s mod D)``, ``s`` from 0 to ``D/2``, the last distance half a block.
So here one grid cell (one row, one key/value head) holds its state in VMEM
and walks the distances: ``x * roll(x, s) * coef_s`` is made for the cell's
queries and keys a distance at a time, rounded once to the products' dtype,
multiplied with the distance's ``D x D`` block of ``S`` and its ``D`` of
``z``, and the block is written back updated. phi never leaves VMEM, and the
state is read once and written once, in the cache's own layout and in place
(the normaliser, a hundredth of it, goes through padded to a row a distance).

Numerics are those of the XLA form: phi in float32, one rounding to ``dtype``
before each product, the state rounded to ``dtype`` for the read alone,
float32 sums and a float32 state; phi's factors are multiplied in
``power_embed``'s order, so the rounded phi is the XLA form's bit for bit.
What differs is the order of the float32 sums (a distance at a time here, one
product over all of phi there), so the parity contract is a pinned tolerance
(``tests/test_retention_pallas.py``), not bit-equality.

Gated by ``ALINK_RETENTION_PALLAS`` through the shared registry gate
(native/kernels.py): on by default in a one-chip TPU process.
"""

from __future__ import annotations

import functools
import math

_LANES = 128
_SUBLANE = 8
# a cell's state is 4.2 MB and the pipeline holds it coming in and going out
# beside 4 MB of queries, keys and outputs: 19.1 MB by Mosaic's account, over
# the 16 MB a kernel is given unasked. v5e's VMEM is 128 MiB. (The distances
# in groups as a grid axis fit the 16 MB and read 5% slower: PERF.md, PR 37.)
_VMEM_BYTES = 48 * 1024 * 1024


def use_retention_pallas() -> bool:
    from ..native.kernels import kernel_enabled

    return kernel_enabled("ALINK_RETENTION_PALLAS")


def use_chunk_kernel(chunk_len: int, head_dim: int) -> bool:
    """Whether ``retention_chunk`` takes the kernel: decided from the call's
    own shapes and the kernel's gate. The head one whole lane group wide, so
    that a distance's block of the state is one ``128 x 128`` tile and a roll
    along the lanes is a roll of the head; the chunk whole sublane tiles."""
    return (head_dim == _LANES and chunk_len % _SUBLANE == 0
            and use_retention_pallas())


def _chunk_kernel(xq_ref, eq_ref, xk_ref, ek_ref, v_ref, e_ref, S_ref, z_ref,
                  num_ref, den_ref, So_ref, zo_ref, *, dtype):
    """One row and key/value head. ``xq (G,T,D)`` the queries, ``xk (T,D)``
    the keys, ``eq``, ``ek (T,D)`` their positions' decays ``e^{c_t}`` and
    ``e^{c_T - c_t}`` on every lane, all float32; ``v (T,D)`` in ``dtype``;
    ``e (1,D)`` the whole chunk's decay; ``S (P,D)`` and ``z (R,D)``, the
    normaliser a distance a row, its last row half zeros."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    groups, _, d = xq_ref.shape
    half = d // 2
    f32 = jnp.float32
    # power_embed's coefficients, as it computes them
    squares = np.float32(1.0) / np.float32(math.sqrt(d))
    pairs = np.float32(math.sqrt(2.0)) / np.float32(math.sqrt(d))
    e = e_ref[...]
    v = v_ref[...]
    num_ref[...] = jnp.zeros(num_ref.shape, f32)
    den_ref[...] = jnp.zeros(den_ref.shape, f32)

    def phi_at(x_ref, decay_ref, s, coef, width=d):
        # roll(x, d - s)[a] = x[(a + s) mod d], the pair's other member; the
        # factors in power_embed's order, so that phi rounds as it does there
        x = x_ref[...]
        return (x * pltpu.roll(x, (d - s) % d, 1) * coef
                * decay_ref[...]).astype(dtype)[:, :width]

    def through(s, coef, rows, width=d):
        """Distance ``s``, whose pairs are ``rows`` of the state and the
        first ``width`` lanes of the normaliser's row ``s``: the queries'
        read, then the keys' update."""
        Ss, zs = S_ref[rows, :], z_ref[pl.ds(s, 1), :width]
        Sd, zd = Ss.astype(dtype), zs.astype(dtype).astype(f32)
        for g in range(groups):
            pq = phi_at(xq_ref.at[g], eq_ref, s, coef, width)
            num_ref[g] += jnp.dot(pq, Sd, preferred_element_type=f32)
            den_ref[g, :, :width] += pq.astype(f32) * zd
        pk = phi_at(xk_ref, ek_ref, s, coef, width)
        So_ref[rows, :] = e * Ss + jax.lax.dot_general(
            pk, v, (((0,), (0,)), ((), ())), preferred_element_type=f32)
        zo_ref[pl.ds(s, 1), :width] = e[:, :width] * zs + pk.astype(f32).sum(
            0, keepdims=True)

    def full(s, carry):
        through(s, jnp.where(s == 0, squares, pairs),
                pl.ds(pl.multiple_of(s * d, d), d))
        return carry

    jax.lax.fori_loop(0, half, full, 0)
    # the last distance holds each pair twice: its first half is kept
    zo_ref[half:, half:] = jnp.zeros((1, d - half), f32)
    through(half, pairs, slice(half * d, half * d + half), half)


@functools.cache
def _build_chunk(dtype_name: str, interpret: bool):
    """The kernel under a jit of its own, built once a dtype: the layers of
    an unrolled stack trace and lower it once a program, not once a layer."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    dtype = jnp.dtype(dtype_name)
    f32 = jnp.float32

    def through_state(q, k, v, cum, S, z):
        B, Hkv, G, T, D = q.shape
        P, R = S.shape[2], D // 2 + 1
        total = cum[..., -1:]
        lanes = lambda c: jnp.broadcast_to(
            jnp.exp(c)[..., None], c.shape + (D,))
        zr = jnp.pad(z, ((0, 0), (0, 0), (0, R * D - P))).reshape(B, Hkv, R, D)
        cell = lambda *dims: pl.BlockSpec(
            (None, None) + dims, lambda b, j: (b, j) + (0,) * len(dims))
        num, den, Sn, zn = pl.pallas_call(
            functools.partial(_chunk_kernel, dtype=dtype),
            grid=(B, Hkv),
            in_specs=[cell(G, T, D), cell(T, D), cell(T, D), cell(T, D),
                      cell(T, D), cell(1, D), cell(P, D), cell(R, D)],
            out_specs=[cell(G, T, D), cell(G, T, D), cell(P, D), cell(R, D)],
            out_shape=[jax.ShapeDtypeStruct((B, Hkv, G, T, D), f32),
                       jax.ShapeDtypeStruct((B, Hkv, G, T, D), f32),
                       jax.ShapeDtypeStruct(S.shape, f32),
                       jax.ShapeDtypeStruct(zr.shape, f32)],
            input_output_aliases={6: 2, 7: 3},
            compiler_params=None if interpret else pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel"),
                vmem_limit_bytes=_VMEM_BYTES),
            interpret=interpret,
            name="retention_chunk_state",
        )(q.astype(f32), lanes(cum), k.astype(f32), lanes(total - cum),
          v.astype(dtype), lanes(total), S, zr)
        return num, den.sum(-1), Sn, zn.reshape(B, Hkv, R * D)[..., :P]

    return jax.jit(through_state)


def chunk_through_state(q, k, v, cum, S, z, *, dtype, interpret: bool = False):
    """The state's part of one prompt chunk as one kernel.

    Heads first: q ``(B,Hkv,G,T,D)``, k, v ``(B,Hkv,T,D)``, padding already
    masked out of ``k``; cum ``(B,Hkv,T)`` float32, the running sum of the
    gates' logs; S ``(B,Hkv,P,D)`` and z ``(B,Hkv,P)`` float32, the state
    before the chunk. Returns what the state adds to the chunk's numerators
    ``(B,Hkv,G,T,D)`` and normalisers ``(B,Hkv,G,T)``, and the state after
    the chunk. The caller checks :func:`use_chunk_kernel` first."""
    import jax.numpy as jnp

    return _build_chunk(jnp.dtype(dtype).name, bool(interpret))(
        q, k, v, cum, S, z)
