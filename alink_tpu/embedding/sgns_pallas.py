"""Pallas TPU kernel: fused SGNS block gradients.

The sharded-engine hot loop (embedding/skipgram.py::_build_sgns_sharded)
pulls the center rows ``v`` (B, D) and the context+negative rows ``u``
((negs+1)·B, D) through the owner-routed APS, then runs
``_block_grads`` — whose XLA lowering materializes the (B, negs, D)
intermediates (``s_neg`` scores, ``g_neg * u_neg``, ``g_neg * v``) in HBM
between ops. This kernel fuses the whole gather→sigmoid→gradient block:
one grid cell holds an 8-row slice of ``v``/``u_pos`` plus ONE negative's
rows in VMEM, computes its dot products, sigmoids, and both gradient
contributions in registers, and accumulates ``grad_v`` by revisiting the
same output block across the negatives grid axis (sequential TPU grid ⇒
safe accumulation, the ``pallas_hist`` pattern). The (B, negs, D)
intermediates never exist.

The fusion boundary is the device-local compute between the collectives:
the APS ``pull``/``push`` exchanges (all_to_all) and the hot-cache psum
write-back stay outside — collectives cannot live inside a Pallas program.

Numerics: ``grad_v`` accumulates sequentially over negatives
(``g_pos·u_pos + g_0·u_0 + g_1·u_1 + …``) where the XLA path reduces
``(g_neg * u_neg).sum(1)`` in XLA's own order — deterministic both ways,
but not the same float summation order, so the parity contract is a pinned
fp32 tolerance (atol=1e-5), not bit-equality (tests/test_kernels.py).
Knob-off the caller compiles the untouched XLA path — byte-identical to
pre-kernel builds.

The tests run the same program under the Pallas interpreter on the
8-virtual-device CPU mesh. Gated by ``ALINK_SGNS_PALLAS`` through the
shared registry gate (native/kernels.py).
"""

from __future__ import annotations

_BB = 8        # row block = fp32 sublane tile
_LANES = 128   # lane width; D pads up to a multiple


def use_sgns_pallas() -> bool:
    """Gate for the fused block-gradient kernel: ``ALINK_SGNS_PALLAS``
    through the registry's shared parser (on by default on real TPU
    backends)."""
    from ..native.kernels import kernel_enabled

    return kernel_enabled("ALINK_SGNS_PALLAS")


def _pad_axis(x, mult: int, axis: int):
    import jax.numpy as jnp

    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def sgns_block_grads(v, u_pos, u_neg, *, interpret: bool = False):
    """Fused SGNS gradients for one block — drop-in for
    ``skipgram._block_grads`` (same shapes, same row order).

    v: (B, D) center rows; u_pos: (B, D) context rows;
    u_neg: (B, negs, D) negative rows. Returns ``(grad_v, grad_u)`` with
    ``grad_v`` (B, D) and ``grad_u`` ((negs+1)·B, D) laid out as
    ``concat(context rows, negative rows b-major)`` — exactly the id order
    ``push`` consumes (``concat(ctx, neg.reshape(-1))``)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    B, D = v.shape
    negs = u_neg.shape[1]
    v_p = _pad_axis(_pad_axis(v, _BB, 0), _LANES, 1)
    up_p = _pad_axis(_pad_axis(u_pos, _BB, 0), _LANES, 1)
    # negatives ride as the LEADING axis, (negs, B, D): Mosaic wants the
    # last two block dims (8, 128)-aligned or the array's own, and one
    # negative's 8-row slice is then a plain (8, D) trailing slab
    un_p = _pad_axis(_pad_axis(u_neg.transpose(1, 0, 2), _BB, 1), _LANES, 2)
    b_pad, d_pad = v_p.shape

    grid = (b_pad // _BB, negs)   # negatives grid-minor: grad_v block
    #                               revisits across n (safe accumulation)

    def kernel(v_ref, up_ref, un_ref, gv_ref, gup_ref, gun_ref):
        n = pl.program_id(1)
        vb = v_ref[:]                                   # (_BB, D)
        un = un_ref[0]                                  # (_BB, D)
        g_n = jax.nn.sigmoid((vb * un).sum(-1, keepdims=True))  # (_BB, 1)
        gun_ref[0] = g_n * vb

        @pl.when(n == 0)
        def _first():
            ub = up_ref[:]
            g_pos = jax.nn.sigmoid((vb * ub).sum(-1, keepdims=True)) - 1.0
            gup_ref[:] = g_pos * vb
            gv_ref[:] = g_pos * ub + g_n * un

        @pl.when(n > 0)
        def _accumulate():
            gv_ref[:] += g_n * un

    gv, gup, gun = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((_BB, d_pad), lambda r, n: (r, 0)),
            pl.BlockSpec((_BB, d_pad), lambda r, n: (r, 0)),
            pl.BlockSpec((1, _BB, d_pad), lambda r, n: (n, r, 0)),
        ],
        out_specs=[
            pl.BlockSpec((_BB, d_pad), lambda r, n: (r, 0)),
            pl.BlockSpec((_BB, d_pad), lambda r, n: (r, 0)),
            pl.BlockSpec((1, _BB, d_pad), lambda r, n: (n, r, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b_pad, d_pad), jnp.float32),
            jax.ShapeDtypeStruct((b_pad, d_pad), jnp.float32),
            jax.ShapeDtypeStruct((negs, b_pad, d_pad), jnp.float32),
        ],
        interpret=interpret,
    )(v_p, up_p, un_p)
    grad_v = gv[:B, :D]
    grad_u = jnp.concatenate(
        [gup[:B, :D],
         gun[:, :B, :D].transpose(1, 0, 2).reshape(B * negs, D)])
    return grad_v, grad_u
