"""Pallas TPU kernel: per-feature binned histogram accumulation.

The GBDT hot loop (SURVEY §7 hard-part #3; reference:
operator/common/tree/parallelcart/ConstructLocalHistogram.java — the
per-worker histogram the reference AllReduces). The XLA fallback is a
vmapped ``segment_sum`` (tree/grow.py); this kernel instead keeps the whole
(node×bin, feature-block) histogram resident in VMEM and accumulates row
blocks with one-hot × value products — the scatter becomes a streaming
compare+matvec, revisiting the same output block across the row grid
(sequential TPU grid ⇒ safe accumulation).

The tests run the same program under the Pallas interpreter on the
8-virtual-device CPU mesh.

Registered as ``tree.pallas_hist`` in the custom-kernel registry
(``native/kernels.py``); the gate and interpret-mode switches are the
registry's shared helpers so all kernels parse on/off/backend identically.
"""

from __future__ import annotations

from functools import partial

# shared registry gate: re-exported so existing importers of
# pallas_hist.interpret_mode keep working
from ..native.kernels import interpret_mode, kernel_enabled

import numpy as np

_ROWS = 512      # row block (grid-minor: revisits the output block)
_DBLK = 128      # feature block = lane width


def use_pallas_hist() -> bool:
    """Opt-in switch: on by default on a real TPU backend, forceable via
    ALINK_GBDT_PALLAS=1/0 — parsed by the registry's shared gate."""
    return kernel_enabled("ALINK_GBDT_PALLAS")


def _pad_to(x, m, axis):
    import numpy as _np

    pad = (-x.shape[axis]) % m
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    import jax.numpy as jnp

    return jnp.pad(x, widths)


@partial(
    __import__("jax").jit,
    static_argnames=("num_segments", "interpret"),
)
def pallas_histogram(ids, vals, *, num_segments: int,
                     interpret: bool = False):
    """``out[s, f] = sum_n vals[n] * (ids[n, f] == s)``.

    ids: (n, d) int32 segment ids per feature; vals: (n,) float32.
    Returns (num_segments, d) float32."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    n, d = ids.shape
    lb_pad = ((num_segments + 7) // 8) * 8
    ids_p = _pad_to(_pad_to(ids.astype(jnp.int32), _ROWS, 0), _DBLK, 1)
    # padded rows must not contribute: give them an out-of-range segment id
    n_pad = ids_p.shape[0]
    row_ok = (jnp.arange(n_pad) < n)[:, None]
    ids_p = jnp.where(row_ok, ids_p, lb_pad)
    vals_p = _pad_to(vals.astype(jnp.float32).reshape(-1, 1), _ROWS, 0)
    d_pad = ids_p.shape[1]

    grid = (d_pad // _DBLK, n_pad // _ROWS)   # rows grid-minor

    def kernel(ids_ref, vals_ref, out_ref):
        r = pl.program_id(1)

        @pl.when(r == 0)
        def _zero():
            out_ref[:] = jnp.zeros_like(out_ref)

        ids_blk = ids_ref[:]                   # (_ROWS, _DBLK)
        v = vals_ref[:]                        # (_ROWS, 1)

        # loop over segments: each iteration is a fully vectorized
        # (_ROWS, _DBLK) compare+mask+reduce on the VPU, and the output
        # write is sublane-dynamic (lane-dynamic indexing is not lowerable
        # on TPU — dimension-1 indices must be static multiples of 128)
        def segment(s, _):
            eq = (ids_blk == s).astype(jnp.float32)          # (_ROWS, _DBLK)
            contrib = (eq * v).sum(axis=0, keepdims=True)    # (1, _DBLK)
            out_ref[pl.dslice(s, 1), :] += contrib
            return 0

        jax.lax.fori_loop(0, lb_pad, segment, 0)

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((_ROWS, _DBLK), lambda f, r: (r, f)),
            pl.BlockSpec((_ROWS, 1), lambda f, r: (r, 0)),
        ],
        out_specs=pl.BlockSpec((lb_pad, _DBLK), lambda f, r: (0, f)),
        out_shape=jax.ShapeDtypeStruct((lb_pad, d_pad), jnp.float32),
        interpret=interpret,
    )(ids_p, vals_p)
    return out[:num_segments, :d]
