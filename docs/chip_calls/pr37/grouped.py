"""The way not taken: the chunk kernel of ``dl/retention_pallas.py`` with the
65 cyclic distances in five groups of 13 as a third, sequential grid axis, so
that a grid step holds 13 blocks of the state (0.85 MB; in and out, twice
each, 3.4 MB) and fits the 16 MB a kernel is given unasked. 8,256 rows are
64.5 blocks of 128, so the last group's block is ragged (its last 64 rows lie
beyond the array) and the numerators and normalisers stay resident across the
groups. On the chip: milliseconds a layer and chunk beside the kernel the
program runs, and the largest difference between the two.

    chiprun --chips 1 --timeout 900 -- python docs/chip_calls/pr37/grouped.py

On the CPU (``JAX_PLATFORMS=cpu ... grouped.py check``) it is compared at a
small size under the Pallas interpreter and nothing is timed.
"""

import functools
import json
import math
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

sys.path.insert(0, os.getcwd())
from alink_tpu.dl import retention_pallas as RP  # noqa: E402

GROUP = 13      # distances a grid step; 5 x 13 = 65


def _kernel(xq_ref, eq_ref, xk_ref, ek_ref, v_ref, e_ref, S_ref, z_ref,
            num_ref, den_ref, So_ref, zo_ref, *, dtype):
    groups, _, d = xq_ref.shape
    half = d // 2
    f32 = jnp.float32
    squares = np.float32(1.0) / np.float32(math.sqrt(d))
    pairs = np.float32(math.sqrt(2.0)) / np.float32(math.sqrt(d))
    g, last = pl.program_id(2), pl.num_programs(2) - 1
    e, v = e_ref[...], v_ref[...]

    @pl.when(g == 0)
    def _():
        num_ref[...] = jnp.zeros(num_ref.shape, f32)
        den_ref[...] = jnp.zeros(den_ref.shape, f32)

    def phi_at(x_ref, decay_ref, s, coef, width):
        x = x_ref[...]
        return (x * pltpu.roll(x, (d - s) % d, 1) * coef
                * decay_ref[...]).astype(dtype)[:, :width]

    def through(s, coef, rows, width=d):
        Ss, zs = S_ref[rows, :], z_ref[pl.ds(s, 1), :width]
        Sd, zd = Ss.astype(dtype), zs.astype(dtype).astype(f32)
        for h in range(groups):
            pq = phi_at(xq_ref.at[h], eq_ref, s, coef, width)
            num_ref[h] += jnp.dot(pq, Sd, preferred_element_type=f32)
            den_ref[h, :, :width] += pq.astype(f32) * zd
        pk = phi_at(xk_ref, ek_ref, s, coef, width)
        So_ref[rows, :] = e * Ss + jax.lax.dot_general(
            pk, v, (((0,), (0,)), ((), ())), preferred_element_type=f32)
        zo_ref[pl.ds(s, 1), :width] = e[:, :width] * zs + pk.astype(f32).sum(
            0, keepdims=True)

    def full(i, carry):
        s = g * GROUP + i
        through(s, jnp.where(s == 0, squares, pairs),
                pl.ds(pl.multiple_of(i * d, d), d))
        return carry

    jax.lax.fori_loop(0, GROUP - 1, full, 0)

    @pl.when(g < last)
    def _():
        full(GROUP - 1, 0)

    @pl.when(g == last)
    def _():
        zo_ref[half:, half:] = jnp.zeros((1, d - half), f32)
        at = (GROUP - 1) * d
        through(half, pairs, slice(at, at + half), half)


def grouped(q, k, v, cum, S, z, *, dtype, interpret=False):
    """``retention_pallas.chunk_through_state``'s operands and results."""
    f32 = jnp.float32
    B, Hkv, G, T, D = q.shape
    P, R = S.shape[2], D // 2 + 1
    assert R == 5 * GROUP
    total = cum[..., -1:]
    lanes = lambda c: jnp.broadcast_to(jnp.exp(c)[..., None], c.shape + (D,))
    zr = jnp.pad(z, ((0, 0), (0, 0), (0, R * D - P))).reshape(B, Hkv, R, D)
    cell = lambda *dims: pl.BlockSpec(
        (None, None) + dims, lambda b, j, g: (b, j) + (0,) * len(dims))
    state = pl.BlockSpec((None, None, GROUP * D, D),
                         lambda b, j, g: (b, j, g, 0))
    num, den, Sn, zn = pl.pallas_call(
        functools.partial(_kernel, dtype=jnp.dtype(dtype)),
        grid=(B, Hkv, R // GROUP),
        in_specs=[cell(G, T, D), cell(T, D), cell(T, D), cell(T, D),
                  cell(T, D), cell(1, D), state, cell(R, D)],
        out_specs=[cell(G, T, D), cell(G, T, D), state, cell(R, D)],
        out_shape=[jax.ShapeDtypeStruct((B, Hkv, G, T, D), f32),
                   jax.ShapeDtypeStruct((B, Hkv, G, T, D), f32),
                   jax.ShapeDtypeStruct(S.shape, f32),
                   jax.ShapeDtypeStruct(zr.shape, f32)],
        input_output_aliases={6: 2, 7: 3},
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="retention_chunk_state_grouped",
    )(q.astype(f32), lanes(cum), k.astype(f32), lanes(total - cum),
      v.astype(dtype), lanes(total), S, zr)
    return num, den.sum(-1), Sn, zn.reshape(B, Hkv, R * D)[..., :P]


def operands(B, T, HKV, G, D=128, seed=37):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    P = D * (D + 1) // 2
    lg = jax.nn.log_sigmoid(jax.random.normal(ks[3], (B, HKV, T)) + 7.0)
    return (jax.random.normal(ks[0], (B, HKV, G, T, D)),
            jax.random.normal(ks[1], (B, HKV, T, D)),
            jax.random.normal(ks[2], (B, HKV, T, D)), jnp.cumsum(lg, axis=-1),
            jax.random.normal(ks[4], (B, HKV, P, D)),
            jnp.abs(jax.random.normal(ks[5], (B, HKV, P))) + 1.0)


def main():
    check = sys.argv[1:] == ["check"]
    dtype = jnp.float32 if check else jnp.bfloat16
    args = operands(1, 16, 2, 2) if check else operands(16, 256, 8, 5)
    forms = {"whole_cell_48MiB": lambda *a: RP.chunk_through_state(
                 *a, dtype=dtype, interpret=check),
             "grouped_13_default_vmem": lambda *a: grouped(
                 *a, dtype=dtype, interpret=check)}
    out, rows = {}, []
    for name, form in forms.items():
        f = jax.jit(form, donate_argnums=() if check else (4, 5))
        copy = lambda: tuple(x + 0.0 for x in args[4:])
        got = f(*args[:4], *copy())
        out[name] = [np.asarray(x) for x in got]
        if not check:
            state = copy()
            jax.block_until_ready(state)
            t0 = time.perf_counter()
            for _ in range(10):
                state = f(*args[:4], *state)[2:]
            jax.block_until_ready(state)
            rows.append({"name": name, "layer_chunk_ms":
                         1e2 * (time.perf_counter() - t0)})
            print(json.dumps(rows[-1]), flush=True)
    diffs = {n: float(np.abs(a - b).max()) for n, a, b in zip(
        ("num", "den", "S", "z"), *out.values())}
    print(json.dumps({"max_abs_diff": diffs}), flush=True)
    if check:
        assert max(diffs.values()) < 1e-3, diffs
    else:
        os.makedirs("chiprun_out/pr37", exist_ok=True)
        with open("chiprun_out/pr37/grouped.json", "w") as f:
            json.dump({"rows": rows, "max_abs_diff": diffs}, f, indent=1)


if __name__ == "__main__":
    main()
