"""Sparse experts with group-limited token-choice routing, for a layer that is
told which of the experts it holds.

The layer (DeepSeek-V3, arXiv:2412.19437 section 2.1.2): a token's scores are
``s = sigmoid(W_r n)`` over all ``E`` experts, the product in float32. The
choice is by ``s + b`` (``b`` the expert bias, which steers the load and never
weighs an output): the experts lie in ``n_group`` groups, a group's score is
the sum of its two largest, the best ``topk_group`` groups are kept and the
best ``top_k`` experts among them chosen. Their weights are ``s_i / sum(s
chosen) * routed_scaling_factor``::

    y = sum_i w_i E_i(n) + E_shared(n)        E(n) = W_d (silu(W_g n) * W_u n)

:func:`route` routes over all ``E`` outputs whatever is held. The expert layer
(:func:`routed_experts`) holds the experts ``[lo, hi)`` and adds only their
terms: an assignment to an expert elsewhere is that expert's chip's to
compute, and nothing stands in for it here. No token is dropped: the
assignments held are sorted by expert and go through grouped matrix products
(``jax.lax.ragged_dot``) whose groups are as long as the router made them.
Those products take the sorted rows up to the smallest rung of a ladder of
row counts (:func:`rungs`) that holds every held assignment of the call, and
not all ``N K`` of them: with 16 of 256 experts held some 6% of the rows are
held, and every other row would be gathered, activated, zeroed and unsorted
for nothing. The top rung is ``N K``, so a router however skewed loses no
assignment.

Under ``jax.grad`` (the training step of :mod:`alink_tpu.dl.lm`) the choice is
a constant: the gradient reaches ``W_r`` through the chosen scores' normalised
weights and through the balance loss (:func:`seq_balance`), and ``b`` has no
gradient: :func:`bias_step` moves it by the sign of each expert's load. With
``piece`` set, :func:`routed_experts` takes each held expert's sorted rows
``piece`` at a time through plain products, as many pieces as its load takes,
so what is live at once is one piece and not every assignment, and the
products are the matrix unit's own (the grouped products ran at a ninth of
the peak in the training step; PERF.md section 6, PR 34).
"""

from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp

from .retention import einsum_f32

ROUTE_SCOPE = "moe_route"
EXPERTS_SCOPE = "moe_experts"
# the ladder of sorted rows the grouped form takes: down to a sixteenth of a
# call's assignments, and no rung under 128 rows (a pass of the matrix unit)
RUNG_DEPTH = 16
RUNG_MIN_ROWS = 128


def route(logits, bias, *, n_group: int, topk_group: int, top_k: int,
          scale: float):
    """logits ``(N,E)`` float32, bias ``(E,)``. Returns the chosen experts
    ``(N,top_k)`` int32 and their weights ``(N,top_k)`` float32."""
    N, E = logits.shape
    s = jax.nn.sigmoid(logits.astype(jnp.float32))
    choice = jax.lax.stop_gradient(s + bias.astype(jnp.float32)).reshape(
        N, n_group, E // n_group)
    group_score = jax.lax.top_k(choice, 2)[0].sum(-1)            # (N,n_group)
    kept = jax.lax.top_k(group_score, topk_group)[1]             # (N,topk_group)
    keep = (kept[..., None] == jnp.arange(n_group)).any(axis=1)  # (N,n_group)
    choice = jnp.where(keep[..., None], choice, -jnp.inf).reshape(N, E)
    idx = jax.lax.top_k(choice, top_k)[1]
    w = jnp.take_along_axis(s, idx, axis=-1)
    w = w / (w.sum(-1, keepdims=True) + 1e-20) * scale
    return idx.astype(jnp.int32), w


def held_load(idx, valid, held):
    """Where each assignment goes here: ``local`` ``(B,T,K)``, the expert's
    index among those held or ``hi - lo`` for one held elsewhere or made by
    a padding position; and ``(B,hi-lo)``, each row's count a held expert."""
    lo, hi = held
    here = (idx >= lo) & (idx < hi) & valid[..., None]
    local = jnp.where(here, idx - lo, hi - lo)
    B = idx.shape[0]
    load = (local.reshape(B, -1, 1) == jnp.arange(hi - lo)).sum(axis=1)
    return local, load.astype(jnp.int32)


def load_counts(idx, n_experts: int):
    """How many of ``idx`` ``(..., T, K)`` chose each of the router's
    ``n_experts`` outputs: ``(..., n_experts)`` int32."""
    hit = idx[..., None] == jnp.arange(n_experts, dtype=idx.dtype)
    return hit.sum(axis=(-3, -2)).astype(jnp.int32)


def seq_balance(logits, counts, *, top_k: int):
    """The sequence-wise balance term of each row (DeepSeek-V3 section
    2.1.2, before its weight ``alpha``): ``sum_i f_i P_i`` with ``f_i = E /
    (K T) * counts_i`` and ``P_i`` the row's mean of ``s_i / sum_j s_j``.
    logits ``(B,T,E)`` float32, counts ``(B,E)``: a constant."""
    T, E = logits.shape[-2:]
    s = jax.nn.sigmoid(logits.astype(jnp.float32))
    p = (s / s.sum(-1, keepdims=True)).mean(axis=-2)             # (B,E)
    f = jax.lax.stop_gradient(counts).astype(jnp.float32) * (E / (top_k * T))
    return (f * p).sum(-1)


def bias_step(bias, counts, rate: float):
    """The bias after a step whose tokens made ``counts`` ``(E,)``
    assignments to each output: up by ``rate`` where an expert got fewer
    than the mean, down where more (DeepSeek-V3 section 4.2)."""
    c = counts.astype(jnp.float32)
    return bias + rate * jnp.sign(c.mean() - c).astype(bias.dtype)


def _expert_rows(xs, w_rows, sizes, gate_up, down, *, dtype):
    """Sorted rows ``xs (R,H)`` with their weights ``(R,)`` through the
    grouped products; ``sizes (E,)``: how many rows are each expert's."""
    F = down.shape[1]
    if xs.dtype != jnp.float32 and jax.default_backend() == "cpu":
        xs, gate_up, down = (a.astype(jnp.float32)
                             for a in (xs, gate_up, down))
    dot = lambda a, b, to: jax.lax.ragged_dot(
        a, b, sizes, preferred_element_type=to)
    gu = dot(xs, gate_up, jnp.float32)
    # an assignment's weight goes in before the way down (the product is
    # linear), so that what comes back is summed as it lies, and comes
    # back in the operands' dtype: in float32 the six layers' sorted
    # outputs of a 128-row prompt chunk are 4 GB of live buffers
    hidden = jax.nn.silu(gu[:, :F]) * gu[:, F:] * w_rows[:, None]
    return dot(hidden.astype(dtype).astype(xs.dtype), down,
               xs.dtype).astype(dtype)                           # (R,H)


def rungs(rows: int) -> Tuple[int, ...]:
    """The row counts, ascending, the grouped form may take through the held
    experts' products for ``rows`` sorted assignments: each power of two
    below ``rows`` down to ``rows / RUNG_DEPTH`` and none under
    ``RUNG_MIN_ROWS``, then ``rows`` itself."""
    low, p = max(RUNG_MIN_ROWS, -(-rows // RUNG_DEPTH)), 1
    while 2 * p < rows:
        p *= 2
    ladder = [rows]
    while low <= p < rows:
        ladder.append(p)
        p //= 2
    return tuple(reversed(ladder))


def rung(held, rows: int):
    """The smallest rung of :func:`rungs` that holds ``held`` assignments,
    on the device: its index and its rows (int32)."""
    ladder = rungs(rows)
    i = jnp.sum(jnp.asarray(ladder[:-1], jnp.int32) < held, dtype=jnp.int32)
    return i, jnp.asarray(ladder, jnp.int32)[i]


def routed_experts(n, local, w, sizes, gate_up, down, *, dtype,
                   piece: int = 0):
    """The held experts' part of the layer's output.

    n ``(N,H)`` float32; local, w ``(N,K)``: each assignment's expert among
    the ``E`` held (``E``: not held) and its weight; sizes ``(E,)`` int32:
    assignments an expert; gate_up ``(E,H,2F)``, down ``(E,F,H)``. The
    sorted rows go through the grouped products up to the call's rung
    (:func:`rung`). With ``piece`` an expert's sorted rows are taken
    ``piece`` at a time through plain products (:func:`_pieces_fwd`): the
    training step's form."""
    N, K = local.shape
    flat = local.reshape(-1)
    order = jnp.argsort(flat, stable=True)       # held first, by expert
    if piece:
        # room past the last assignment for the last piece's slice
        order = jnp.concatenate([order, jnp.zeros((piece,), order.dtype)])
        return _pieces(n.astype(dtype), w, gate_up, down, order, sizes, dtype,
                       piece)
    held = sizes.sum()

    def through(R: int):
        def run(n_t, w, order, held, sizes, gate_up, down):
            src = order[:R]
            out = _expert_rows(n_t[src // K], w.reshape(-1)[src], sizes,
                               gate_up, down, dtype=dtype)       # (R,H)
            # a row past the held ones belongs to no expert held here
            tok = jnp.where(jnp.arange(R) < held, src // K, N)
            return jnp.zeros(n_t.shape, jnp.float32).at[tok].add(
                out.astype(jnp.float32), mode="drop")
        return run

    with jax.named_scope(EXPERTS_SCOPE):
        return jax.lax.switch(rung(held, N * K)[0],
                              [through(R) for R in rungs(N * K)],
                              n.astype(dtype), w, order, held, sizes, gate_up,
                              down)


def _rows_through(xs, w_rows, gate_up, down, *, dtype):
    """Rows ``xs (R,H)`` with their weights through one expert's matrices,
    ``(H,2F)`` and ``(F,H)``: plain products. A row of weight nought comes
    out nought and takes no gradient anywhere."""
    F = down.shape[0]
    gu = einsum_f32("rh,hf->rf", xs, gate_up)
    hidden = jax.nn.silu(gu[:, :F]) * gu[:, F:] * w_rows[:, None]
    return einsum_f32("rf,fh->rh", hidden.astype(dtype), down)


def _piece_of(order, w, ends, sizes, e: int, c, piece: int):
    """Piece ``c`` of expert ``e``'s sorted rows: the assignments it holds,
    their tokens, and their weights (nought past the expert's last row)."""
    first = ends[e] - sizes[e] + c * piece
    src = jax.lax.dynamic_slice(order, (first,), (piece,))
    mine = first + jnp.arange(piece) < ends[e]
    return src, src // w.shape[1], mine, jnp.where(mine, w.reshape(-1)[src], 0)


@partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _pieces(n_t, w, gate_up, down, order, sizes, dtype, piece):
    return _pieces_fwd(n_t, w, gate_up, down, order, sizes, dtype, piece)[0]


def _pieces_fwd(n_t, w, gate_up, down, order, sizes, dtype, piece):
    """Expert by expert, its sorted rows ``piece`` at a time through plain
    products, as many pieces as its load takes (a loop whose length the
    router decides, which ``jax.grad`` could not go back through: hence the
    backward pass below). Kept for it: the operands, once."""
    ends = jnp.cumsum(sizes)
    y = jnp.zeros(n_t.shape, jnp.float32)
    with jax.named_scope(EXPERTS_SCOPE):
        for e in range(gate_up.shape[0]):
            def one(c, y, e=e):
                _, tok, _, w_rows = _piece_of(order, w, ends, sizes, e, c, piece)
                return y.at[tok].add(_rows_through(
                    n_t[tok], w_rows, gate_up[e], down[e], dtype=dtype))

            y = jax.lax.fori_loop(0, -(-sizes[e] // piece), one, y)
    return y, (n_t, w, gate_up, down, order, sizes)


def _pieces_bwd(dtype, piece, kept, dy):
    """Each piece again, and its pull-back of ``dy``'s rows added up in
    float32: to the tokens' rows, the weights and the expert's matrices."""
    n_t, w, gate_up, down, order, sizes = kept
    ends = jnp.cumsum(sizes)
    f32 = jnp.float32
    dn, dw = jnp.zeros(n_t.shape, f32), jnp.zeros((w.size,), f32)
    d_gate_up, d_down = [], []
    with jax.named_scope(EXPERTS_SCOPE):
        for e in range(gate_up.shape[0]):
            def one(c, acc, e=e):
                dn, dw, dg, dd = acc
                src, tok, mine, w_rows = _piece_of(order, w, ends, sizes, e, c,
                                                   piece)
                _, back = jax.vjp(partial(_rows_through, dtype=dtype),
                                  n_t[tok], w_rows, gate_up[e], down[e])
                d_xs, d_w, d_g, d_d = back(dy[tok])
                return (dn.at[tok].add(d_xs.astype(f32)),
                        dw.at[src].add(jnp.where(mine, d_w, 0)),
                        dg + d_g.astype(f32), dd + d_d.astype(f32))

            dn, dw, dg, dd = jax.lax.fori_loop(
                0, -(-sizes[e] // piece), one,
                (dn, dw, jnp.zeros(gate_up.shape[1:], f32),
                 jnp.zeros(down.shape[1:], f32)))
            d_gate_up.append(dg.astype(gate_up.dtype))
            d_down.append(dd.astype(down.dtype))
    return (dn.astype(n_t.dtype), dw.reshape(w.shape).astype(w.dtype),
            jnp.stack(d_gate_up), jnp.stack(d_down), None, None)


_pieces.defvjp(_pieces_fwd, _pieces_bwd)
