"""Kimi delta attention (KDA): the delta rule with a per-channel gate.

The layer (Kimi Linear, arXiv:2510.26692) keeps, per head, a state ``S``
(``d_k x d_v``) that every position first decays channel by channel, then
corrects along its key, then writes its value into::

    S_t = (I - b_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + b_t k_t v_t^T
    o_t = S_t^T q_t

``g_t <= 0`` is the log of the gate, one value a head and key channel;
``b_t`` in (0, 1) is the step of the correction, one a head. Written with the
value the state does not yet hold, ``u_t = v_t - (Diag(exp g_t) S_{t-1})^T
k_t``, the update is ``S_t = Diag(exp g_t) S_{t-1} + b_t k_t u_t^T``.

Two forms of that one layer, as :mod:`alink_tpu.dl.retention` has for its own:

- :func:`kda_chunk`: a chunk of ``T`` prompt positions, in the WY form of the
  paper's section 3. With ``G_t`` the summed log-gates up to ``t`` inside the
  chunk and ``W = b * U`` the corrected values of the whole chunk, ``(I +
  Diag(b) M) W = Diag(b) (V - (K * exp G) S_0)``, ``M_ti = sum_c k_tc k_ic
  exp(G_tc - G_ic)`` for ``i < t``: one unit-lower-triangular system a head,
  solved by substitution. ``exp(G_t - G_i)`` is never formed: the two sides
  are scaled to a reference between them, the start of ``t``'s sub-block of
  ``SUB`` positions, so that a factor is ``exp`` of at most ``SUB * |g|``.
  The configuration bounds the gate (``kda_lower_bound`` -5: 16 x 5 = 80 <
  88) for exactly this, and :func:`kda_chunk` refuses a looser bound.
- :func:`kda_step`: one new position for a batch of states.

:func:`short_conv` is the causal depthwise convolution that stands before q,
k and v; what a sequence carries of it is its last ``K - 1`` inputs.

A position marked invalid (padding, always a row's trailing positions) leaves
a row's state and convolution tail untouched: its gate is 1 and its step 0.
The state is float32; ``dtype`` is that of the large products' operands.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .retention import einsum_f32

KDA_SCOPE = "kda_core"
SUB = 16                # positions a sub-block: SUB * |lower bound| < 88
_MAX_EXPONENT = 87.0    # exp stays finite in float32 below 88.7


def short_conv(x, w, tail, valid):
    """Causal depthwise convolution over positions. x ``(B,T,C)`` float32;
    w ``(C,K)``, tap ``K-1`` on the current position; tail ``(B,K-1,C)``,
    the inputs before the chunk; valid ``(B,T)`` bool, false on a row's
    trailing padding. Returns ``(B,T,C)`` and the tail after the row's last
    valid position."""
    K = w.shape[-1]
    T = x.shape[1]
    full = jnp.concatenate([tail, x], axis=1)                    # (B,T+K-1,C)
    wf = w.astype(jnp.float32)
    y = sum(full[:, j:j + T] * wf[:, j] for j in range(K))
    n = valid.sum(axis=1).astype(jnp.int32)                      # (B,)
    idx = n[:, None] + jnp.arange(K - 1)[None, :]                # (B,K-1)
    return y, jnp.take_along_axis(full, idx[..., None], axis=1)


def short_conv_step(x, w, tail, valid=None):
    """One new position: x ``(B,C)``; returns ``(B,C)`` and the new tail."""
    full = jnp.concatenate([tail, x[:, None]], axis=1)           # (B,K,C)
    y = (full * w.astype(jnp.float32).T).sum(axis=1)
    new = full[:, 1:]
    if valid is not None:
        new = jnp.where(valid[:, None, None], new, tail)
    return y, new


def _mask_padding(g, beta, valid):
    if valid is None:
        return g, beta
    return (jnp.where(valid[..., None, None], g, 0.0),
            jnp.where(valid[..., None], beta, 0.0))


def kda_chunk(q, k, v, g, beta, valid, S, *, lower_bound: float,
              dtype=jnp.float32):
    """One chunk of ``T`` prompt positions for ``B`` rows.

    q, k ``(B,T,H,Dk)`` (k of unit length, q scaled); v ``(B,T,H,Dv)``; g
    ``(B,T,H,Dk)`` with ``lower_bound < g <= 0``; beta ``(B,T,H)``; valid
    ``(B,T)`` bool or None; S ``(B,H,Dk,Dv)`` float32, the state before the
    chunk. Returns the outputs ``(B,T,H,Dv)`` float32 and the state after."""
    if SUB * abs(lower_bound) > _MAX_EXPONENT:
        raise ValueError(f"a gate bounded by {lower_bound} overflows float32 "
                         f"over a sub-block of {SUB} positions")
    B, T0, H, Dk = k.shape
    f32 = jnp.float32
    g, beta = _mask_padding(g.astype(f32), beta.astype(f32), valid)
    pad = (-T0) % SUB
    if pad:                 # gate 1 and step 0: the state passes through
        tail = lambda x: jnp.pad(x, [(0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 2))
        q, k, v, g, beta = (tail(x) for x in (q, k, v, g, beta))
    T, n = T0 + pad, (T0 + pad) // SUB
    q, k, v = q.astype(f32), k.astype(f32), v.astype(f32)
    with jax.named_scope(KDA_SCOPE):
        G = jnp.cumsum(g, axis=1)                                # (B,T,H,Dk)
        # the summed log-gates before each sub-block's first position
        ref = jnp.concatenate([jnp.zeros_like(G[:, :1]),
                               G[:, SUB - 1:T - 1:SUB]], axis=1)  # (B,n,H,Dk)
        row = jnp.exp(G.reshape(B, n, SUB, H, Dk) - ref[:, :, None])
        k_row = (k.reshape(B, n, SUB, H, Dk) * row).astype(dtype)
        q_row = (q.reshape(B, n, SUB, H, Dk) * row).astype(dtype)
        # every key scaled to each sub-block's reference: a key of the same
        # sub-block grows by at most exp(SUB * |lower_bound|), an earlier
        # one shrinks, a later one is masked below
        col = jnp.exp(jnp.minimum(ref[:, :, None] - G[:, None], _MAX_EXPONENT))
        k_col = (k[:, None] * col).astype(dtype)                 # (B,n,T,H,Dk)
        M = einsum_f32("bashc,baihc->bhasi", k_row, k_col).reshape(B, H, T, T)
        P = einsum_f32("bashc,baihc->bhasi", q_row, k_col).reshape(B, H, T, T)
        t = jnp.arange(T)
        A = jnp.where(t[:, None] > t[None, :], M, 0.0) \
            * beta.transpose(0, 2, 1)[..., None]
        P = jnp.where(t[:, None] >= t[None, :], P, 0.0)
        decay = jnp.exp(G)                                       # to the start
        Sd = S.astype(dtype)
        R = v - einsum_f32("bthc,bhcv->bthv", (k * decay).astype(dtype), Sd)
        W = jax.scipy.linalg.solve_triangular(
            A + jnp.eye(T, dtype=f32),
            (R * beta[..., None]).transpose(0, 2, 1, 3),
            lower=True, unit_diagonal=True)                      # (B,H,T,Dv)
        Wd = W.astype(dtype)
        o = einsum_f32("bthc,bhcv->bthv", (q * decay).astype(dtype), Sd) \
            + einsum_f32("bhti,bhiv->bthv", P.astype(dtype), Wd)
        last = G[:, -1]                                          # (B,H,Dk)
        k_end = (k * jnp.exp(last[:, None] - G)).astype(dtype)
        Sn = jnp.exp(last)[..., None] * S \
            + einsum_f32("bthc,bhtv->bhcv", k_end, Wd)
    return o[:, :T0], Sn


def kda_step(q, k, v, g, beta, valid, S):
    """One new position for ``B`` rows through their states.

    q, k, g ``(B,H,Dk)``; v ``(B,H,Dv)``; beta ``(B,H)``; valid ``(B,)`` bool
    or None; S ``(B,H,Dk,Dv)`` float32. Returns the outputs ``(B,H,Dv)``
    float32 and the updated state. The two reads of the state, along the key
    and along the query, are sums in float32 over one pass, and the output
    comes from them: ``S_t^T q = Sd^T q + (q.k) b u``."""
    f32 = jnp.float32
    if valid is not None:
        g = jnp.where(valid[:, None, None], g, 0.0)
        beta = jnp.where(valid[:, None], beta, 0.0)
    q, k, v = q.astype(f32), k.astype(f32), v.astype(f32)
    with jax.named_scope(KDA_SCOPE):
        Sd = S * jnp.exp(g.astype(f32))[..., None]
        kq = jnp.stack([k, q], axis=2)                           # (B,H,2,Dk)
        read = (Sd[:, :, None] * kq[..., None]).sum(axis=-2)     # (B,H,2,Dv)
        u = (v - read[:, :, 0]) * beta.astype(f32)[..., None]
        Sn = Sd + k[..., None] * u[..., None, :]
        o = read[:, :, 1] + (q * k).sum(-1, keepdims=True) * u
    return o, Sn
