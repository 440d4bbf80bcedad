"""The ``bailing_hybrid`` stack at toy width on the CPU: the KDA layer's forms
against the naive recurrence, latent attention through its cache against the
full forward, routing and the expert layer's shares against the reference,
the cache manager with both kinds of cache, the served generator against
``benchmark/reference/ling.py`` (logits, not tokens), the configuration's
pattern, the declared partition specs and the counters.
"""

import dataclasses
import json
import os
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from alink_tpu.common.metrics import metrics
from alink_tpu.dl import kda as K
from alink_tpu.dl import lm as L
from alink_tpu.dl import mla as A
from alink_tpu.dl import moe as E

HF = dict(
    model_type="bailing_hybrid", vocab_size=320, hidden_size=64,
    intermediate_size=128, num_hidden_layers=7, num_attention_heads=4,
    num_key_value_heads=4, head_dim=16, hidden_act="silu", rms_norm_eps=1e-6,
    rope_theta=6000000, layer_group_size=6, first_k_dense_replace=1,
    short_conv_kernel_size=4, kda_lower_bound=-5, kda_safe_gate=True,
    no_kda_lora=True, linear_silu=True, kv_lora_rank=32, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16, q_lora_rank=None, num_experts=8,
    num_experts_per_tok=4, n_group=4, topk_group=2, norm_topk_prob=True,
    routed_scaling_factor=2.5, scoring_func="sigmoid", topk_method="noaux_tc",
    moe_intermediate_size=32, moe_shared_expert_intermediate_size=32,
    num_shared_experts=1, moe_router_enable_expert_bias=True, use_qk_norm=True,
    rope_interleave=True, published={"num_experts": 32},
    deployment={"experts_held": [0, 8]})
DRAW = dict(weight_std=0.15, router_std=0.3, router_bias_std=0.05, conv_std=0.3,
            A_log=[-0.25, 0.25], dt_bias=[-3.0, -6.0])
PROMPTS = [[5, 6, 7, 8, 9, 10, 11], [20, 21, 22], [30 + i for i in range(21)]]
NEW = 6
CHUNK = 4
POSITIONS = 48


@pytest.fixture(autouse=True)
def tracing_on(monkeypatch):
    monkeypatch.setenv("ALINK_TRACING", "on")


@pytest.fixture(scope="module")
def ckpt(tmp_path_factory):
    """One chip's share (experts 0-7 of 32) of a seeded toy checkpoint in the
    HF layout, written by the benchmark's generator."""
    from benchmark import gen, gen_ling

    path = str(tmp_path_factory.mktemp("ling") / "checkpoint")
    gen_ling.write_checkpoint(path, HF, 7, DRAW, gen.make_vocab(HF["vocab_size"]))
    return path


@pytest.fixture(scope="module")
def model(ckpt):
    """Chunks of 4 positions: these prompts of 3 to 21 tokens take one to six
    calls of the prefill program."""
    loaded = L.load_causal_lm(ckpt, slots=8, positions=POSITIONS)[0]
    return L.CausalLM(loaded.cfg, loaded.params, slots=8, positions=POSITIONS,
                      prefill_chunk=CHUNK)


@pytest.fixture(scope="module")
def exact(model):
    """The same model with float32 weights, operands and products: what the
    program computes, apart from its rounding."""
    cfg = dataclasses.replace(model.cfg, dtype="float32")
    params = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), model.params)
    return lambda chunk: L.CausalLM(cfg, params, slots=8, positions=POSITIONS,
                                    prefill_chunk=chunk)


# -- the KDA layer -------------------------------------------------------------

def _kda_inputs(T=37, B=2, H=2, D=8, seed=0, gate=(-5.0, 0.0)):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (B, T, H, D))) * D ** -0.5
    k = unit(jax.random.normal(ks[1], (B, T, H, D)))
    v = jax.random.normal(ks[2], (B, T, H, D))
    lo, hi = gate
    g = lo + (hi - lo) * jax.random.uniform(ks[3], (B, T, H, D))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (B, T, H)))
    return q, k, v, g, beta


def _naive(q, k, v, g, beta, S):
    """The recurrence as written, one position at a time, in float64-free
    numpy float32 with no chunk, no scaling trick."""
    q, k, v, g, beta = (np.asarray(x, np.float64) for x in (q, k, v, g, beta))
    S = np.asarray(S, np.float64).copy()
    B, T, H, D = q.shape
    out = np.zeros((B, T, H, v.shape[-1]))
    for t in range(T):
        for b in range(B):
            for h in range(H):
                Sd = S[b, h] * np.exp(g[b, t, h])[:, None]
                kt = k[b, t, h]
                S[b, h] = Sd - beta[b, t, h] * np.outer(kt, kt @ Sd) \
                    + beta[b, t, h] * np.outer(kt, v[b, t, h])
                out[b, t, h] = S[b, h].T @ q[b, t, h]
    return out, S


def _chunked(q, k, v, g, beta, S, chunk, lens=None):
    B, T = q.shape[:2]
    pad = (-T) % chunk
    padded = lambda x: jnp.pad(x, [(0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 2))
    q, k, v, g, beta = (padded(x) for x in (q, k, v, g, beta))
    lens = jnp.full((B,), T) if lens is None else jnp.asarray(lens)
    valid = jnp.arange(T + pad)[None, :] < lens[:, None]
    outs = []
    for c in range(0, T + pad, chunk):
        sl = slice(c, c + chunk)
        o, S = K.kda_chunk(q[:, sl], k[:, sl], v[:, sl], g[:, sl], beta[:, sl],
                           valid[:, sl], S, lower_bound=-5.0)
        outs.append(o)
    return jnp.concatenate(outs, axis=1)[:, :T], S


@pytest.mark.parametrize("gate", [(-5.0, -4.5), (-0.01, 0.0), (-5.0, 0.0)],
                         ids=["forgets", "remembers", "both_ends"])
@pytest.mark.parametrize("chunk", [1, 5, 16, 20, 64])
def test_kda_chunked_form_is_the_naive_recurrence(chunk, gate):
    """Across chunk edges and sub-block edges (16), with a state inherited
    from before, at both ends of the gate's range: exp(+-sum g) of a
    sub-block stays inside float32 (16 x 5 = 80)."""
    with jax.default_matmul_precision("highest"):
        q, k, v, g, beta = _kda_inputs(gate=gate)
        S0 = jax.random.normal(jax.random.PRNGKey(9), (2, 2, 8, 8))
        want, S_want = _naive(q, k, v, g, beta, S0)
        got, S_got = _chunked(q, k, v, g, beta, S0, chunk)
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(S_got, S_want, atol=2e-4, rtol=2e-4)


def test_kda_recurrent_form_is_the_naive_recurrence():
    q, k, v, g, beta = _kda_inputs(T=19)
    S = S0 = jax.random.normal(jax.random.PRNGKey(9), (2, 2, 8, 8))
    outs = []
    for t in range(q.shape[1]):
        o, S = K.kda_step(q[:, t], k[:, t], v[:, t], g[:, t], beta[:, t], None, S)
        outs.append(o)
    want, S_want = _naive(q, k, v, g, beta, S0)
    np.testing.assert_allclose(jnp.stack(outs, axis=1), want, atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(S, S_want, atol=2e-5, rtol=2e-5)


def test_kda_padding_leaves_a_rows_state_untouched():
    """Row 1's prompt ends at position 11: the chunks after it, and the
    chunk's own trailing positions, change neither its state nor the
    outputs before them; a padded step does the same."""
    with jax.default_matmul_precision("highest"):
        q, k, v, g, beta = _kda_inputs(T=30)
        S0 = jnp.zeros((2, 2, 8, 8))
        _, S = _chunked(q, k, v, g, beta, S0, 8, lens=[30, 11])
        _, S_short = _chunked(*(x[:, :11] for x in (q, k, v, g, beta)), S0, 8)
        np.testing.assert_allclose(S[1], S_short[1], atol=1e-6)
        _, S_step = K.kda_step(q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0],
                               jnp.asarray([True, False]), S)
    np.testing.assert_array_equal(S_step[1], S[1])
    assert np.abs(S_step[0] - S[0]).max() > 1e-3


def test_kda_refuses_a_gate_bound_that_overflows_a_sub_block():
    q, k, v, g, beta = _kda_inputs(T=16)
    with pytest.raises(ValueError, match="overflows"):
        K.kda_chunk(q, k, v, g, beta, None, jnp.zeros((2, 2, 8, 8)),
                    lower_bound=-6.0)


@pytest.mark.parametrize("chunk", [3, 8])
def test_short_convolution_carries_its_tail_over_chunks_and_padding(chunk):
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 17, 6))
    w = jax.random.normal(jax.random.PRNGKey(1), (6, 4))
    full = np.concatenate([np.zeros((2, 3, 6)), np.asarray(x)], axis=1)
    want = sum(full[:, j:j + 17] * np.asarray(w)[:, j] for j in range(4))
    lens = np.asarray([17, 10])
    tail, outs = jnp.zeros((2, 3, 6)), []
    pad = (-17) % chunk
    xp = jnp.pad(x, [(0, 0), (0, pad), (0, 0)])
    for c in range(0, 17 + pad, chunk):
        valid = (c + jnp.arange(chunk))[None, :] < lens[:, None]
        y, tail = K.short_conv(xp[:, c:c + chunk], w, tail, valid)
        outs.append(y)
    got = jnp.concatenate(outs, axis=1)
    np.testing.assert_allclose(got[0, :17], want[0], atol=1e-5)
    np.testing.assert_allclose(got[1, :10], want[1, :10], atol=1e-5)
    np.testing.assert_allclose(tail[0], x[0, 14:17], atol=0)
    np.testing.assert_allclose(tail[1], x[1, 7:10], atol=0)
    y, tail2 = K.short_conv_step(x[:, 0], w, tail, jnp.asarray([True, False]))
    np.testing.assert_allclose(tail2[1], tail[1], atol=0)
    np.testing.assert_allclose(
        y[0], (np.concatenate([tail[0], x[0, :1]]) * np.asarray(w).T).sum(0),
        atol=1e-5)


# -- latent attention ----------------------------------------------------------

def test_mla_prefill_and_absorbed_decode_through_the_cache_are_the_full_forward():
    """A prompt in chunks of 5 and then single steps through the latent
    cache, in the absorbed form, against the reference's expanded attention
    over the whole sequence with no cache."""
    from benchmark.reference import ling

    H, r, dn, dr, dv, hid, T = 4, 32, 16, 8, 16, 64, 23
    ks = jax.random.split(jax.random.PRNGKey(3), 8)
    w = {"self_attn.q_proj": jax.random.normal(ks[0], (H * (dn + dr), hid)) * 0.2,
         "self_attn.kv_a_proj_with_mqa": jax.random.normal(ks[1], (r + dr, hid)) * 0.2,
         "self_attn.kv_a_layernorm": 1 + 0.1 * jax.random.normal(ks[2], (r,)),
         "self_attn.kv_b_proj": jax.random.normal(ks[3], (H * (dn + dv), r)) * 0.2,
         "self_attn.g_proj": jax.random.normal(ks[4], (H, hid)) * 0.2,
         "self_attn.o_proj": jax.random.normal(ks[5], (hid, H * dv)) * 0.2}
    a = jax.random.normal(ks[6], (2, T, hid))
    cfg = L.CausalLMConfig(
        vocab_size=8, hidden_size=hid, intermediate_size=8, num_hidden_layers=1,
        num_attention_heads=H, num_key_value_heads=H, head_dim=16,
        rope_theta=6e6, layer_types=("mla",), dtype="float32", kv_lora_rank=r,
        qk_nope_head_dim=dn, qk_rope_head_dim=dr, v_head_dim=dv)
    layer = {n.split(".")[1]: x for n, x in w.items()}
    c = dict(num_attention_heads=H, kv_lora_rank=r, qk_nope_head_dim=dn,
             qk_rope_head_dim=dr, v_head_dim=dv, rope_theta=6e6, rms_norm_eps=1e-6)
    lens = np.asarray([T, 14])
    with jax.default_matmul_precision("highest"):
        mm = partial(ling._mm, precision="f32")
        want = [ling._mla(w, a[b, :n], jnp.arange(n), jnp.zeros(n, jnp.int32), c, mm)
                for b, n in enumerate(lens)]
        latent = jnp.zeros((2, 32, r + dr))
        length = jnp.zeros((2,), jnp.int32)
        outs = []
        for s in range(0, 15, 5):          # the prompts' first 15 positions
            pos = s + jnp.arange(5)[None, :] + jnp.zeros((2, 1), jnp.int32)
            valid = pos < jnp.minimum(lens, 15)[:, None]
            y, latent, length = L._mla_mixer(cfg, layer, a[:, s:s + 5],
                                             jnp.where(valid, pos, 0), valid,
                                             latent, length)
            outs.append(y)
        np.testing.assert_array_equal(length, [15, 14])
        for t in range(15, T):              # row 0 alone goes on, step by step
            valid = jnp.asarray([[True], [False]])
            y, latent, length = L._mla_mixer(
                cfg, layer, a[:, t:t + 1], jnp.full((2, 1), t), valid, latent, length)
            outs.append(y)
    got = jnp.concatenate(outs, axis=1)
    np.testing.assert_allclose(got[0], want[0], atol=2e-5)
    np.testing.assert_allclose(got[1, :14], want[1], atol=2e-5)
    np.testing.assert_array_equal(length, [T, 14])
    assert float(jnp.abs(latent[1, 14:]).max()) == 0.0   # nothing written past it


def test_interleaved_rotary_positions_rotate_neighbouring_pairs():
    x = jax.random.normal(jax.random.PRNGKey(0), (3, 2, 8))
    pos = jnp.asarray([0, 5, 11])
    y = A.rope_interleaved(x, pos, 1e4)
    np.testing.assert_allclose(y[0], x[0], atol=1e-6)
    inv = 1.0 / (1e4 ** (np.arange(0, 8, 2) / 8))
    for i in range(4):
        ang = 11 * inv[i]
        np.testing.assert_allclose(
            y[2, :, 2 * i], x[2, :, 2 * i] * np.cos(ang) - x[2, :, 2 * i + 1] * np.sin(ang),
            atol=1e-5)


# -- routing and the expert layer ----------------------------------------------

def _route_by_hand(logits, bias, n_group, topk_group, top_k, scale):
    s = 1 / (1 + np.exp(-np.asarray(logits, np.float64)))
    choice = s + np.asarray(bias, np.float64)
    idx, w = [], []
    for row_s, row_c in zip(s, choice):
        groups = row_c.reshape(n_group, -1)
        score = np.sort(groups, axis=1)[:, -2:].sum(1)
        kept = np.argsort(-score)[:topk_group]
        allowed = np.full_like(row_c, -np.inf).reshape(n_group, -1)
        allowed[kept] = groups[kept]
        chosen = np.argsort(-allowed.reshape(-1))[:top_k]
        idx.append(np.sort(chosen))
        w.append(row_s[np.sort(chosen)] / row_s[chosen].sum() * scale)
    return np.asarray(idx), np.asarray(w)


def test_routing_keeps_groups_uses_the_bias_for_choice_only_and_normalises():
    logits = jax.random.normal(jax.random.PRNGKey(0), (64, 32)) * 1.5
    bias = jax.random.normal(jax.random.PRNGKey(1), (32,)) * 0.5
    kw = dict(n_group=4, topk_group=2, top_k=4, scale=2.5)
    idx, w = E.route(logits, bias, **kw)
    order = np.argsort(np.asarray(idx), axis=1)
    want_idx, want_w = _route_by_hand(logits, bias, 4, 2, 4, 2.5)
    np.testing.assert_array_equal(np.take_along_axis(np.asarray(idx), order, 1), want_idx)
    np.testing.assert_allclose(np.take_along_axis(np.asarray(w), order, 1), want_w,
                               rtol=1e-5)
    np.testing.assert_allclose(np.asarray(w).sum(1), 2.5, rtol=1e-5)
    assert (np.asarray(idx) // 8 == np.asarray(idx)[:, :1] // 8).sum(1).max() <= 4
    assert all(len(set(r // 8)) <= 2 for r in np.asarray(idx))
    # the bias moves the choice and never a weight: chosen the same way
    # without it, the same experts would weigh the same
    idx0, w0 = E.route(logits, jnp.zeros(32), **kw)
    assert (np.sort(np.asarray(idx0), 1) != want_idx).any()
    s = jax.nn.sigmoid(logits)
    picked = jnp.take_along_axis(s, idx, axis=1)
    np.testing.assert_allclose(w, picked / picked.sum(1, keepdims=True) * 2.5,
                               rtol=1e-5)


def _toy_expert_layer(seed=0, E_all=16, H=24, F=12, N=40):
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    w = {"mlp.gate": jax.random.normal(ks[0], (E_all, H)) * 0.5,
         "mlp.gate.expert_bias": jax.random.normal(ks[1], (E_all,)) * 0.1}
    for e in range(E_all):
        kk = jax.random.split(jax.random.fold_in(ks[2], e), 3)
        w[f"mlp.experts.{e}.gate_proj"] = jax.random.normal(kk[0], (F, H)) * 0.3
        w[f"mlp.experts.{e}.up_proj"] = jax.random.normal(kk[1], (F, H)) * 0.3
        w[f"mlp.experts.{e}.down_proj"] = jax.random.normal(kk[2], (H, F)) * 0.3
    for i, name in enumerate(("gate_proj", "up_proj", "down_proj")):
        shape = (H, F) if name == "down_proj" else (F, H)
        w[f"mlp.shared_experts.{name}"] = jax.random.normal(ks[3 + i], shape) * 0.3
    return w, jax.random.normal(ks[7], (N, H))


def _program_share(w, n, held, E_all=16, skew=0.0):
    """What the program's expert layer gives for the experts ``held``,
    without the shared expert: x = 0 and the norm's scale 1 make its input
    ``n`` as it stands (rows of unit mean square)."""
    lo, hi = held
    logits = jnp.einsum("nh,eh->ne", n, w["mlp.gate"],
                        precision="highest") + skew * jnp.arange(E_all)[::-1]
    idx, wt = E.route(logits, w["mlp.gate.expert_bias"], n_group=4, topk_group=2,
                      top_k=4, scale=2.5)
    local, load = E.held_load(idx[None], jnp.ones((1, n.shape[0]), bool), held)
    gate_up = jnp.stack([jnp.concatenate(
        [w[f"mlp.experts.{e}.gate_proj"].T, w[f"mlp.experts.{e}.up_proj"].T], 1)
        for e in range(lo, hi)])
    down = jnp.stack([w[f"mlp.experts.{e}.down_proj"].T for e in range(lo, hi)])
    y = E.routed_experts(n, local[0], wt, load.sum(0), gate_up, down,
                         dtype=jnp.float32)
    return y, idx, load


def test_the_four_shares_of_an_expert_layer_add_up_to_the_uncut_reference():
    """Four chips of four experts each, every one routing over all 16: their
    parts, with the shared expert counted once, are the reference's layer
    with all experts held."""
    from benchmark.reference import ling

    w, n = _toy_expert_layer()
    c = dict(num_experts_per_tok=4, n_group=4, topk_group=2,
             routed_scaling_factor=2.5, experts_held=(0, 16))
    with jax.default_matmul_precision("highest"):
        mm = partial(ling._mm, precision="f32")
        whole, ref_idx = ling._experts(w, n, c, mm, True)
        shared = ling._swiglu(n, w["mlp.shared_experts.gate_proj"],
                              w["mlp.shared_experts.up_proj"],
                              w["mlp.shared_experts.down_proj"], mm)
        parts = [_program_share(w, n, (lo, lo + 4)) for lo in range(0, 16, 4)]
        one, _ = ling._experts(w, n, dict(c, experts_held=(4, 8)), mm, True)
    np.testing.assert_allclose(sum(p[0] for p in parts) + shared, whole, atol=2e-5)
    np.testing.assert_allclose(parts[1][0] + shared, one, atol=2e-5)
    np.testing.assert_array_equal(np.sort(parts[0][1], 1), np.sort(ref_idx, 1))
    # every assignment is held by exactly one share
    assert sum(int(p[2].sum()) for p in parts) == n.shape[0] * 4


def test_no_token_is_dropped_under_a_skewed_router():
    """A router that sends every token to the same four experts: the share
    that holds them serves all of the assignments, the others none, and the
    output is still the reference's."""
    from benchmark.reference import ling

    w, n = _toy_expert_layer(seed=1)
    w = dict(w, **{"mlp.gate": w["mlp.gate"] * 0.0,
                   "mlp.gate.expert_bias": 3.0 * jnp.arange(16)[::-1]})
    with jax.default_matmul_precision("highest"):
        y, idx, load = _program_share(w, n, (0, 4))
        none = _program_share(w, n, (8, 12))
        c = dict(num_experts_per_tok=4, n_group=4, topk_group=2,
                 routed_scaling_factor=2.5, experts_held=(0, 4))
        mm = partial(ling._mm, precision="f32")
        want, _ = ling._experts(w, n, c, mm, True)
        shared = ling._swiglu(n, w["mlp.shared_experts.gate_proj"],
                              w["mlp.shared_experts.up_proj"],
                              w["mlp.shared_experts.down_proj"], mm)
    np.testing.assert_array_equal(np.asarray(load)[0], [40, 40, 40, 40])
    assert int(none[2].sum()) == 0 and float(jnp.abs(none[0]).max()) == 0.0
    np.testing.assert_allclose(y + shared, want, atol=2e-5)


# -- the model -----------------------------------------------------------------

def _reference_logprobs(ckpt, prompts, ids, lost_at=None, grouped=True):
    from benchmark.reference import ling

    rows, visible = [], None if lost_at is None else []
    for p, e in zip(prompts, ids):
        seq = np.asarray(list(p) + list(e[:-1]))
        rows.append((seq, np.arange(len(seq)), len(p) - 1 + np.arange(len(e))))
        if lost_at is not None:
            t = np.minimum(np.arange(len(seq)), len(p) - 1)
            visible.append((t // lost_at * lost_at).astype(np.int32))
    logits, chosen = ling.logits_at(ling.Checkpoint(ckpt), HF, rows,
                                    visible_from=visible, grouped=grouped)
    at = np.arange(len(ids[0]))
    lp = np.stack([np.asarray(jax.nn.log_softmax(jnp.asarray(l), -1))[at, e]
                   for l, e in zip(logits, ids)])
    return lp, chosen


@pytest.mark.parametrize("chunk", [4, 5, 64])
def test_prefill_and_cached_steps_are_the_references_full_forward(ckpt, exact,
                                                                  chunk):
    """Logits, not tokens, with float32 products on both sides: chunked KDA
    with carried state and convolution tails, absorbed attention through the
    latent cache, grouped expert products, against the naive recurrence,
    expanded attention and a loop over experts. Rows of different lengths in
    one batch; chunks that end inside, on and past a sub-block."""
    with jax.default_matmul_precision("highest"):
        m = exact(chunk)
        ids, lps = m.generate(PROMPTS, NEW)
        want, chosen = _reference_logprobs(ckpt, PROMPTS, ids)
    np.testing.assert_allclose(lps, want, atol=2e-4)
    # and the routing: each row's held assignments, layer by layer
    for r, c in enumerate(chosen):
        counts = np.stack([np.bincount(l[l < 8], minlength=8) for l in c])
        np.testing.assert_array_equal(m.expert_load[r], counts)


def test_the_bfloat16_program_is_as_near_the_reference_as_bfloat16_is(ckpt, model):
    """The served precision: operands rounded to bfloat16 flip a routing
    choice now and then (a different expert's output, not a rounding), so
    the program is held to what the reference reads with its own products
    rounded to bfloat16: 0.05-0.17 from float32 on these rows, where float8
    reads 0.6-2.6."""
    ids, lps = model.generate(PROMPTS, NEW)
    want, _ = _reference_logprobs(ckpt, PROMPTS, ids)
    assert np.abs(lps - want).max() < 0.4


def test_a_rows_answer_does_not_depend_on_its_neighbours(model):
    ids, lps = model.generate(PROMPTS, NEW)
    for r, p in enumerate(PROMPTS):
        alone_ids, alone_lps = model.generate([p], NEW)
        np.testing.assert_array_equal(alone_ids[0], ids[r])
        np.testing.assert_allclose(alone_lps[0], lps[r], atol=1e-5)


def test_a_reused_slot_holds_nothing_of_its_last_sequence(model):
    """Both kinds of cache: a long batch fills states, convolution tails,
    latent cache and counts; the short batch after it reads as on a fresh
    model, and its latent lengths are its own."""
    first = model.generate(PROMPTS, NEW)
    model.generate([[60 + i for i in range(30)]] * 5, NEW)
    lengths = [kept[1] for kept, kind in zip(model.cache.peek(),
                                             model.cfg.layer_types) if kind == "mla"]
    np.testing.assert_array_equal(lengths[0][:5], [30 + NEW - 1] * 5)
    again = model.generate(PROMPTS, NEW)
    np.testing.assert_array_equal(first[0], again[0])
    np.testing.assert_array_equal(first[1], again[1])
    lengths = [kept[1] for kept, kind in zip(model.cache.peek(),
                                             model.cfg.layer_types) if kind == "mla"]
    np.testing.assert_array_equal(
        lengths[0][:3], [len(p) + NEW - 1 for p in PROMPTS])
    np.testing.assert_array_equal(lengths[0][3:], 0)     # rows beyond the batch


def test_the_cache_manager_lays_out_both_kinds_and_refuses_a_row_too_long(model):
    cfg = model.cfg
    state = L.StateCache(cfg, 8, POSITIONS).peek()   # the gauges are its own
    assert [len(kept) for kept in state] == [2, 3, 3, 3, 3, 3, 3]
    assert state[0][0].shape == (8, 4, 16, 16) and state[0][1].shape == (8, 3, 192)
    assert state[5][0].shape == (8, POSITIONS, 40) and state[5][0].dtype == jnp.bfloat16
    assert state[5][1].shape == (8,) and state[5][1].dtype == jnp.int32
    assert state[1][2].shape == (8, 8) and state[1][2].dtype == jnp.int32
    assert metrics.gauge("lm.kda_state_bytes") == 8 * 6 * (4 * 16 * 16 + 3 * 192) * 4
    assert metrics.gauge("lm.state_bytes") == metrics.gauge("lm.kda_state_bytes")
    assert metrics.gauge("lm.latent_cache_bytes") == 8 * POSITIONS * 40 * 2
    assert metrics.gauge("lm.latent_cache_positions") == POSITIONS
    with pytest.raises(ValueError, match="latent cache"):
        model.generate([[1] * (POSITIONS - NEW + 2)], NEW)
    with pytest.raises(ValueError, match="positions"):
        L.CausalLM(cfg, model.params, slots=8)
    model.generate([[1] * (POSITIONS - NEW + 1)], NEW)      # the last that fits


def test_a_state_lost_between_chunks_is_the_references_control(ckpt, exact,
                                                              monkeypatch):
    """The fault the benchmark's ``chunk_state_dropped`` control stands for,
    planted in the program: every call of the prefill program starts from an
    empty cache. The answers are what the reference reads when a position
    sees back to its chunk's start only."""
    with jax.default_matmul_precision("highest"):
        m = exact(CHUNK)
        real = m._program

        def lossy(kernel_id, builder, rows):
            prog = real(kernel_id, builder, rows)
            if kernel_id != "lm.prefill_chunk":
                return prog
            return lambda params, state, tokens, pos, first, *rest: prog(
                params, state, tokens, pos, True, *rest)

        monkeypatch.setattr(m, "_program", lossy)
        parts = [m.generate([p], NEW) for p in PROMPTS]
        ids = np.concatenate([a for a, _ in parts])
        lps = np.concatenate([b for _, b in parts])
        sound, _ = _reference_logprobs(ckpt, PROMPTS, ids)
        lost, _ = _reference_logprobs(ckpt, PROMPTS, ids, lost_at=CHUNK)
    np.testing.assert_allclose(lps[1], sound[1], atol=2e-4)   # one chunk
    assert np.abs(lps[[0, 2]] - sound[[0, 2]]).max(axis=1).min() > 0.1
    np.testing.assert_allclose(lps, lost, atol=2e-4)


def test_the_ungrouped_control_chooses_other_experts(ckpt, exact):
    with jax.default_matmul_precision("highest"):
        ids, _ = exact(CHUNK).generate(PROMPTS, NEW)
        sound, chosen = _reference_logprobs(ckpt, PROMPTS, ids)
        loose, other = _reference_logprobs(ckpt, PROMPTS, ids, grouped=False)
    assert np.abs(loose - sound).max() > 0.05
    assert any((np.sort(a, -1) != np.sort(b, -1)).any() for a, b in zip(chosen, other))
    assert all(len(set((e // 8).tolist())) <= 2 for e in chosen[0][0])


def _texts(vocab_words, n=5):
    rng = np.random.default_rng(1)
    return [" ".join(vocab_words[j] for j in rng.integers(0, len(vocab_words),
                                                         size=3 + 2 * i))
            for i in range(n)]


@pytest.mark.parametrize("path", ["batch_dag", "model_server"])
def test_the_op_serves_the_hybrid_checkpoint(ckpt, path):
    from alink_tpu.common.mtable import MTable
    from alink_tpu.dl.pretrained import load_vocab_file
    from alink_tpu.operator.batch import (CausalLMGenerateBatchOp,
                                          TableSourceBatchOp)
    from alink_tpu.pipeline import CausalLMGenerator, PipelineModel
    from alink_tpu.serving import ModelServer, ServingConfig

    vocab = load_vocab_file(os.path.join(ckpt, "vocab.txt"))
    texts = _texts([t for t in vocab if len(t) == 6 and t.isalpha()])
    kw = dict(modelPath=ckpt, selectedCol="prompt", predictionCol="text",
              predictionDetailCol="detail", maxNewTokens=NEW, stateSlots=8,
              cachePositions=POSITIONS)
    index = {t: i for i, t in enumerate(vocab)}
    prompts = [[index[w] for w in t.split()] for t in texts]
    lm, _ = L.load_causal_lm(ckpt, slots=8, positions=POSITIONS)
    want, _ = lm.generate(prompts, NEW)
    if path == "batch_dag":
        out = CausalLMGenerateBatchOp(**kw).link_from(
            TableSourceBatchOp(MTable({"prompt": texts}))).collect()
        rows = [out.get_row(i) for i in range(out.num_rows)]
    else:
        server = ModelServer(ServingConfig(max_batch_rows=8, flush_deadline_s=0.05))
        try:
            server.load("lm", PipelineModel(CausalLMGenerator(**kw)),
                        "prompt string", warmup_rows=[(texts[0],)])
            rows = [f.result(120) for f in
                    [server.submit("lm", (t,)) for t in texts]]
        finally:
            server.close()
    assert [r[0] for r in rows] == texts
    for r, ids, load in zip(rows, want, lm.expert_load):
        detail = json.loads(r[2])
        assert detail["ids"] == ids.tolist()
        assert detail["expert_load"] == load.tolist()
        assert np.sum(detail["expert_load"]) <= 6 * 4 * (
            detail["prompt_tokens"] + NEW - 1)
    # against the reference, teacher-forced: the served log-probabilities
    lps = np.asarray([json.loads(r[2])["logprobs"] for r in rows])
    ref, _ = _reference_logprobs(ckpt, prompts, want)
    assert np.abs(lps - ref).max() < 0.4


# -- configuration, ingest, specs, counters ------------------------------------

def test_from_hf_derives_the_pattern_and_refuses_what_it_does_not_compute():
    hf = dict(HF, num_experts=32, experts_held=[8, 16], num_hidden_layers=12,
              first_k_dense_replace=2)
    cfg = L.CausalLMConfig.from_hf(hf)
    assert cfg.layer_types == ("kda",) * 5 + ("mla",) + ("kda",) * 5 + ("mla",)
    assert cfg.ffn_types == ("dense",) * 2 + ("experts",) * 10
    assert cfg.experts_held == (8, 16) and cfg.num_experts == 32
    assert cfg.prefill_chunk == L.HYBRID_PREFILL_CHUNK
    assert L.CausalLMConfig.from_hf(dict(hf, experts_held=None)).experts_held == (0, 32)
    limits = [0] * 11 + [4]
    for key in ("expert_swiglu_limit_list", "share_expert_swiglu_limit_list"):
        with pytest.raises(NotImplementedError, match="swiglu"):
            L.CausalLMConfig.from_hf(dict(hf, **{key: limits}))
        # a layer past the kept ones is not this stack's to refuse
        L.CausalLMConfig.from_hf(dict(hf, **{key: limits, "num_hidden_layers": 11}))
    with pytest.raises(NotImplementedError, match="q_lora_rank"):
        L.CausalLMConfig.from_hf(dict(hf, q_lora_rank=1536))
    with pytest.raises(ValueError, match="experts_held"):
        L.CausalLMConfig.from_hf(dict(hf, experts_held=[24, 40]))


@pytest.mark.parametrize("model_type", [None, "llama", "bailing_moe"])
def test_from_hf_refuses_a_model_type_it_does_not_know(model_type):
    """A config without ``layer_types`` used to become a retention stack,
    whatever its family."""
    hf = {k: v for k, v in HF.items() if k != "model_type"}
    if model_type:
        hf["model_type"] = model_type
    with pytest.raises(NotImplementedError, match="model_type"):
        L.CausalLMConfig.from_hf(hf)


def test_the_ingest_stacks_the_held_experts_and_refuses_a_missing_one(ckpt, model):
    from alink_tpu.dl.pretrained import iter_safetensors

    layer = model.params["layers"][1]
    assert layer["experts_gate_up"].shape == (8, 64, 64)
    assert layer["experts_down"].shape == (8, 32, 64)
    assert "experts" not in layer and "gate_proj" not in layer
    tensors = dict(iter_safetensors(ckpt))
    np.testing.assert_array_equal(
        np.asarray(layer["experts_gate_up"][3, :, :32].astype(jnp.float32)),
        np.asarray(tensors["model.layers.1.mlp.experts.3.gate_proj.weight"]
                   ).astype(np.float32).T)
    np.testing.assert_array_equal(
        np.asarray(layer["experts_down"][5].astype(jnp.float32)),
        np.asarray(tensors["model.layers.1.mlp.experts.5.down_proj.weight"]
                   ).astype(np.float32).T)
    leaves = jax.tree_util.tree_leaves(model.params)
    assert sum(x.size for x in leaves) == sum(
        int(np.prod(s)) for s in L.tensor_shapes(model.cfg).values())
    wider = L.CausalLMConfig.from_hf(dict(HF, num_experts=32, experts_held=[0, 9]))
    with pytest.raises(ValueError, match="lacks"):
        L.params_from_tensors(wider, iter_safetensors(ckpt))


@pytest.mark.parametrize("leaf,shape,spec", [
    ("layers/1/experts_gate_up", (8, 64, 64), ("expert", None, None)),
    ("layers/1/experts_down", (8, 32, 64), ("expert", None, None)),
    ("layers/1/shared_gate_proj", (32, 64), ("model", None)),
    ("layers/1/shared_down_proj", (64, 32), (None, "model")),
    ("layers/0/f_proj", (64, 64), ("model", None)),
    ("layers/0/b_proj", (4, 64), ("model", None)),
    ("layers/0/q_conv1d", (64, 1, 4), ("model", None, None)),
    ("layers/0/dt_bias", (64,), ("model",)),
    ("layers/0/A_log", (4,), ("model",)),
    ("layers/5/kv_b_proj", (128, 32), ("model", None)),
    ("layers/5/kv_a_proj_with_mqa", (40, 64), ()),
    ("layers/5/kv_a_layernorm", (32,), ()),
    ("layers/1/gate", (32, 64), ()),
    ("layers/1/expert_bias", (32,), ()),
    ("layers/0/o_norm", (16,), ())])
def test_partition_specs_of_the_hybrid_parameter_paths(leaf, shape, spec):
    from jax.sharding import PartitionSpec as P

    from alink_tpu.dl.sharding import _spec_for, make_dl_mesh, sharding_for
    from alink_tpu.parallel.mesh import make_mesh

    assert _spec_for(leaf, shape) == P(*spec)
    mesh = make_mesh({"data": 2, "model": 2, "expert": 2})
    assert sharding_for(leaf, shape, mesh).spec == P(*spec)
    # a mesh without the expert axis holds every expert everywhere
    plain = make_dl_mesh(dp=4, tp=2)
    want = P() if "expert" in spec else P(*spec)
    assert sharding_for(leaf, shape, plain).spec == want


def test_counters_and_gauges_of_a_batch(model):
    before = dict(metrics.counters("moe."))
    h0 = metrics.histogram_states()
    model.generate(PROMPTS, NEW)
    grew = lambda n: metrics.counters("moe.").get(n, 0) - before.get(n, 0)
    tokens = sum(len(p) for p in PROMPTS) + len(PROMPTS) * (NEW - 1)
    assert grew("moe.assignments") == tokens * 4 * 6
    assert grew("moe.assignments_held") == int(model.expert_load.sum())
    assert 0 < grew("moe.assignments_held") < grew("moe.assignments")
    h1 = metrics.histogram_states()
    count = lambda h, n: h[n]["count"] if n in h else 0
    assert count(h1, "moe.expert_load_max_over_mean") \
        - count(h0, "moe.expert_load_max_over_mean") == 6
    assert count(h1, "lm.step_latent_positions") \
        - count(h0, "lm.step_latent_positions") == NEW - 1
