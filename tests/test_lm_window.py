"""The ``mimo_v2_flash`` stack at toy width on the CPU: the full and window
cores (``dl/gqa.py``) against attention written out over whole sequences,
the full core's kernel in the interpreter against XLA's loop, the served
generator through both kinds of cache against ``benchmark/reference/mimo.py``
(logits, not tokens), the window's forgetting and the sink, the expert layer
without a shared expert, the configuration's patterns and refusals, the cache
manager, the counters, and each control of the benchmark's check.
"""

import dataclasses
import json
import os
import re
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from alink_tpu.common.metrics import metrics
from alink_tpu.dl import attn_pallas as AP
from alink_tpu.dl import gqa as Q
from alink_tpu.dl import lm as L

HF = dict(
    model_type="mimo_v2_flash", vocab_size=320, hidden_size=64,
    intermediate_size=128, num_hidden_layers=7, num_attention_heads=8,
    num_key_value_heads=2, head_dim=16, v_head_dim=8, hidden_act="silu",
    layernorm_epsilon=1e-5, rope_theta=5000000, swa_rope_theta=10000,
    partial_rotary_factor=0.5, sliding_window=5, sliding_window_size=5,
    attention_value_scale=0.707, add_swa_attention_sink_bias=True,
    add_full_attention_sink_bias=False, attention_bias=False,
    tie_word_embeddings=False, swa_num_attention_heads=8,
    swa_num_key_value_heads=4, swa_head_dim=16, swa_v_head_dim=8,
    hybrid_layer_pattern=[0, 1, 1, 1, 1, 0, 1, 1, 1, 1, 1, 0],
    moe_layer_freq=[0] + [1] * 11, moe_intermediate_size=32,
    n_routed_experts=4, n_shared_experts=None, num_experts_per_tok=4,
    norm_topk_prob=True, scoring_func="sigmoid", n_group=1, topk_group=1,
    topk_method="noaux_tc", routed_scaling_factor=None,
    published={"n_routed_experts": 16}, deployment={"experts_held": [0, 4]})
DRAW = dict(weight_std=0.15, router_std=0.3, router_bias_std=0.05,
            sink_logit=[-1.0, 2.0])
# longer than the window (5), chunk edges at 4 or 7 that the window's do not
# share; one prompt shorter than the window
PROMPTS = [[5, 6, 7, 8, 9, 10, 11], [20, 21, 22], [30 + i for i in range(21)]]
NEW = 6
CHUNK = 4
POSITIONS = 48
# the program and the reference, both in float32 products at "highest":
# what remains is the order of float32 sums (the running softmax's blocks,
# the grouped products), some 1e-6 of a log-probability
TOL = 2e-4


@pytest.fixture(autouse=True)
def tracing_on(monkeypatch):
    monkeypatch.setenv("ALINK_TRACING", "on")


@pytest.fixture(scope="module")
def ckpt(tmp_path_factory):
    """One chip's share (experts 0-3 of 16) of a seeded toy checkpoint in the
    HF layout, written by the benchmark's generator."""
    from benchmark import gen, gen_mimo

    path = str(tmp_path_factory.mktemp("mimo") / "checkpoint")
    gen_mimo.write_checkpoint(path, HF, 7, DRAW, gen.make_vocab(HF["vocab_size"]))
    return path


@pytest.fixture(scope="module")
def model(ckpt):
    loaded = L.load_causal_lm(ckpt, slots=8, positions=POSITIONS)[0]
    return L.CausalLM(loaded.cfg, loaded.params, slots=8, positions=POSITIONS,
                      prefill_chunk=CHUNK)


@pytest.fixture(scope="module")
def exact(model):
    """The same model with float32 weights, operands and products."""
    cfg = dataclasses.replace(model.cfg, dtype="float32")
    params = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), model.params)
    return lambda chunk: L.CausalLM(cfg, params, slots=8, positions=POSITIONS,
                                    prefill_chunk=chunk)


# -- the two cores -------------------------------------------------------------

def _written_out(q, k, v, window, sink):
    """Attention over whole sequences as plainly as it can be written, in
    float64: q ``(B,L,Hq,D)``, k ``(B,L,Hkv,D)``, v ``(B,L,Hkv,Dv)``; query
    ``t`` sees ``t - window < s <= t``; the sink a column of the softmax."""
    q, k, v = (np.asarray(x, np.float64) for x in (q, k, v))
    B, Lq, Hq, D = q.shape
    g = Hq // k.shape[2]
    k, v = np.repeat(k, g, axis=2), np.repeat(v, g, axis=2)
    s = np.einsum("bthd,bshd->bhts", q, k) / np.sqrt(D)
    t = np.arange(Lq)
    seen = (t[None] <= t[:, None]) & (t[:, None] - t[None] < window)
    s = np.where(seen, s, -np.inf)
    m = s.max(-1, keepdims=True)
    if sink is not None:
        m = np.maximum(m, np.asarray(sink, np.float64)[None, :, None, None])
    p = np.exp(s - m)
    den = p.sum(-1)
    if sink is not None:
        den = den + np.exp(np.asarray(sink, np.float64)[None, :, None] - m[..., 0])
    return np.einsum("bhts,bshd->bthd", p, v) / den.transpose(0, 2, 1)[..., None]


def _qkv(B=2, Lq=23, Hq=8, hkv=2, D=16, Dv=8, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(ks[0], (B, Lq, Hq, D)),
            jax.random.normal(ks[1], (B, Lq, hkv, D)),
            jax.random.normal(ks[2], (B, Lq, hkv, Dv)))


def _through_window(q, k, v, W, sink, chunk, lens=None):
    B, Lq, _, D = q.shape
    hkv, Dv = k.shape[2], v.shape[-1]
    ring_k = jnp.zeros((B, hkv, D, W))
    ring_v = jnp.zeros((B, hkv, W, Dv))
    length = jnp.zeros((B,), jnp.int32)
    lens = jnp.full((B,), Lq) if lens is None else jnp.asarray(lens)
    outs = []
    for c in range(0, Lq, chunk):
        sl = slice(c, c + chunk)
        valid = (c + jnp.arange(q[:, sl].shape[1]))[None] < lens[:, None]
        o, ring_k, ring_v, length = Q.window_attend(
            q[:, sl], k[:, sl], v[:, sl], ring_k, ring_v, length, valid, sink,
            window=W, scale=D ** -0.5, dtype=jnp.float32)
        outs.append(o)
    return jnp.concatenate(outs, axis=1), (ring_k, ring_v, length)


@pytest.mark.parametrize("sink", [False, True], ids=["no_sink", "sink"])
@pytest.mark.parametrize("chunk", [1, 3, 4, 7, 23])
def test_window_core_chunk_by_chunk_is_attention_over_the_window(chunk, sink):
    """Across chunk edges that the window's (5) do not share, chunks shorter
    and longer than the window, and one chunk for the whole sequence."""
    with jax.default_matmul_precision("highest"):
        q, k, v = _qkv()
        s = jnp.linspace(-1.0, 2.0, 8) if sink else None
        got, _ = _through_window(q, k, v, 5, s, chunk)
    np.testing.assert_allclose(got, _written_out(q, k, v, 5, s), atol=2e-5)


def test_a_key_older_than_the_window_changes_no_window_output():
    """What a window layer reads is its ring and the chunk: keys and values
    past the window, changed, change nothing; one inside it does."""
    with jax.default_matmul_precision("highest"):
        q, k, v = _qkv()
        base, _ = _through_window(q, k, v, 5, None, 4)
        old_k = k.at[:, :10].set(100.0)
        old_v = v.at[:, :10].set(-100.0)
        moved, _ = _through_window(q, old_k, old_v, 5, None, 4)
        near, _ = _through_window(q, k.at[:, 12].set(3.0), v, 5, None, 4)
    np.testing.assert_array_equal(np.asarray(moved[:, 14:]), np.asarray(base[:, 14:]))
    assert np.abs(np.asarray(near[:, 12:17] - base[:, 12:17])).max() > 1e-3
    np.testing.assert_array_equal(np.asarray(near[:, 17:]), np.asarray(base[:, 17:]))


def test_the_ring_holds_the_rows_last_positions():
    """After rows of 23 and 9 positions in chunks of 4, slot ``p % 5`` holds
    position p of the last five, and the row's length is its own."""
    with jax.default_matmul_precision("highest"):
        q, k, v = _qkv()
        _, (ring_k, ring_v, length) = _through_window(q, k, v, 5, None, 4,
                                                      lens=[23, 9])
    np.testing.assert_array_equal(length, [23, 9])
    for b, n in enumerate((23, 9)):
        for p in range(n - 5, n):
            np.testing.assert_array_equal(ring_k[b, :, :, p % 5], k[b, p])
            np.testing.assert_array_equal(ring_v[b, :, p % 5], v[b, p])


def test_the_sink_joins_the_denominator_and_weighs_no_value():
    """With a sink s_h, a head's output is the one without it times
    sum e^l / (e^s + sum e^l): the same direction, a smaller norm."""
    with jax.default_matmul_precision("highest"):
        q, k, v = _qkv(Lq=9)
        s = jnp.linspace(-1.0, 2.0, 8)
        with_sink, _ = _through_window(q, k, v, 5, s, 9)
        without, _ = _through_window(q, k, v, 5, None, 9)
    qf, kf = np.asarray(q, np.float64), np.repeat(np.asarray(k, np.float64), 4, 2)
    lg = np.einsum("bthd,bshd->bhts", qf, kf) / 4.0
    t = np.arange(9)
    seen = (t[None] <= t[:, None]) & (t[:, None] - t[None] < 5)
    mass = np.where(seen, np.exp(lg), 0).sum(-1)                    # (B,H,T)
    share = (mass / (mass + np.exp(np.asarray(s, np.float64))[None, :, None]))
    np.testing.assert_allclose(with_sink, without * share.transpose(0, 2, 1)[..., None],
                               rtol=1e-4, atol=1e-6)
    assert share.min() < 0.5 and share.max() < 1.0       # the sink takes a part


def _through_full(q, k, v, chunk, block):
    B, Lq, _, D = q.shape
    hkv, Dv = k.shape[2], v.shape[-1]
    P = Lq + chunk + (-(Lq + chunk)) % block
    K, V = jnp.zeros((B, hkv, D, P)), jnp.zeros((B, hkv, P, Dv))
    length = jnp.zeros((B,), jnp.int32)
    outs = []
    for c in range(0, Lq, chunk):
        sl = slice(c, c + chunk)
        T = q[:, sl].shape[1]
        valid = jnp.ones((B, T), bool)
        start = length
        K, V, length = Q.cache_write(K, V, length, k[:, sl], v[:, sl], valid)
        hi = jnp.where(valid.any(1), length, 0)
        o = Q._blocks(Q._grouped(q[:, sl], hkv, jnp.float32), K, V, start, hi,
                      T, D ** -0.5, block)
        outs.append(Q._ungrouped(o, T))
    return jnp.concatenate(outs, axis=1)


@pytest.mark.parametrize("chunk,block", [(1, 8), (4, 8), (7, 16), (23, 32)])
def test_full_core_through_the_cache_is_causal_attention(chunk, block):
    """Chunks written to the cache and read back in blocks of keys with a
    running maximum and sum, the blocks fewer than the cache holds."""
    with jax.default_matmul_precision("highest"):
        q, k, v = _qkv()
        got = _through_full(q, k, v, chunk, block)
    np.testing.assert_allclose(got, _written_out(q, k, v, 10 ** 6, None),
                               atol=2e-5)


@pytest.mark.parametrize("chunk,rows", [(1, 16), (8, 16), (16, 32)])
def test_full_core_kernel_is_the_block_loop(chunk, rows):
    """``gqa_attend`` in the interpreter at the real widths (192 / 128)
    against XLA's loop, bfloat16 operands: rows of different lengths, one
    whose chunk holds none of its positions, blocks past a row's length."""
    B, hkv, D, Dv, P, block = 2, 2, 192, 128, 256, 64
    g = rows // chunk
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (B, hkv, rows, D)).astype(jnp.bfloat16)
    K = jax.random.normal(ks[1], (B, hkv, D, P)).astype(jnp.bfloat16)
    V = jax.random.normal(ks[2], (B, hkv, P, Dv)).astype(jnp.bfloat16)
    start = jnp.asarray([130, 7], jnp.int32)
    hi = jnp.asarray([130 + chunk, 0], jnp.int32)
    assert AP.use_gqa_attention(rows, D, Dv, P, block) is False   # CPU: off
    want = Q._blocks(q, K, V, start, hi, chunk, 0.07, block)
    got = AP.gqa_attention(q, K, V, start, hi, chunk, scale=0.07, block=block,
                           interpret=True)
    assert g * chunk == rows
    np.testing.assert_allclose(got, want, atol=2e-5)
    np.testing.assert_array_equal(np.asarray(got[1]), 0.0)


def test_the_full_core_takes_and_counts_the_kernel_where_its_gate_is_on(
        monkeypatch):
    """``full_attend`` down the kernel (in the interpreter) where the gate
    is on and the cache is in whole blocks: causal attention through the
    cache as the block loop gives it, counted as the kernel's trace."""
    monkeypatch.setenv("ALINK_ATTN_PALLAS", "1")
    monkeypatch.setattr(Q, "KV_BLOCK", 16)
    q, k, v = _qkv(Lq=16, D=64, Dv=128)
    K, V = jnp.zeros((2, 2, 64, 32)), jnp.zeros((2, 2, 32, 128))
    length = jnp.zeros((2,), jnp.int32)
    before = dict(metrics.counters("attention."))
    outs = []
    with jax.default_matmul_precision("highest"):
        for c in range(0, 16, 4):
            o, K, V, length = Q.full_attend(
                q[:, c:c + 4], k[:, c:c + 4], v[:, c:c + 4], K, V, length,
                jnp.ones((2, 4), bool), scale=0.125, dtype=jnp.float32)
            outs.append(o)
    grew = lambda n: metrics.counters("attention.").get(n, 0) - before.get(n, 0)
    assert grew("attention.gqa_fused_traces") == 4
    assert grew("attention.gqa_block_traces") == 0
    np.testing.assert_allclose(jnp.concatenate(outs, axis=1),
                               _written_out(q, k, v, 10 ** 6, None), atol=2e-5)


@pytest.mark.parametrize("T,start", [(64, [300, 5]), (8, [130, 0]), (1, [200, 3]),
                                     (64, [230, 100])],
                         ids=["prompt_chunk", "short_chunk", "decode_step",
                              "wrapping_chunk"])
def test_window_kernel_is_the_xla_form(monkeypatch, T, start):
    """``window_attend`` in the interpreter at the real widths (8 key/value
    heads of 8 queries, 192 / 128, a ring of 128) against XLA's form, the
    sink in, bfloat16 operands: the ring and the chunk as the kernel's
    operands of their own; a row with part of its chunk valid, one whose
    ring is not yet full, and chunks whose slots pass the ring's end. The
    ring left behind is the same array."""
    B, Hq, hkv, D, Dv, W = 2, 64, 8, 192, 128, 128
    ks = jax.random.split(jax.random.PRNGKey(5), 5)
    q = jax.random.normal(ks[0], (B, T, Hq, D))
    k = jax.random.normal(ks[1], (B, T, hkv, D))
    v = jax.random.normal(ks[2], (B, T, hkv, Dv))
    ring_k = jax.random.normal(ks[3], (B, hkv, D, W)).astype(jnp.bfloat16)
    ring_v = jax.random.normal(ks[4], (B, hkv, W, Dv)).astype(jnp.bfloat16)
    length = jnp.asarray(start, jnp.int32)
    valid = jnp.arange(T)[None] < jnp.asarray([[T], [max(1, T // 2)]])
    sink = jnp.linspace(2.0, 7.0, Hq)
    run = lambda: Q.window_attend(q, k, v, ring_k, ring_v, length, valid, sink,
                                  window=W, scale=D ** -0.5, dtype=jnp.bfloat16)
    c0 = dict(metrics.counters("attention."))
    want = run()
    monkeypatch.setenv("ALINK_ATTN_PALLAS", "1")
    got = run()
    grew = lambda n: metrics.counters("attention.").get(n, 0) - c0.get(n, 0)
    assert grew("attention.window_block_traces") == 1
    assert grew("attention.window_fused_traces") == 1
    # P is rounded to bfloat16 for its product on both sides, after sums
    # taken in another order: an element rounds the other way now and then
    np.testing.assert_allclose(got[0], want[0], atol=2e-3)
    for a, b in zip(got[1:], want[1:]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _rebuilt(ring_k, ring_v, length, k, v, valid):
    """The ring after a chunk as it was once computed: every slot gathered
    anew from the chunk and selected whole against the ring."""
    T, W = k.shape[1], ring_k.shape[3]
    end = length + valid.sum(axis=1)
    now = Q.ring_positions(end, W)
    fresh = now >= length[:, None]
    at = jnp.clip(now - length[:, None], 0, T - 1)
    new_k, new_v = (jnp.take_along_axis(x, at[:, :, None, None], axis=1)
                    for x in (k, v))
    return (jnp.where(fresh[:, None, None, :], new_k.transpose(0, 2, 3, 1),
                      ring_k),
            jnp.where(fresh[:, None, :, None], new_v.transpose(0, 2, 1, 3),
                      ring_v))


# (chunk, ring, each row's positions before the chunk, each row's valid
# ones). A ring of 16 or 8 is written whole (one select); one of 300 or
# more in the chunk's slots alone, a row at a time.
RING_CASES = {
    "random_lengths": (4, 16, [37, 5, 90, 16], [4, 4, 4, 4]),
    "a_row_with_none": (4, 16, [21, 9, 3, 40], [4, 0, 4, 0]),
    "part_of_a_chunk": (6, 16, [12, 30, 0, 7], [6, 3, 1, 5]),
    "decode_step": (1, 16, [15, 16, 0, 33], [1, 1, 0, 1]),
    "past_the_ring_end": (6, 16, [13, 27, 45, 62], [6, 6, 2, 5]),
    "ring_not_full": (4, 16, [0, 3, 8, 11], [4, 2, 4, 3]),
    "chunk_over_the_ring": (20, 8, [3, 0, 14, 6], [20, 11, 7, 17]),
    "rows_start_together": (4, 16, [24, 24, 9, 24], [4, 2, 0, 4]),
    "wide_ring": (64, 512, [300, 5, 1000, 130], [64, 64, 10, 0]),
    "wide_ring_past_its_end": (64, 512, [480, 500, 1023, 64], [64, 40, 64, 64]),
    "wide_ring_decode_step": (1, 384, [383, 0, 1000, 255], [1, 1, 0, 1]),
    "wide_ring_long_chunk": (200, 640, [600, 3, 250, 0], [200, 150, 0, 77]),
    "wide_ring_not_whole_tiles": (4, 300, [299, 130, 10, 290], [4, 4, 4, 2]),
}


@pytest.mark.parametrize("case", list(RING_CASES))
def test_the_ring_is_written_as_the_whole_ring_rebuilt(case):
    """The ring after ``window_attend`` holds exactly what rebuilding it
    whole from the chunk gives (a slot the chunk's valid positions do not
    reach keeps what it held), whether the ring is written whole or in the
    chunk's slots alone; and its outputs are XLA's form over the ring as
    it stood before the chunk."""
    T, W, lens, ns = RING_CASES[case]
    B, Hq, hkv, D, Dv = len(lens), 4, 2, 8, 8
    ks = jax.random.split(jax.random.PRNGKey(len(case)), 5)
    q, k, v = (jax.random.normal(kk, (B, T, h, d)) for kk, h, d in
               ((ks[0], Hq, D), (ks[1], hkv, D), (ks[2], hkv, Dv)))
    ring_k = jax.random.normal(ks[3], (B, hkv, D, W))
    ring_v = jax.random.normal(ks[4], (B, hkv, W, Dv))
    length = jnp.asarray(lens, jnp.int32)
    valid = jnp.arange(T)[None] < jnp.asarray(ns)[:, None]
    sink = jnp.linspace(-1.0, 1.0, Hq)
    with jax.default_matmul_precision("highest"):
        o, got_k, got_v, end = Q.window_attend(
            q, k, v, ring_k, ring_v, length, valid, sink, window=W,
            scale=D ** -0.5, dtype=jnp.float32)
        want_o = Q._window_xla(q, k, v, ring_k, ring_v,
                               length[:, None] + jnp.arange(T)[None],
                               Q.ring_positions(length, W), valid, sink,
                               window=W, scale=D ** -0.5, dtype=jnp.float32)
    want_k, want_v = _rebuilt(ring_k, ring_v, length, k, v, valid)
    np.testing.assert_array_equal(np.asarray(got_k), np.asarray(want_k))
    np.testing.assert_array_equal(np.asarray(got_v), np.asarray(want_v))
    np.testing.assert_array_equal(np.asarray(end), np.asarray(lens) + ns)
    np.testing.assert_allclose(o, want_o, atol=1e-5)


@pytest.mark.parametrize("fused", [False, True], ids=["xla_form", "kernel"])
@pytest.mark.parametrize("T", [4, 130, 1],
                         ids=["prompt_chunk", "long_prompt_chunk", "decode_step"])
def test_no_pass_over_the_whole_ring_is_lowered(monkeypatch, fused, T):
    """``window_attend`` lowered on the CPU at a toy size with a ring of
    four lane tiles donated, down XLA's form and down the kernel (in the
    interpreter): no concatenation as wide as ring and chunk side by side,
    no gather and no select of the ring's width, is in its program; the
    ring is written by slices in place."""
    if fused:
        monkeypatch.setenv("ALINK_ATTN_PALLAS", "1")
    B, Hq, hkv, D, Dv, W = 2, 8, 2, 64, 128, 512
    f = lambda q, k, v, rk, rv, n, ok: Q.window_attend(
        q, k, v, rk, rv, n, ok, jnp.zeros((Hq,)), window=W, scale=0.125,
        dtype=jnp.bfloat16)
    shape = lambda *dims, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(dims, dtype)
    c0 = metrics.counters("attention.").get("attention.window_fused_traces", 0)
    text = jax.jit(f, donate_argnums=(3, 4)).lower(
        shape(B, T, Hq, D), shape(B, T, hkv, D), shape(B, T, hkv, Dv),
        shape(B, hkv, D, W), shape(B, hkv, W, Dv),
        shape(B, dtype=jnp.int32), shape(B, T, dtype=jnp.bool_)).as_text()
    grew = metrics.counters("attention.").get("attention.window_fused_traces", 0) - c0
    assert grew == int(fused)
    ops = lambda name: re.findall(rf"stablehlo\.{name}.*-> tensor<([0-9x]+)x", text)
    assert not [t for t in ops("concatenate") if str(W + T) in t.split("x")]
    assert not [t for t in ops("gather") if str(W) in t.split("x")]
    assert not [t for t in ops("select") if str(W) in t.split("x")]
    assert "stablehlo.dynamic_update_slice" in text


# -- the served generator against the reference --------------------------------

def _reference_logprobs(ckpt, prompts, ids, lost_at=None, **fault):
    from benchmark.reference import mimo

    rows, visible = [], None if lost_at is None else []
    for p, e in zip(prompts, ids):
        seq = np.asarray(list(p) + list(e[:-1]))
        rows.append((seq, np.arange(len(seq)), len(p) - 1 + np.arange(len(e))))
        if lost_at is not None:
            t = np.minimum(np.arange(len(seq)), len(p) - 1)
            visible.append((t // lost_at * lost_at).astype(np.int32))
    logits, chosen = mimo.logits_at(mimo.Checkpoint(ckpt), HF, rows,
                                    fault.pop("precision", "f32"),
                                    visible_from=visible, **fault)
    at = np.arange(len(ids[0]))
    lp = np.stack([np.asarray(jax.nn.log_softmax(jnp.asarray(l), -1))[at, e]
                   for l, e in zip(logits, ids)])
    return lp, chosen


@pytest.mark.parametrize("chunk", [4, 7, 64])
def test_prefill_and_cached_steps_are_the_references_full_forward(ckpt, exact,
                                                                  chunk):
    """Logits, not tokens, with float32 products on both sides: the full
    layers through their cache, the window layers through their ring with
    its sink, the expert layer without a shared expert, against attention
    over whole sequences with the window and the sink as masks. Rows of
    different lengths in one batch, longer and shorter than the window."""
    with jax.default_matmul_precision("highest"):
        m = exact(chunk)
        ids, lps = m.generate(PROMPTS, NEW)
        want, chosen = _reference_logprobs(ckpt, PROMPTS, ids)
    np.testing.assert_allclose(lps, want, atol=TOL)
    for r, c in enumerate(chosen):
        counts = np.stack([np.bincount(l[l < 4], minlength=4) for l in c])
        np.testing.assert_array_equal(m.expert_load[r], counts)


@pytest.mark.parametrize("control,fault", [
    ("fp8", dict(precision="fp8")),
    ("swapped", dict()),
    ("window_as_full", dict(window_as_full=True)),
    ("sink_dropped", dict(sink_dropped=True)),
    ("chunk_cache_dropped", dict(lost_at=CHUNK))])
def test_each_control_of_the_check_fails_the_comparison(ckpt, exact, control,
                                                        fault):
    """What the benchmark's controls stand for, computed by the reference,
    lies far outside the program's agreement with it (``swapped``: each row's
    emitted ids after its neighbour's prompt)."""
    with jax.default_matmul_precision("highest"):
        ids, lps = exact(CHUNK).generate(PROMPTS, NEW)
        prompts = PROMPTS[1:] + PROMPTS[:1] if control == "swapped" else PROMPTS
        other, _ = _reference_logprobs(ckpt, prompts, ids, **fault)
    assert np.abs(lps - other).max() > 50 * TOL


def test_a_state_lost_between_chunks_is_the_references_control(ckpt, exact,
                                                              monkeypatch):
    """The fault ``chunk_cache_dropped`` stands for, planted in the program:
    every call of the prefill program starts from empty caches and rings."""
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
        lost, _ = _reference_logprobs(ckpt, PROMPTS, ids, lost_at=CHUNK)
    np.testing.assert_allclose(lps, lost, atol=TOL)


def test_a_rows_answer_does_not_depend_on_its_neighbours(model):
    ids, lps = model.generate(PROMPTS, NEW)
    for r, p in enumerate(PROMPTS):
        alone_ids, alone_lps = model.generate([p], NEW)
        np.testing.assert_array_equal(alone_ids[0], ids[r])
        np.testing.assert_allclose(alone_lps[0], lps[r], atol=1e-5)


def test_a_reused_slot_holds_nothing_of_its_last_sequence(model):
    """Both kinds of cache: a long batch fills keys, values, rings and
    counts; the short batch after it reads as on a fresh model, and its
    lengths are its own."""
    first = model.generate(PROMPTS, NEW)
    model.generate([[60 + i for i in range(30)]] * 5, NEW)
    again = model.generate(PROMPTS, NEW)
    np.testing.assert_array_equal(first[0], again[0])
    np.testing.assert_array_equal(first[1], again[1])
    for kept, kind in zip(model.cache.peek(), model.cfg.layer_types):
        np.testing.assert_array_equal(
            kept[2][:3], [len(p) + NEW - 1 for p in PROMPTS])
        np.testing.assert_array_equal(kept[2][3:], 0)      # rows beyond the batch


def test_the_cache_manager_lays_out_both_kinds_with_their_own_heads(model):
    cfg = model.cfg
    assert cfg.attention_dims("attention") == (2, 16, 8, 5e6)
    assert cfg.attention_dims("window") == (4, 16, 8, 1e4)
    state = L.StateCache(cfg, 8, POSITIONS).peek()   # the gauges are its own
    P = POSITIONS + L.HYBRID_PREFILL_CHUNK
    assert [len(kept) for kept in state] == [3, 4, 4, 4, 4, 4, 4]
    assert state[0][0].shape == (8, 2, 16, P) and state[0][0].dtype == jnp.bfloat16
    assert state[0][1].shape == (8, 2, P, 8) and state[0][2].shape == (8,)
    assert state[1][0].shape == (8, 4, 16, 5) and state[1][1].shape == (8, 4, 5, 8)
    assert state[5][0].shape == (8, 2, 16, P) and state[1][3].shape == (8, 4)
    assert metrics.gauge("lm.kv_cache_positions") == P
    assert metrics.gauge("lm.kv_cache_bytes") == 8 * P * 2 * 2 * (16 + 8) * 2
    assert metrics.gauge("lm.window_cache_bytes") == 8 * 5 * 5 * 4 * (16 + 8) * 2
    layer = model.params["layers"]
    assert layer[0]["k_proj"].shape == (2 * 16, 64)
    assert layer[1]["k_proj"].shape == (4 * 16, 64)
    assert layer[1]["attention_sink_bias"].shape == (8,)
    assert "attention_sink_bias" not in layer[5]
    assert not any(k.startswith("shared_") for k in layer[1])


def test_a_row_past_the_cache_is_refused_by_name(model):
    """The messages name the cache that grows with the sequence, whichever
    layer has it."""
    with pytest.raises(ValueError, match=r"positions a slot's cache holds .*"
                                         r"\(attention\)"):
        model.generate([[1] * (POSITIONS - NEW + 2)], NEW)
    with pytest.raises(ValueError, match=r"grows with the sequence "
                                         r"\(attention\) needs `positions`"):
        L.CausalLM(model.cfg, model.params, slots=8)
    with pytest.raises(ValueError, match="prompt chunk of 128"):
        L.CausalLM(model.cfg, model.params, slots=8, positions=POSITIONS,
                   prefill_chunk=128)
    model.generate([[1] * (POSITIONS - NEW + 1)], NEW)      # the last that fits


# -- the expert layer without a shared expert ----------------------------------

def test_the_sixteen_shares_of_an_expert_layer_add_up_to_the_uncut_layer():
    """Sixteen chips of one expert each, every one routing over all 16 with
    the sigmoid router's one group: their parts are the reference's layer
    with every expert held, and with no shared expert nothing is counted
    twice."""
    from benchmark.reference import mimo

    E_all, H, F, N = 16, 24, 12, 40
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    w = {"mlp.gate": jax.random.normal(ks[0], (E_all, H)) * 0.5,
         "mlp.gate.e_score_correction_bias": jax.random.normal(ks[1], (E_all,)) * 0.1}
    for e in range(E_all):
        kk = jax.random.split(jax.random.fold_in(ks[2], e), 3)
        w[f"mlp.experts.{e}.gate_proj"] = jax.random.normal(kk[0], (F, H)) * 0.3
        w[f"mlp.experts.{e}.up_proj"] = jax.random.normal(kk[1], (F, H)) * 0.3
        w[f"mlp.experts.{e}.down_proj"] = jax.random.normal(kk[2], (H, F)) * 0.3
    n = jax.random.normal(ks[3], (N, H))
    cfg = L.CausalLMConfig.from_hf(dict(HF, n_routed_experts=E_all,
                                        num_experts_per_tok=8, hidden_size=H,
                                        moe_intermediate_size=F))
    assert cfg.shared_intermediate_size == 0

    with jax.default_matmul_precision("highest"):
        # the program's layer takes the residual n and adds its output to
        # it; the reference takes n normed as the layer norms it
        mm = partial(mimo._mm, precision="f32")
        c = dict(num_experts_per_tok=8, routed_scaling_factor=None,
                 experts_held=(0, E_all))
        xs = n * jax.lax.rsqrt(jnp.mean(n * n, -1, keepdims=True) + 1e-5)
        whole, _ = mimo._experts(w, xs, c, mm)

        def share_of(e):
            layer = {"gate": w["mlp.gate"],
                     "post_attention_layernorm": jnp.ones(H),
                     "experts_gate_up": jnp.concatenate(
                         [w[f"mlp.experts.{e}.gate_proj"].T,
                          w[f"mlp.experts.{e}.up_proj"].T], 1)[None],
                     "experts_down": w[f"mlp.experts.{e}.down_proj"].T[None],
                     "expert_bias": w["mlp.gate.e_score_correction_bias"]}
            held = dataclasses.replace(cfg, experts_held=(e, e + 1),
                                       dtype="float32")
            y, load, _ = L._experts_ffn(held, layer, n[None],
                                        jnp.ones((1, N), bool),
                                        jnp.zeros((1, 1), jnp.int32))
            return y[0] - n, load

        parts = [share_of(e) for e in range(E_all)]
    np.testing.assert_allclose(sum(p[0] for p in parts), whole, atol=2e-5)
    assert sum(int(p[1].sum()) for p in parts) == N * 8


# -- configuration -------------------------------------------------------------

def test_from_hf_derives_both_patterns():
    cfg = L.CausalLMConfig.from_hf(dict(HF, num_hidden_layers=12,
                                        n_routed_experts=16,
                                        experts_held=[4, 8]))
    assert cfg.layer_types == ("attention",) + ("window",) * 4 + ("attention",) \
        + ("window",) * 5 + ("attention",)
    assert cfg.ffn_types == ("dense",) + ("experts",) * 11
    assert (cfg.swa_num_key_value_heads, cfg.num_key_value_heads) == (4, 2)
    assert (cfg.rotary_dim, cfg.sliding_window, cfg.swa_sink) == (8, 5, True)
    assert (cfg.rope_theta, cfg.swa_rope_theta) == (5e6, 1e4)
    assert cfg.attention_value_scale == 0.707 and cfg.rms_norm_eps == 1e-5
    assert cfg.experts_held == (4, 8) and cfg.num_experts == 16
    assert cfg.shared_intermediate_size == 0 and cfg.routed_scaling_factor == 1.0
    assert cfg.router_bias_name == "e_score_correction_bias"
    # the published widths' rotary: int(0.334 x 192) = 64
    assert L.CausalLMConfig.from_hf(dict(HF, head_dim=192, swa_head_dim=192,
                                         partial_rotary_factor=0.334)
                                    ).rotary_dim == 64


@pytest.mark.parametrize("key,value,match", [
    ("add_full_attention_sink_bias", True, "add_full_attention_sink_bias"),
    ("n_shared_experts", 1, "n_shared_experts"),
    ("scoring_func", "softmax", "scoring_func"),
    ("topk_method", "greedy", "topk_method"),
    ("n_group", 8, "n_group"),
    ("sliding_window_size", 4, "sliding_window 5 over the ring"),
    ("swa_num_attention_heads", 16, "swa_num_attention_heads"),
    ("hybrid_layer_pattern", [0, 1, 2, 1, 1, 0, 1], "hybrid_layer_pattern"),
])
def test_from_hf_refuses_what_the_block_does_not_compute(key, value, match):
    with pytest.raises(NotImplementedError, match=match):
        L.CausalLMConfig.from_hf(dict(HF, **{key: value}))


# -- counters and gauges -------------------------------------------------------

def test_counters_and_gauges_of_a_batch(model):
    """Host arithmetic once a batch: in each chunk of 4 a row with positions
    in it reads every position up to its chunk's last in a full layer, and
    the ring's positions and its chunk's in a window layer; a step, the same
    with a chunk of one."""
    c0 = dict(metrics.counters("attention."))
    h0 = metrics.histogram_states()
    model.generate(PROMPTS, NEW)
    grew = lambda n: metrics.counters("attention.").get(n, 0) - c0.get(n, 0)
    full = ring = 0
    for p in PROMPTS:
        n = len(p)
        for c in range(0, n, CHUNK):
            own = min(CHUNK, n - c)
            full += c + own
            ring += min(c, 5) + own
        for i in range(1, NEW):
            full += n + i
            ring += min(n + i - 1, 5) + 1
    assert grew("attention.full_positions_read") == 2 * full
    assert grew("attention.window_positions_read") == 5 * ring
    h1 = metrics.histogram_states()
    steps = h1["lm.step_kv_positions"]["count"] - (
        h0["lm.step_kv_positions"]["count"] if "lm.step_kv_positions" in h0 else 0)
    assert steps == NEW - 1
    assert "lm.step_latent_positions" not in h1 or \
        h1["lm.step_latent_positions"] == h0.get("lm.step_latent_positions")


@pytest.mark.parametrize("valid", [128, 40, 0])
def test_an_expert_layer_counts_its_rung_and_the_rows_it_cut(model, valid):
    """One call of an expert layer over 2 x 128 positions, 1,024 sorted
    rows: the rows through the held experts' products are the smallest rung
    that holds the held assignments, and with those cut they are every
    row."""
    from alink_tpu.dl import moe

    x = jax.random.normal(jax.random.PRNGKey(valid), (2, 128, 64))
    ok = jnp.broadcast_to(jnp.arange(128) < valid, (2, 128))
    load = jnp.zeros((2, 4), jnp.int32)
    _, added, (through, cut) = L._experts_ffn(model.cfg, model.params["layers"][1],
                                              x, ok, load)
    held = int(added.sum())
    assert int(through) + int(cut) == 2 * 128 * 4
    assert int(through) == min(r for r in moe.rungs(1024) if r >= held)
    assert held <= 2 * valid * 4 and (held > 0) == (valid > 0)


def test_a_batch_counts_the_rows_its_expert_layers_took_and_cut(model):
    """Chunks of 16 over 8 rows: 512 sorted rows a prompt chunk and layer,
    of which the held ones fit a lower rung, and 32 a decode step (one rung).
    The two counters a batch adds, from its one read, are every row of every
    call in all six expert layers."""
    m = L.CausalLM(model.cfg, model.params, slots=8, positions=POSITIONS,
                   prefill_chunk=16)
    c0 = dict(metrics.counters("moe."))
    m.generate(PROMPTS, NEW)
    grew = lambda n: metrics.counters("moe.").get(n, 0) - c0.get(n, 0)
    chunks = -(-max(map(len, PROMPTS)) // 16)
    assert grew("moe.rows_through") + grew("moe.rows_cut") == 6 * 4 * 8 * (
        chunks * 16 + NEW - 1)
    assert grew("moe.rows_cut") > 0
    assert grew("moe.rows_through") >= grew("moe.assignments_held") > 0
    assert metrics.gauge("lm.kv_cache_positions") == POSITIONS + L.HYBRID_PREFILL_CHUNK


def test_the_op_serves_the_checkpoint_behind_the_server(ckpt):
    from alink_tpu.dl.pretrained import load_vocab_file
    from alink_tpu.pipeline import CausalLMGenerator, PipelineModel
    from alink_tpu.serving import ModelServer, ServingConfig

    vocab = load_vocab_file(os.path.join(ckpt, "vocab.txt"))
    words = [t for t in vocab if len(t) == 6 and t.isalpha()]
    rng = np.random.default_rng(1)
    texts = [" ".join(words[j] for j in rng.integers(0, len(words), 3 + 4 * i))
             for i in range(4)]
    index = {t: i for i, t in enumerate(vocab)}
    prompts = [[index[w] for w in t.split()] for t in texts]
    lm, _ = L.load_causal_lm(ckpt, slots=8, positions=POSITIONS)
    want, _ = lm.generate(prompts, NEW)
    kw = dict(modelPath=ckpt, selectedCol="prompt", predictionCol="text",
              predictionDetailCol="detail", maxNewTokens=NEW, stateSlots=8,
              cachePositions=POSITIONS)
    server = ModelServer(ServingConfig(max_batch_rows=8, flush_deadline_s=0.05))
    try:
        server.load("lm", PipelineModel(CausalLMGenerator(**kw)),
                    "prompt string", warmup_rows=[(texts[0],)])
        rows = [f.result(120) for f in [server.submit("lm", (t,)) for t in texts]]
    finally:
        server.close()
    for r, ids, load in zip(rows, want, lm.expert_load):
        detail = json.loads(r[2])
        assert detail["ids"] == ids.tolist()
        assert detail["expert_load"] == load.tolist()
    lps = np.asarray([json.loads(r[2])["logprobs"] for r in rows])
    ref, _ = _reference_logprobs(ckpt, prompts, want)
    assert np.abs(lps - ref).max() < 0.4
