"""The ``afmoe`` stack (Trinity) at toy width on the CPU: the served
generator through both kinds of cache against
``benchmark/reference/trinity.py`` (logits, not tokens), each mechanism the
block gained (the output gate, the q/k norm, no rotary in full layers, the
post-norms) shown to weigh, the expert layer's shares with the shared expert
counted once, ``from_hf`` and its refusals, both kernels at 6 and 16 query
heads a key/value head against their XLA forms, the rows they pad, and each
control of the benchmark's check.
"""

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from alink_tpu.common.metrics import metrics
from alink_tpu.dl import gqa as Q
from alink_tpu.dl import lm as L

HF = dict(
    model_type="afmoe", vocab_size=320, hidden_size=64, intermediate_size=128,
    num_hidden_layers=5, num_attention_heads=12, num_key_value_heads=2,
    head_dim=16, hidden_act="silu", rms_norm_eps=1e-5, rope_theta=10000,
    rope_scaling=None, sliding_window=5, global_attn_every_n_layers=4,
    layer_types=["sliding_attention"] * 3 + ["full_attention"]
    + ["sliding_attention"] * 3 + ["full_attention"],
    num_dense_layers=1, num_experts=4, num_experts_per_tok=4,
    num_shared_experts=1, moe_intermediate_size=32, route_norm=True,
    route_scale=2.448, score_func="sigmoid", n_group=1, topk_group=1,
    num_expert_groups=1, num_limited_groups=1, mup_enabled=True,
    tie_word_embeddings=False, load_balance_coeff=5e-5, use_grouped_mm=True,
    published={"num_experts": 16}, deployment={"experts_held": [0, 4]})
DRAW = dict(weight_std=0.15, gate_std=0.3, router_std=0.3, router_bias_std=0.05)
# longer than the window (5), chunk edges at 4 or 7 that the window's do not
# share; one prompt shorter than the window
PROMPTS = [[5, 6, 7, 8, 9, 10, 11], [20, 21, 22], [30 + i for i in range(21)]]
NEW = 6
CHUNK = 4
POSITIONS = 48
# the program and the reference, both in float32 products at "highest":
# what remains is the order of float32 sums, some 1e-6 of a log-probability
TOL = 2e-4


@pytest.fixture(autouse=True)
def tracing_on(monkeypatch):
    monkeypatch.setenv("ALINK_TRACING", "on")


@pytest.fixture(scope="module")
def ckpt(tmp_path_factory):
    """One chip's share (experts 0-3 of 16) of a seeded toy checkpoint in the
    HF layout, written by the benchmark's generator."""
    from benchmark import gen, gen_trinity

    path = str(tmp_path_factory.mktemp("trinity") / "checkpoint")
    gen_trinity.write_checkpoint(path, HF, 7, DRAW,
                                 gen.make_vocab(HF["vocab_size"]))
    return path


@pytest.fixture(scope="module")
def model(ckpt):
    loaded = L.load_causal_lm(ckpt, slots=8, positions=POSITIONS)[0]
    return L.CausalLM(loaded.cfg, loaded.params, slots=8, positions=POSITIONS,
                      prefill_chunk=CHUNK)


@pytest.fixture(scope="module")
def exact(model):
    """The same model with float32 weights, operands and products, and any
    of the configuration's fields set otherwise."""
    params = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), model.params)

    def make(chunk=CHUNK, **fields):
        cfg = dataclasses.replace(model.cfg, dtype="float32", **fields)
        return L.CausalLM(cfg, params, slots=8, positions=POSITIONS,
                          prefill_chunk=chunk)

    return make


def _reference(ckpt, prompts, ids, lost_at=None, **fault):
    from benchmark.reference import trinity

    rows, visible = [], None if lost_at is None else []
    for p, e in zip(prompts, ids):
        seq = np.asarray(list(p) + list(e[:-1]))
        rows.append((seq, np.arange(len(seq)), len(p) - 1 + np.arange(len(e))))
        if lost_at is not None:
            t = np.minimum(np.arange(len(seq)), len(p) - 1)
            visible.append((t // lost_at * lost_at).astype(np.int32))
    logits, chosen, _ = trinity.logits_at(trinity.Checkpoint(ckpt), HF, rows,
                                          fault.pop("precision", "f32"),
                                          visible_from=visible, **fault)
    at = np.arange(len(ids[0]))
    lp = np.stack([np.asarray(jax.nn.log_softmax(jnp.asarray(l), -1))[at, e]
                   for l, e in zip(logits, ids)])
    return lp, chosen


# -- the served generator against the reference --------------------------------

@pytest.mark.parametrize("chunk", [4, 7, 64])
def test_prefill_and_cached_steps_are_the_references_full_forward(ckpt, exact,
                                                                  chunk):
    """Logits, not tokens, with float32 products on both sides: the full
    layer through its cache with no rotary, the sliding layers through their
    ring, gated heads with q/k norms, four norms a layer, the expert layer
    with its shared expert, against attention over whole sequences with the
    window as a mask. Rows of different lengths in one batch, longer and
    shorter than the window."""
    with jax.default_matmul_precision("highest"):
        m = exact(chunk)
        ids, lps = m.generate(PROMPTS, NEW)
        want, chosen = _reference(ckpt, PROMPTS, ids)
    np.testing.assert_allclose(lps, want, atol=TOL)
    for r, c in enumerate(chosen):
        counts = np.stack([np.bincount(l[l < 4], minlength=4) for l in c])
        np.testing.assert_array_equal(m.expert_load[r], counts)


@pytest.mark.parametrize("fault,fields", [
    ("gate_dropped", dict(attention_gate=False)),
    ("rope_on_full", dict(full_rotary=True)),
    ("window_as_full", dict(sliding_window=POSITIONS))])
def test_each_switch_weighs_and_is_the_references_fault(ckpt, exact, fault,
                                                        fields):
    """The program with a switch of the block turned is the reference with
    the matching fault, and both lie far from the block as published: the
    gate, NoPE in the full layer and the window each weigh."""
    with jax.default_matmul_precision("highest"):
        ids, base = exact().generate(PROMPTS, NEW)
        got_ids, lps = exact(**fields).generate(PROMPTS, NEW)
        want, _ = _reference(ckpt, PROMPTS, got_ids, **{fault: True})
        other, _ = _reference(ckpt, PROMPTS, ids, **{fault: True})
    np.testing.assert_allclose(lps, want, atol=TOL)
    assert np.abs(base - other).max() > 50 * TOL


def test_the_qk_norm_weighs(exact):
    """Without the per-head norm on queries and keys the scores move, and
    with them the log-probabilities."""
    with jax.default_matmul_precision("highest"):
        _, base = exact().generate(PROMPTS, NEW)
        _, without = exact(qk_norm=False).generate(PROMPTS, NEW)
    assert np.abs(base - without).max() > 50 * TOL


def test_the_post_norms_are_the_references_and_weigh(ckpt, exact, monkeypatch):
    """The two post-norms left out of the program (each sub-layer's output
    joins the residual stream as it is) are the reference's control
    ``post_norms_dropped``, far from the block with them."""
    with jax.default_matmul_precision("highest"):
        ids, base = exact().generate(PROMPTS, NEW)
        monkeypatch.setattr(L, "_joined", lambda cfg, layer, norm, x, y: x + y)
        # retention_eps has no part in this block: another value gives the
        # programs a cache key of their own, traced with the patch
        m = exact(retention_eps=0.5)
        got_ids, lps = m.generate(PROMPTS, NEW)
        want, _ = _reference(ckpt, PROMPTS, got_ids, post_norms_dropped=True)
    np.testing.assert_allclose(lps, want, atol=TOL)
    other, _ = _reference(ckpt, PROMPTS, ids, post_norms_dropped=True)
    assert np.abs(base - other).max() > 50 * TOL


@pytest.mark.parametrize("control,fault", [
    ("fp8", dict(precision="fp8")),
    ("swapped", dict()),
    ("chunk_cache_dropped", dict(lost_at=CHUNK)),
    ("window_as_full", dict(window_as_full=True)),
    ("gate_dropped", dict(gate_dropped=True)),
    ("rope_on_full", dict(rope_on_full=True)),
    ("post_norms_dropped", dict(post_norms_dropped=True))])
def test_each_control_of_the_check_fails_the_comparison(ckpt, exact, control,
                                                        fault):
    """What the benchmark's controls stand for, computed by the reference,
    lies far outside the program's agreement with it (``swapped``: each row's
    emitted ids after its neighbour's prompt)."""
    with jax.default_matmul_precision("highest"):
        ids, lps = exact().generate(PROMPTS, NEW)
        prompts = PROMPTS[1:] + PROMPTS[:1] if control == "swapped" else PROMPTS
        other, _ = _reference(ckpt, prompts, ids, **fault)
    assert np.abs(lps - other).max() > 50 * TOL


def test_a_rows_answer_does_not_depend_on_its_neighbours(model):
    ids, lps = model.generate(PROMPTS, NEW)
    for r, p in enumerate(PROMPTS):
        alone_ids, alone_lps = model.generate([p], NEW)
        np.testing.assert_array_equal(alone_ids[0], ids[r])
        np.testing.assert_allclose(alone_lps[0], lps[r], atol=1e-5)


# -- the expert layer's shares, the shared expert once -------------------------

def test_the_four_shares_of_an_expert_layer_add_up_to_the_uncut_layer():
    """Four chips of four experts each, every one routing over all 16 with
    the router's scale: their routed parts, with the shared expert that each
    computes alike counted once, are the reference's layer with every
    expert held (before the post-norm, which a deployment applies to the
    exchanged sum)."""
    from benchmark.reference import trinity

    E_all, H, F, N, K = 16, 24, 12, 40, 4
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    w = {"mlp.gate": jax.random.normal(ks[0], (E_all, H)) * 0.5,
         "mlp.gate.expert_bias": jax.random.normal(ks[1], (E_all,)) * 0.1}
    for e in range(E_all):
        kk = jax.random.split(jax.random.fold_in(ks[2], e), 3)
        w[f"mlp.experts.{e}.gate_proj"] = jax.random.normal(kk[0], (F, H)) * 0.3
        w[f"mlp.experts.{e}.up_proj"] = jax.random.normal(kk[1], (F, H)) * 0.3
        w[f"mlp.experts.{e}.down_proj"] = jax.random.normal(kk[2], (H, F)) * 0.3
    sk = jax.random.split(ks[3], 3)
    for name, k, shape in (("gate_proj", sk[0], (F, H)), ("up_proj", sk[1], (F, H)),
                           ("down_proj", sk[2], (H, F))):
        w[f"mlp.shared_experts.{name}"] = jax.random.normal(k, shape) * 0.3
    w["post_mlp_layernorm"] = jnp.ones(H)
    n = jax.random.normal(ks[4], (N, H))
    cfg = L.CausalLMConfig.from_hf(dict(HF, num_experts=E_all, hidden_size=H,
                                        moe_intermediate_size=F,
                                        num_experts_per_tok=K))
    assert cfg.shared_intermediate_size == F and cfg.routed_scaling_factor == 2.448
    spec = (("num_experts_per_tok", K), ("route_scale", 2.448),
            ("rms_norm_eps", 1e-5), ("experts_held", (0, E_all)))
    with jax.default_matmul_precision("highest"):
        xs = n * jax.lax.rsqrt(jnp.mean(n * n, -1, keepdims=True) + 1e-5)
        idx, weight, _ = trinity.route(w, xs, spec=spec, precision="f32")
        whole = trinity.experts_forward(
            w, jnp.zeros_like(xs), xs, idx, weight, jnp.bool_(False), spec=spec,
            precision="f32", capacity=N)
        shared = trinity._swiglu(xs, w["mlp.shared_experts.gate_proj"],
                                 w["mlp.shared_experts.up_proj"],
                                 w["mlp.shared_experts.down_proj"],
                                 partial(trinity._mm, precision="f32"))

        def share_of(lo):
            hi = lo + E_all // 4
            held = range(lo, hi)
            layer = {"gate": w["mlp.gate"], "pre_mlp_layernorm": jnp.ones(H),
                     "experts_gate_up": jnp.stack([jnp.concatenate(
                         [w[f"mlp.experts.{e}.gate_proj"].T,
                          w[f"mlp.experts.{e}.up_proj"].T], 1) for e in held]),
                     "experts_down": jnp.stack([w[f"mlp.experts.{e}.down_proj"].T
                                                for e in held]),
                     "expert_bias": w["mlp.gate.expert_bias"],
                     **{f"shared_{k}": w[f"mlp.shared_experts.{k}"]
                        for k in ("gate_proj", "up_proj", "down_proj")}}
            part = dataclasses.replace(cfg, experts_held=(lo, hi),
                                       dtype="float32", sandwich_norms=False)
            layer["post_attention_layernorm"] = layer["pre_mlp_layernorm"]
            y, load, _ = L._experts_ffn(part, layer, n[None],
                                        jnp.ones((1, N), bool),
                                        jnp.zeros((1, hi - lo), jnp.int32))
            return y[0] - n, load

        parts = [share_of(lo) for lo in range(0, E_all, E_all // 4)]
    np.testing.assert_allclose(sum(p[0] for p in parts) - 3 * shared, whole,
                               atol=2e-5)
    assert sum(int(p[1].sum()) for p in parts) == N * K


# -- configuration -------------------------------------------------------------

def test_from_hf_reads_the_afmoe_block():
    cfg = L.CausalLMConfig.from_hf(dict(HF, num_hidden_layers=8,
                                        num_dense_layers=2, num_experts=16,
                                        experts_held=[4, 8]))
    assert cfg.layer_types == ("window",) * 3 + ("attention",) \
        + ("window",) * 3 + ("attention",)
    assert cfg.ffn_types == ("dense",) * 2 + ("experts",) * 6
    assert (cfg.num_key_value_heads, cfg.swa_num_key_value_heads) == (2, 2)
    assert (cfg.head_dim, cfg.v_head_dim, cfg.swa_head_dim) == (16, 16, 16)
    assert (cfg.sliding_window, cfg.swa_sink, cfg.swa_rope_theta) == (5, False, 1e4)
    assert (cfg.qk_norm, cfg.full_rotary, cfg.attention_gate,
            cfg.sandwich_norms) == (True, False, True, True)
    assert cfg.embedding_scale == pytest.approx(8.0)      # sqrt(64)
    assert cfg.experts_held == (4, 8) and cfg.num_experts == 16
    assert cfg.routed_scaling_factor == 2.448 and cfg.shared_intermediate_size == 32
    assert cfg.router_bias_name == "expert_bias"
    shapes = L.tensor_shapes(cfg)
    p = "model.layers.3."
    assert shapes[p + "self_attn.g_proj.weight"] == (12 * 16, 64)
    assert shapes[p + "self_attn.q_norm.weight"] == (16,)
    assert {p + f"{n}.weight" for n in ("input_layernorm", "post_attention_layernorm",
                                        "pre_mlp_layernorm", "post_mlp_layernorm")
            } <= set(shapes)
    # layer_types left out: every fourth layer full
    derived = dict(HF)
    del derived["layer_types"]
    assert L.CausalLMConfig.from_hf(derived).layer_types == \
        ("window",) * 3 + ("attention", "window")


def test_the_families_before_afmoe_keep_the_switches_off():
    import json
    import os

    with open(os.path.join(os.path.dirname(os.path.dirname(__file__)), "benchmark",
                           "tests", "data", "configs", "toy_mimo.json")) as f:
        cfg = L.CausalLMConfig.from_hf(json.load(f))
    assert (cfg.qk_norm, cfg.full_rotary, cfg.attention_gate,
            cfg.sandwich_norms, cfg.embedding_scale) == (False, True, False,
                                                         False, 1.0)
    assert L.layer_norms(cfg) == ("input_layernorm", "post_attention_layernorm")


@pytest.mark.parametrize("key,value,match", [
    ("score_func", "softmax", "score_func"),
    ("route_norm", False, "route_norm"),
    ("mup_enabled", False, "mup_enabled"),
    ("tie_word_embeddings", True, "tie_word_embeddings"),
    ("attention_bias", True, "attention_bias"),
    ("rope_scaling", {"type": "yarn", "factor": 4.0}, "rope_scaling"),
    ("n_group", 8, "n_group"),
    ("num_expert_groups", 8, "num_expert_groups"),
    ("topk_group", 2, "topk_group"),
    ("num_limited_groups", 2, "num_limited_groups"),
    ("layer_types", ["sliding_attention", "chunked_attention"] * 4,
     "layer_types"),
    ("hidden_act", "gelu", "hidden_act"),
])
def test_from_hf_refuses_what_the_block_does_not_compute(key, value, match):
    with pytest.raises(NotImplementedError, match=match):
        L.CausalLMConfig.from_hf(dict(HF, **{key: value}))


def test_the_cache_manager_lays_out_rings_and_a_full_cache(model):
    cfg = model.cfg
    state = L.StateCache(cfg, 8, POSITIONS).peek()
    P = POSITIONS + L.HYBRID_PREFILL_CHUNK
    assert [len(kept) for kept in state] == [3, 4, 4, 4, 4]
    assert state[0][0].shape == (8, 2, 16, 5) and state[0][1].shape == (8, 2, 5, 16)
    assert state[3][0].shape == (8, 2, 16, P) and state[3][1].shape == (8, 2, P, 16)
    assert state[1][3].shape == (8, 4)
    assert metrics.gauge("lm.kv_cache_bytes") == 8 * P * 2 * 32 * 2
    assert metrics.gauge("lm.window_cache_bytes") == 8 * 5 * 4 * 2 * 32 * 2
    layer = model.params["layers"]
    assert layer[1]["g_proj"].shape == (12 * 16, 64)
    assert layer[3]["q_norm"].shape == (16,)
    assert layer[1]["shared_gate_proj"].shape == (32, 64)
    assert "post_mlp_layernorm" in layer[0] and "pre_mlp_layernorm" in layer[4]


# -- the kernels at 6 and 16 query heads a key/value head ----------------------

def _qkv(B, T, G, hkv=2, D=128, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(ks[0], (B, T, G * hkv, D)),
            jax.random.normal(ks[1], (B, T, hkv, D)),
            jax.random.normal(ks[2], (B, T, hkv, D)))


@pytest.mark.parametrize("G", [6, 16])
@pytest.mark.parametrize("T", [1, 4])
def test_the_full_kernel_takes_a_group_of_six_as_of_sixteen(monkeypatch, G, T):
    """``gqa_attend`` in the interpreter, a key/value head's rows padded to
    a tile where ``G x T`` is none (6 at a decode step), against XLA's loop
    over the same cache: rows of different lengths, bfloat16 operands."""
    monkeypatch.setattr(Q, "KV_BLOCK", 16)
    B, hkv, D, P = 2, 2, 128, 48
    q, k, v = _qkv(B, T, G)
    ks = jax.random.split(jax.random.PRNGKey(9), 2)
    K = jax.random.normal(ks[0], (B, hkv, D, P)).astype(jnp.bfloat16)
    V = jax.random.normal(ks[1], (B, hkv, P, D)).astype(jnp.bfloat16)
    length = jnp.asarray([20, 3], jnp.int32)
    valid = jnp.ones((B, T), bool)
    run = lambda: Q.full_attend(q, k, v, K, V, length, valid, scale=D ** -0.5,
                                dtype=jnp.bfloat16)
    c0 = dict(metrics.counters("attention."))
    want = run()
    monkeypatch.setenv("ALINK_ATTN_PALLAS", "1")
    got = run()
    grew = lambda n: metrics.counters("attention.").get(n, 0) - c0.get(n, 0)
    assert grew("attention.gqa_block_traces") == 1
    assert grew("attention.gqa_fused_traces") == 1
    np.testing.assert_allclose(got[0], want[0], atol=2e-5)
    for a, b in zip(got[1:], want[1:]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("G", [6, 16])
@pytest.mark.parametrize("T", [1, 4])
def test_the_window_kernel_takes_a_group_of_six_as_of_sixteen(monkeypatch, G, T):
    """``window_attend`` in the interpreter against XLA's form, a ring of
    16 and rows whose ring is full or not yet, one with part of its chunk
    valid; the ring left behind is the same array."""
    B, hkv, D, W = 2, 2, 128, 16
    q, k, v = _qkv(B, T, G, seed=1)
    ks = jax.random.split(jax.random.PRNGKey(4), 2)
    ring_k = jax.random.normal(ks[0], (B, hkv, D, W)).astype(jnp.bfloat16)
    ring_v = jax.random.normal(ks[1], (B, hkv, W, D)).astype(jnp.bfloat16)
    length = jnp.asarray([40, 5], jnp.int32)
    valid = jnp.arange(T)[None] < jnp.asarray([[T], [max(1, T // 2)]])
    run = lambda: Q.window_attend(q, k, v, ring_k, ring_v, length, valid, None,
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


def test_rows_added_to_reach_a_tile_are_the_kernels_own(monkeypatch):
    """6 rows of a decode step gain 2 where a kernel takes the call; a
    prompt chunk's 384 and MiMo's 16 or 8 gain none; nothing where the gate
    is off."""
    assert Q.tile_rows(6) == 8 and Q.tile_rows(384) == 384 and Q.tile_rows(9) == 16
    assert Q.rows_added("window", 6, 128, 128, 0) == 0           # CPU: off
    monkeypatch.setenv("ALINK_ATTN_PALLAS", "1")
    assert Q.rows_added("window", 6, 128, 128, 0) == 2
    assert Q.rows_added("attention", 6, 128, 128, 17408) == 2
    assert Q.rows_added("attention", 6, 128, 128, 17000) == 0    # not in blocks
    assert Q.rows_added("window", 384, 128, 128, 0) == 0
    assert Q.rows_added("window", 8, 192, 128, 0) == 0
    assert Q.rows_added("attention", 16, 192, 128, 17408) == 0
    assert Q.rows_added("window", 6, 16, 16, 0) == 0             # widths: XLA


def test_a_batch_counts_the_rows_padded_and_the_pairs_read(model, monkeypatch):
    """Host arithmetic once a batch: each decode step's 6 rows a key/value
    head gain 2 in every layer, row and head, the prompt chunks' 24 none;
    the window layers' positions read as the ring's and the chunk's own."""
    monkeypatch.setattr(Q, "rows_added",
                        lambda kind, rows, *a: Q.tile_rows(rows) - rows)
    c0 = dict(metrics.counters("attention."))
    model.generate(PROMPTS, NEW)
    grew = lambda n: metrics.counters("attention.").get(n, 0) - c0.get(n, 0)
    assert grew("attention.rows_padded") == 5 * 2 * 8 * (NEW - 1) * 2
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
    assert grew("attention.full_positions_read") == full
    assert grew("attention.window_positions_read") == 4 * ring


def test_on_the_cpu_no_row_is_padded(model):
    c0 = metrics.counters("attention.").get("attention.rows_padded", 0)
    model.generate(PROMPTS, NEW)
    assert metrics.counters("attention.").get("attention.rows_padded", 0) == c0
