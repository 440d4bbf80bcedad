"""The decoder block's training step at toy width on the CPU: the causal block
core against materialised scores, the expanded latent attention against the
absorbed form, the whole step against ``benchmark/reference/moonlight.py``,
the expert layer's shares and pieces, the encoder's step program unchanged,
``from_hf`` for ``deepseek_v3``, the partition specs of the training state, and
``CausalLMTrainBatchOp`` end to end into ``CausalLMGenerateBatchOp``.
"""

import contextlib
import dataclasses
import hashlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from alink_tpu.common.metrics import metrics
from alink_tpu.dl import lm as L
from alink_tpu.dl import mla as A
from alink_tpu.dl import moe as E
from alink_tpu.dl import train as T

HF = dict(
    model_type="deepseek_v3", vocab_size=320, hidden_size=64,
    intermediate_size=128, num_hidden_layers=3, num_attention_heads=4,
    num_key_value_heads=4, hidden_act="silu", rms_norm_eps=1e-5,
    rope_theta=50000, first_k_dense_replace=1, moe_layer_freq=1,
    kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    q_lora_rank=None, n_routed_experts=8, n_shared_experts=2,
    num_experts_per_tok=3, n_group=1, topk_group=1, norm_topk_prob=True,
    routed_scaling_factor=2.446, scoring_func="sigmoid",
    topk_method="noaux_tc", moe_intermediate_size=32,
    num_nextn_predict_layers=0, seq_aux=True, tie_word_embeddings=False,
    attention_bias=False, max_position_embeddings=64, ep_size=1,
    published={"n_routed_experts": 16}, deployment={"experts_held": [0, 8]},
    assumed={"balance_alpha": 1e-2, "bias_update_rate": 1e-3})
DRAW = dict(weight_std=0.15, router_std=0.3)
OPT = dict(b1=0.9, b2=0.999, eps=1e-8, weight_decay=0.01, learning_rate=1e-3,
           warmup_ratio=0.1)
T_ROW, BLOCK = 40, 16
ROWS = 16           # of the op's table


# the program's sizes at the toy width: the attention core's block, the
# sorted expert rows and the positions of the loss taken at a time
TOY_SIZES = ((A, "CAUSAL_BLOCK", BLOCK), (L, "TRAIN_EXPERT_PIECE", 16),
             (L, "TRAIN_LOSS_PIECE", 32))


@contextlib.contextmanager
def toy_sizes():
    """For a module's fixture, which runs before a test's ``monkeypatch``."""
    keep = [getattr(mod, name) for mod, name, _ in TOY_SIZES]
    for mod, name, value in TOY_SIZES:
        setattr(mod, name, value)
    try:
        yield
    finally:
        for (mod, name, _), value in zip(TOY_SIZES, keep):
            setattr(mod, name, value)


@pytest.fixture(autouse=True)
def tracing_on(monkeypatch):
    monkeypatch.setenv("ALINK_TRACING", "on")
    for mod, name, value in TOY_SIZES:
        monkeypatch.setattr(mod, name, value)


@pytest.fixture(scope="module")
def vocab():
    from benchmark import gen

    return gen.make_vocab(HF["vocab_size"])


@pytest.fixture(scope="module")
def ckpt(tmp_path_factory, vocab):
    """One chip's share (experts 0-7 of 16) of a seeded toy checkpoint in
    the HF layout, written by the benchmark's generator."""
    from benchmark import gen_moonlight

    path = str(tmp_path_factory.mktemp("moonlight") / "checkpoint")
    gen_moonlight.write_checkpoint(path, HF, 7, DRAW, vocab)
    return path


@pytest.fixture(scope="module")
def cfg(ckpt):
    with open(os.path.join(ckpt, "config.json")) as f:
        return L.CausalLMConfig.from_hf(json.load(f))


@pytest.fixture(scope="module")
def host_tensors(ckpt):
    """The checkpoint as the reference takes it: float32 tensors by HF
    name, the routers' biases apart."""
    from alink_tpu.dl.pretrained import iter_safetensors

    w = {n: np.asarray(a, np.float32) for n, a in iter_safetensors(ckpt)}
    names = sorted(n for n in w if n.endswith("e_score_correction_bias"))
    return w, np.stack([w.pop(n) for n in names])


@pytest.fixture(scope="module")
def batches():
    rng = np.random.default_rng(3)
    return [rng.integers(0, HF["vocab_size"], size=(2, T_ROW)).astype(np.int32)
            for _ in range(3)]


def _materialised(q, k, v, scale):
    s = jnp.einsum("bthd,bshd->bhts", q, k) * scale
    t = jnp.arange(q.shape[1])
    p = jax.nn.softmax(jnp.where(t[None, :] <= t[:, None], s, -jnp.inf), -1)
    return jnp.einsum("bhts,bshd->bthd", p, v)


@pytest.mark.parametrize("length", [48, 40, 16, 7])
def test_causal_core_matches_materialised_scores(length):
    """Forward and gradient, at lengths that are and are not a multiple of
    the block of 16, and shorter than one block."""
    rng = np.random.default_rng(length)
    q, k = (jnp.asarray(rng.normal(size=(2, length, 3, 24)), jnp.float32)
            for _ in range(2))
    v = jnp.asarray(rng.normal(size=(2, length, 3, 16)), jnp.float32)
    g = jnp.asarray(rng.normal(size=(2, length, 3, 16)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        core = lambda q, k, v: A.causal_core(q, k, v, scale=0.2,
                                             dtype=jnp.float32)
        plain = lambda q, k, v: _materialised(q, k, v, 0.2)
        out, pull = jax.vjp(core, q, k, v)
        want, pull_want = jax.vjp(plain, q, k, v)
        np.testing.assert_allclose(out, want, atol=2e-5)
        for got, ref in zip(pull(g), pull_want(g)):
            np.testing.assert_allclose(got, ref, atol=5e-5)


def test_causal_core_writes_no_square_of_the_length():
    """No tensor of the compiled forward and backward has two dimensions
    of the sequence length."""
    x = jnp.zeros((1, 64, 2, 24), jnp.float32)
    f = lambda q, k, v: A.causal_core(q, k, v, scale=0.2,
                                      dtype=jnp.float32).sum()
    text = jax.jit(jax.grad(f, argnums=(0, 1, 2))).lower(
        x, x, x[..., :16]).compile().as_text()
    assert "64,64" not in text


def test_expanded_form_matches_the_absorbed_form(cfg, ckpt):
    """A layer's mixer over whole sequences (keys and values a head, the
    block core) against ``dl/mla.attend`` through the cache, same weights."""
    cfg32 = dataclasses.replace(cfg, dtype="float32")
    layer = jax.tree.map(jnp.asarray,
                         L.training_variables(cfg32, ckpt)["params"]["layers"][1])
    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.normal(size=(2, T_ROW, cfg.hidden_size)), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(T_ROW), (2, T_ROW))
    with jax.default_matmul_precision("highest"):
        whole = L._mla_mixer_whole(cfg32, layer, a, pos)
        cached, _, length = L._mla_mixer(
            cfg32, layer, a, pos, jnp.ones((2, T_ROW), bool),
            jnp.zeros((2, T_ROW + 8, cfg.latent_width), jnp.float32),
            jnp.zeros((2,), jnp.int32))
    assert length.tolist() == [T_ROW, T_ROW]
    np.testing.assert_allclose(whole, cached, atol=2e-5)


def _program_steps(cfg, ckpt, batches, total_steps, dtype):
    """The first steps through ``make_train_step`` as ``train_model`` builds
    it: losses, the first gradient (from Adam's first moment), the state."""
    cfg = dataclasses.replace(cfg, dtype=dtype)
    assert cfg.balance_alpha == HF["assumed"]["balance_alpha"]
    model = L.CausalLMTrainer(cfg)
    tc = T.TrainConfig(learning_rate=OPT["learning_rate"], weight_decay=0.01,
                       loss="rows")
    tx = T._make_optimizer(tc, total_steps)
    step = T.make_train_step(model, tx, T._loss_fn("rows", False, weighted=True),
                             weighted=True)
    variables = jax.tree.map(jnp.asarray, L.training_variables(cfg, ckpt))
    start = jax.device_get(variables)
    opt_state = tx.init(variables["params"])
    losses, first_mu = [], None
    for tokens in batches:
        variables, opt_state, loss = step(
            variables, opt_state, {"tokens": tokens}, np.zeros(2, np.int32),
            np.ones(2, np.float32), jax.random.PRNGKey(0))
        losses.append(float(loss))
        if first_mu is None:
            first_mu = jax.device_get(next(
                s.mu for s in jax.tree.leaves(
                    opt_state, is_leaf=lambda s: hasattr(s, "mu"))
                if hasattr(s, "mu")))
    return cfg, losses, first_mu, start, jax.device_get(variables)


def _by_name(cfg, tree, bias=None):
    shape = (cfg.ffn_types.count("experts"), cfg.num_experts)
    router = {"expert_bias": np.zeros(shape) if bias is None else bias}
    return {n: np.asarray(a, np.float32)
            for shard in L.hf_tensors(cfg, {"params": tree, "router": router})
            for n, a in shard if not n.endswith("e_score_correction_bias")}


@pytest.fixture(scope="module")
def followed(host_tensors, batches):
    from benchmark.reference import moonlight

    w, bias = host_tensors
    return moonlight.follow_steps(w, bias, batches, HF, OPT, 10, block=BLOCK,
                                  keep_params=True)


@pytest.fixture(scope="module")
def stepped(cfg, ckpt, batches):
    with toy_sizes(), jax.default_matmul_precision("highest"):
        return _program_steps(cfg, ckpt, batches, 10, "float32")


def test_step_losses_match_the_reference(stepped, followed):
    _, losses, *_ = stepped
    np.testing.assert_allclose(losses, followed["loss"], rtol=2e-6)
    # the balance term is in the loss: alpha * mean over rows and layers
    assert all(l > c for l, c in zip(followed["loss"], followed["ce"]))


def test_step_first_gradient_matches_tensor_by_tensor(stepped, followed):
    cfg, _, first_mu, *_ = stepped
    got = _by_name(cfg, first_mu)
    assert set(got) == set(followed["first_grad"])
    for name, ref in followed["first_grad"].items():
        diff = np.linalg.norm(got[name] / (1 - OPT["b1"]) - ref)
        assert diff <= 2e-5 * max(np.linalg.norm(ref), 1e-3), name
    router = followed["first_grad"]["model.layers.1.mlp.gate.weight"]
    assert np.linalg.norm(router) > 0         # through w_i and the balance loss


def test_step_adamw_and_bias_match_the_reference(stepped, followed):
    cfg, _, _, start, after = stepped
    got, was = _by_name(cfg, after["params"]), _by_name(cfg, start["params"])
    for name, ref in followed["params"].items():
        np.testing.assert_allclose(got[name], ref, atol=1e-6, err_msg=name)
        moved = float(np.sum((got[name].astype(np.float64) - was[name]) ** 2))
        assert abs(moved - followed["delta_sq"][name]) \
            <= 1e-3 * followed["delta_sq"][name] + 1e-12, name
    # b moves by signs: exactly the reference's, and has moved
    np.testing.assert_array_equal(after["router"]["expert_bias"],
                                  followed["bias"])
    assert np.abs(followed["bias"]).max() > 0
    # every assignment of the three steps is in the counts kept on the device
    assert after["router"]["load"].sum(axis=1).tolist() == \
        [3 * 2 * T_ROW * HF["num_experts_per_tok"]] * 2


def test_step_in_bfloat16_is_as_near_as_bfloat16_is(cfg, ckpt, batches,
                                                    followed):
    cfg16, losses, first_mu, *_ = _program_steps(cfg, ckpt, batches[:1], 10,
                                                 "bfloat16")
    assert abs(losses[0] - followed["loss"][0]) < 0.02 * followed["loss"][0]
    got = _by_name(cfg16, first_mu)
    rel = sorted(np.linalg.norm(got[n] / (1 - OPT["b1"]) - ref)
                 / max(np.linalg.norm(ref), 1e-6)
                 for n, ref in followed["first_grad"].items())
    assert rel[len(rel) // 2] < 0.15 and rel[-1] < 1.0


def _expert_layer(cfg, ckpt, held):
    """Layer 1's expert layer as a share that holds ``held``."""
    cfg = dataclasses.replace(cfg, dtype="float32", experts_held=held)
    layer = L.training_variables(dataclasses.replace(
        cfg, experts_held=(0, 8)), ckpt)["params"]["layers"][1]
    layer = {k: jnp.asarray(v) for k, v in layer.items()}
    lo, hi = held
    layer["experts_gate_up"] = layer["experts_gate_up"][lo:hi]
    layer["experts_down"] = layer["experts_down"][lo:hi]
    return cfg, layer


def test_shares_add_up_to_the_uncut_layer(cfg, ckpt):
    """At 4 shares of 2 of 8 experts the held parts, the shared expert
    counted once, add up to the layer that holds all 8."""
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=(2, T_ROW, cfg.hidden_size)), jnp.float32)
    bias = jnp.zeros((cfg.num_experts,), jnp.float32)
    with jax.default_matmul_precision("highest"):
        whole_cfg, whole_layer = _expert_layer(cfg, ckpt, (0, 8))
        whole, _, counts = L._experts_ffn_whole(whole_cfg, whole_layer, x, bias, 0)
        n = L._rms_norm(x, whole_layer["post_attention_layernorm"],
                        cfg.rms_norm_eps)
        shared = L._swiglu(n, whole_layer["shared_gate_proj"],
                           whole_layer["shared_up_proj"],
                           whole_layer["shared_down_proj"])
        parts = x + shared
        for lo in range(0, 8, 2):
            c, layer = _expert_layer(cfg, ckpt, (lo, lo + 2))
            y, _, same = L._experts_ffn_whole(c, layer, x, bias, 16)
            parts = parts + (y - x - shared)
            np.testing.assert_array_equal(same, counts)
    np.testing.assert_allclose(parts, whole, atol=2e-5)


def test_held_experts_gradient_is_its_gradient_in_the_uncut_layer(cfg, ckpt):
    rng = np.random.default_rng(6)
    x = jnp.asarray(rng.normal(size=(2, T_ROW, cfg.hidden_size)), jnp.float32)
    g = jnp.asarray(rng.normal(size=(2, T_ROW, cfg.hidden_size)), jnp.float32)
    bias = jnp.zeros((cfg.num_experts,), jnp.float32)

    def grads(held, piece):
        c, layer = _expert_layer(cfg, ckpt, held)
        f = lambda up, down: (L._experts_ffn_whole(
            c, dict(layer, experts_gate_up=up, experts_down=down), x, bias,
            piece)[0] * g).sum()
        return jax.grad(f, argnums=(0, 1))(layer["experts_gate_up"],
                                           layer["experts_down"])

    with jax.default_matmul_precision("highest"):
        up_all, down_all = grads((0, 8), 0)
        up, down = grads((2, 4), 16)
    np.testing.assert_allclose(up, up_all[2:4], atol=2e-5)
    np.testing.assert_allclose(down, down_all[2:4], atol=2e-5)
    assert float(jnp.abs(up).max()) > 0


@pytest.mark.parametrize("piece", [0, 8, 64])
def test_no_token_dropped_under_a_skewed_router(piece):
    """Every token routed onto one held expert, the rows taken in pieces:
    the layer's output is that expert's over every token."""
    rng = np.random.default_rng(8)
    N, K, H, F, held = 40, 3, 16, 8, 4
    n = jnp.asarray(rng.normal(size=(N, H)), jnp.float32)
    gate_up = jnp.asarray(rng.normal(size=(held, H, 2 * F)), jnp.float32)
    down = jnp.asarray(rng.normal(size=(held, F, H)), jnp.float32)
    # expert 2 holds each token's first choice; the other two lie elsewhere
    local = jnp.tile(jnp.asarray([[2, held, held]], jnp.int32), (N, 1))
    w = jnp.asarray(rng.uniform(0.5, 1.0, size=(N, K)), jnp.float32)
    sizes = jnp.asarray([0, 0, N, 0], jnp.int32)
    with jax.default_matmul_precision("highest"):
        y = E.routed_experts(n, local, w, sizes, gate_up, down,
                             dtype=jnp.float32, piece=piece)
        gu = n @ gate_up[2]
        want = (jax.nn.silu(gu[:, :F]) * gu[:, F:] * w[:, :1]) @ down[2]
    np.testing.assert_allclose(y, want, atol=2e-5)
    assert float(jnp.abs(y).min(axis=1).max()) > 0      # no row left at zero


def test_bias_rule_and_balance_term():
    counts = jnp.asarray([5, 1, 3, 3], jnp.int32)
    np.testing.assert_allclose(
        E.bias_step(jnp.zeros(4), counts, 0.5), [-0.5, 0.5, 0.0, 0.0])
    logits = jnp.zeros((1, 6, 4), jnp.float32)           # even scores
    even = E.seq_balance(logits, jnp.asarray([[3, 3, 3, 3]]), top_k=2)
    np.testing.assert_allclose(even, [1.0])               # E/(K T) * K T / E
    # no gradient reaches the bias through the choice
    g = jax.grad(lambda b: E.route(jnp.ones((3, 4)) * jnp.arange(4.), b,
                                   n_group=1, topk_group=1, top_k=2,
                                   scale=1.0)[1].sum())(jnp.zeros(4))
    np.testing.assert_array_equal(g, np.zeros(4))


# sha256 of the lowered text of BERT-tiny's train step, taken on the parent
# of the PR that let the loop take a decoder (PR 34) and unchanged by it. A PR
# that changes the encoder's step on purpose takes it anew.
ENCODER_STEP_SHA256 = \
    "ca86365701dcb5e283eaa134c64c0aa896759e1e9e26490ac40a5a4e34ba646f"


def test_encoder_step_program_is_unchanged():
    from alink_tpu.dl.modules import BertConfig, TransformerEncoder

    cfg = BertConfig.tiny(vocab_size=64, max_position=16, num_labels=2)
    model = TransformerEncoder(cfg)
    batch = {"input_ids": np.zeros((8, 16), np.int32),
             "attention_mask": np.ones((8, 16), np.int32),
             "token_type_ids": np.zeros((8, 16), np.int32)}
    variables = model.init(jax.random.PRNGKey(0), **batch, deterministic=True)
    tx = T._make_optimizer(T.TrainConfig(learning_rate=1e-4, weight_decay=0.01),
                           10)
    step = T.make_train_step(model, tx, T._loss_fn("auto", False, weighted=True),
                             weighted=True, cache_key=("encoder-step-text",))
    text = step.lower(variables, tx.init(variables["params"]), batch,
                      np.zeros(8, np.int32), np.ones(8, np.float32),
                      jax.random.PRNGKey(1)).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == ENCODER_STEP_SHA256


def test_from_hf_reads_deepseek_v3(cfg):
    assert cfg.layer_types == ("mla",) * 3
    assert cfg.ffn_types == ("dense", "experts", "experts")
    assert (cfg.num_experts, cfg.experts_held, cfg.num_experts_per_tok) == \
        (16, (0, 8), 3)
    assert cfg.shared_intermediate_size == 2 * HF["moe_intermediate_size"]
    assert cfg.head_dim == 24 and not cfg.mla_head_gate
    names = L.tensor_shapes(cfg)
    assert "model.layers.1.mlp.gate.e_score_correction_bias" in names
    assert not any("g_proj" in n for n in names)
    assert "model.layers.0.mlp.gate_proj.weight" in names


@pytest.mark.parametrize("key,value", [
    ("q_lora_rank", 1536), ("rope_scaling", {"type": "yarn", "factor": 40}),
    ("num_nextn_predict_layers", 1), ("scoring_func", "softmax"),
    ("topk_method", "greedy"), ("moe_layer_freq", 2), ("hidden_act", "gelu")])
def test_from_hf_refuses_what_the_block_does_not_compute(key, value):
    with pytest.raises(NotImplementedError):
        L.CausalLMConfig.from_hf(dict(HF, **{key: value}))


def test_training_refuses_a_stack_without_a_backward_pass():
    brumby = L.CausalLMConfig(
        vocab_size=64, hidden_size=16, intermediate_size=32,
        num_hidden_layers=1, num_attention_heads=2, num_key_value_heads=2,
        head_dim=8, layer_types=("retention",))
    with pytest.raises(NotImplementedError, match="backward"):
        L.train_rows(brumby, {"layers": [{}]}, None,
                     jnp.zeros((1, 4), jnp.int32))


def test_partition_specs_of_the_training_state(cfg, ckpt):
    """The new leaves as specs: the router collection replicated, the mla
    layer's and the experts' leaves by their names, and the optimizer's
    moments under their parameter's spec."""
    from jax.sharding import PartitionSpec as P

    from alink_tpu.dl.sharding import _spec_for, param_shardings
    from alink_tpu.parallel.mesh import (AXIS_DATA, AXIS_EXPERT, AXIS_MODEL,
                                         make_mesh)

    assert _spec_for("router/expert_bias", (2, 16)) == P()
    assert _spec_for("router/load", (2, 16)) == P()
    assert _spec_for("params/layers/1/gate", (16, 64)) == P()
    assert _spec_for("params/layers/1/kv_a_proj_with_mqa", (40, 64)) == P()
    assert _spec_for("params/layers/1/kv_b_proj", (128, 32)) == P(AXIS_MODEL, None)
    assert _spec_for("params/layers/1/experts_gate_up", (8, 64, 64)) == \
        P(AXIS_EXPERT, None, None)
    mesh = make_mesh({AXIS_DATA: 2, AXIS_MODEL: 2, AXIS_EXPERT: 2},
                     devices=jax.devices()[:8])
    variables = L.training_variables(cfg, ckpt)
    tx = T._make_optimizer(T.TrainConfig(), 10)
    state = jax.eval_shape(tx.init, variables["params"])
    shard = param_shardings({"variables": variables, "opt": state}, mesh)
    assert shard["variables"]["router"]["load"].spec == P()
    mu = next(s.mu for s in jax.tree.leaves(
        shard["opt"], is_leaf=lambda s: hasattr(s, "mu")) if hasattr(s, "mu"))
    for got, want in zip(jax.tree.leaves(mu), jax.tree.leaves(
            shard["variables"]["params"])):
        assert got.spec == want.spec
    assert mu["layers"][1]["experts_down"].spec == P(AXIS_EXPERT, None, None)
    assert mu["layers"][0]["down_proj"].spec == P(None, AXIS_MODEL)


def test_pack_rows_lays_documents_end_to_end():
    from alink_tpu.dl.data import pack_rows

    rows = pack_rows([[1, 2, 3], [4], [5, 6, 7, 8]], 4, 0)
    assert rows.tolist() == [[1, 2, 3, 0], [4, 0, 5, 6]]     # 7, 8, 0 left over
    assert pack_rows([[1]], 4, 0).shape == (0, 4)


# -- the op, end to end --------------------------------------------------------

@pytest.fixture(scope="module")
def trained(ckpt, vocab, tmp_path_factory):
    """``CausalLMTrainBatchOp`` over a small table: 16 rows of 40 tokens, 8 a
    step (one a device of the tests' data mesh), 10 epochs: 20 steps."""
    from alink_tpu.common.mtable import MTable
    from alink_tpu.common.tracing import tracer
    from alink_tpu.operator.batch import CausalLMTrainBatchOp
    from alink_tpu.operator.batch.base import TableSourceBatchOp
    from benchmark import gen_moonlight

    docs, packed = gen_moonlight.make_documents(
        11, ROWS, T_ROW, {"median": 12, "sigma": 0.8, "cut": T_ROW}, vocab)
    out = str(tmp_path_factory.mktemp("trained") / "checkpoint")
    os.environ["ALINK_TRACING"] = "on"
    tracer.clear()
    before = dict(metrics.counters())
    with toy_sizes():
        table = CausalLMTrainBatchOp(
            textCol="text", checkpointFilePath=ckpt, outputPath=out,
            maxSeqLength=T_ROW, batchSize=8, numEpochs=10, learningRate=3e-3,
            randomSeed=5).link_from(
                TableSourceBatchOp(MTable({"text": docs}))).collect()
    grown = {k: v - before.get(k, 0) for k, v in metrics.counters().items()}
    spans = [s["name"] for s in tracer.spans()]
    return dict(path=out, table=table, counters=grown, spans=spans,
                packed=packed, docs=docs)


def test_op_loss_falls_over_twenty_steps(trained):
    row = trained["table"].to_rows()[0]
    meta = json.loads(row[1])
    assert row[0] == trained["path"] and meta["steps"] == 20
    assert meta["rows"] == ROWS and meta["tokens"] == ROWS * T_ROW
    loss = meta["epochLoss"]
    assert len(loss) == 10 and loss[-1] < 0.8 * loss[0]


def test_op_opens_the_fits_spans_and_counts(trained):
    for name in ("train.tokenize", "train.pack", "train.ingest_checkpoint",
                 "train.place_state", "train.epoch", "train.export_model"):
        assert name in trained["spans"], name
    assert trained["spans"].count("train.epoch") == 10
    c = trained["counters"]
    layers, k = 2, HF["num_experts_per_tok"]
    assert c["train.tokens"] == 10 * ROWS * T_ROW
    assert c["moe.assignments"] == 10 * ROWS * T_ROW * k * layers
    assert 0 < c["moe.assignments_held"] <= c["moe.assignments"]
    assert c["moe.bias_updates"] == 20 * layers
    assert c["attention.causal_block_traces"] >= 3
    assert metrics.gauge("train.state_bytes") > 0
    assert c.get("train.steps") == 20


def test_op_checkpoint_is_served_and_agrees_with_the_reference(trained, vocab):
    """The written checkpoint loads through ``load_causal_lm`` and
    ``CausalLMGenerateBatchOp`` serves it; prefill and cached steps agree with
    the reference's full forward over the trained weights (logits' log-
    probabilities of the emitted ids, teacher-forced)."""
    from alink_tpu.common.mtable import MTable
    from alink_tpu.dl.pretrained import iter_safetensors
    from alink_tpu.operator.batch import CausalLMGenerateBatchOp
    from alink_tpu.operator.batch.base import TableSourceBatchOp
    from benchmark.reference import moonlight

    path = trained["path"]
    with open(os.path.join(path, "config.json")) as f:
        assert json.load(f)["model_type"] == "deepseek_v3"
    w = {n: np.asarray(a, np.float32) for n, a in iter_safetensors(path)}
    names = sorted(n for n in w if n.endswith("e_score_correction_bias"))
    bias = np.stack([w.pop(n) for n in names])
    assert np.abs(bias).max() > 0              # the trained b was written
    prompts = [" ".join(d.split()[:n]) for d, n in zip(trained["docs"], (9, 5))]
    out = CausalLMGenerateBatchOp(
        modelPath=path, selectedCol="prompt", predictionCol="text",
        predictionDetailCol="detail", maxNewTokens=4, stateSlots=8,
        cachePositions=32).link_from(
            TableSourceBatchOp(MTable({"prompt": prompts}))).collect()
    rows = out.to_rows()
    tok = {t: i for i, t in enumerate(vocab)}
    spec = moonlight.spec_of(HF)
    on_device = {k: jnp.asarray(v) for k, v in w.items()}
    for prompt, row in zip(prompts, rows):
        detail = json.loads(row[-1])
        ids = [tok[p] for p in prompt.split()] + detail["ids"]
        assert detail["prompt_tokens"] == len(prompt.split())
        logits = np.asarray(moonlight.logits(
            on_device, jnp.asarray(bias), jnp.asarray(ids[:-1], jnp.int32),
            spec=spec, block=BLOCK))
        logp = logits - np.log(np.exp(logits - logits.max(-1, keepdims=True)
                                      ).sum(-1, keepdims=True)) \
            - logits.max(-1, keepdims=True)
        first = len(prompt.split()) - 1
        want = [logp[first + i, t] for i, t in enumerate(detail["ids"])]
        np.testing.assert_allclose(detail["logprobs"], want, atol=0.08)
