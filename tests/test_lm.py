"""The causal language model at toy width on the CPU: the retention layer's
forms against each other, the served generator against the plain reference's
full forward pass (logits, not tokens), the state cache, the op on all three
paths, the ingest, the declared partition specs and the spans.
"""

import json
import math
import os

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np
import pytest

from alink_tpu.common.metrics import metrics
from alink_tpu.dl import lm as L
from alink_tpu.dl import retention as R

HF = dict(model_type="brumby", vocab_size=320, hidden_size=64, intermediate_size=128,
          num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
          head_dim=16, hidden_act="silu", rms_norm_eps=1e-6, rope_theta=1e6)
REF_CFG = dict(HF, assumed={"retention": {"normaliser_eps": 1e-6}})
PROMPTS = [[5, 6, 7, 8, 9, 10, 11], [20, 21, 22],
           [30, 31, 32, 33, 34, 35, 36, 37, 38, 39]]
NEW = 6
CHUNK = 4


@pytest.fixture(autouse=True)
def tracing_on(monkeypatch):
    monkeypatch.setenv("ALINK_TRACING", "on")


@pytest.fixture(scope="module")
def ckpt(tmp_path_factory):
    """A seeded toy checkpoint in the HF layout, written by the benchmark's
    generator (bfloat16 shards, index, vocab.txt)."""
    from benchmark import gen, gen_lm

    path = str(tmp_path_factory.mktemp("lm") / "checkpoint")
    gen_lm.write_checkpoint(path, HF, 7, 0.15, (2.0, 4.0),
                            gen.make_vocab(HF["vocab_size"]))
    return path


@pytest.fixture(scope="module")
def model(ckpt):
    """Chunks of 4 positions, so that these prompts of 3 to 10 tokens take
    one to three calls of the prefill program."""
    loaded = L.load_causal_lm(ckpt, slots=8)[0]
    return L.CausalLM(loaded.cfg, loaded.params, slots=8, prefill_chunk=CHUNK)


def _layer_inputs(L_=13, B=2, Hq=4, Hkv=2, D=8, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(ks[0], (B, L_, Hq, D)),
            jax.random.normal(ks[1], (B, L_, Hkv, D)),
            jax.random.normal(ks[2], (B, L_, Hkv, D)),
            jax.nn.log_sigmoid(jax.random.normal(ks[3], (B, L_, Hkv))))


def retention_prompt(q, k, v, log_g, S, z, *, chunk, eps):
    """A whole prompt of any length through ``retention_chunk``, ``chunk``
    positions at a time; the tail that does not fill a chunk is padded with
    invalid positions."""
    B, n = q.shape[:2]
    pad = (-n) % chunk
    padded = lambda x: jnp.pad(x, [(0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 2))
    q, k, v, log_g = (padded(x) for x in (q, k, v, log_g))
    valid = jnp.arange(n + pad) < n
    outs = []
    for c in range(0, n + pad, chunk):
        sl = slice(c, c + chunk)
        o, S, z = R.retention_chunk(
            q[:, sl], k[:, sl], v[:, sl], log_g[:, sl],
            jnp.broadcast_to(valid[sl], (B, chunk)), S, z, eps=eps)
        outs.append(o)
    return jnp.concatenate(outs, axis=1)[:, :n], S, z


def retention_attention(q, k, v, log_g, *, eps):
    """The layer in its attention form over a whole sequence, with no state:
    the definition (ISSUE 28) the program's two forms are held to."""
    B, n, Hq, D = q.shape
    Hkv = k.shape[2]
    s = jnp.einsum("btjgd,bsjd->bjgts", q.reshape(B, n, Hkv, Hq // Hkv, D),
                   k) / math.sqrt(D)
    cum = jnp.cumsum(log_g, axis=1).transpose(0, 2, 1)
    decay = jnp.where(jnp.tril(jnp.ones((n, n), bool)),
                      jnp.exp(jnp.minimum(cum[..., :, None] - cum[..., None, :],
                                          0.0)), 0.0)
    a = s * s * decay[:, :, None]
    num = jnp.einsum("bjgts,bsjd->btjgd", a, v)
    den = a.sum(-1).transpose(0, 3, 1, 2)
    return (num / (den[..., None] + eps)).reshape(B, n, Hq, D)


def test_power_embedding_gives_the_squared_scaled_product():
    q, k, _, _ = _layer_inputs()
    d = q.shape[-1]
    pq, pk = R.power_embed(q[:, :, :2]), R.power_embed(k)
    assert pq.shape[-1] == R.phi_dim(d) == d * (d + 1) // 2
    want = (jnp.einsum("blhd,blhd->blh", q[:, :, :2], k) / math.sqrt(d)) ** 2
    np.testing.assert_allclose(jnp.einsum("blhp,blhp->blh", pq, pk), want,
                               rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("chunk", [1, 4, 5, 13, 16])
def test_chunked_form_is_the_attention_form(chunk):
    """Chunk sizes that divide the length (1, 13), that do not (4, 5) and
    one longer than it (16)."""
    q, k, v, lg = _layer_inputs()
    B, _, _, D = q.shape
    Hkv, P = k.shape[2], R.phi_dim(D)
    want = retention_attention(q, k, v, lg, eps=1e-6)
    got, S, z = retention_prompt(q, k, v, lg, jnp.zeros((B, Hkv, P, D)),
                                   jnp.zeros((B, Hkv, P)), chunk=chunk, eps=1e-6)
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-4)
    # and the state it leaves is the recurrent form's
    Sr, zr = jnp.zeros((B, Hkv, P, D)), jnp.zeros((B, Hkv, P))
    for t in range(q.shape[1]):
        _, Sr, zr = R.retention_step(q[:, t], k[:, t], v[:, t], lg[:, t], None,
                                     Sr, zr, eps=1e-6)
    np.testing.assert_allclose(S, Sr, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(z, zr, rtol=1e-4, atol=1e-5)


def test_recurrent_form_is_the_attention_form():
    q, k, v, lg = _layer_inputs(seed=3)
    B, n, _, D = q.shape
    Hkv, P = k.shape[2], R.phi_dim(D)
    S, z, outs = jnp.zeros((B, Hkv, P, D)), jnp.zeros((B, Hkv, P)), []
    for t in range(n):
        o, S, z = R.retention_step(q[:, t], k[:, t], v[:, t], lg[:, t], None,
                                   S, z, eps=1e-6)
        outs.append(o)
    np.testing.assert_allclose(jnp.stack(outs, 1),
                               retention_attention(q, k, v, lg, eps=1e-6),
                               rtol=5e-3, atol=2e-3)


def test_a_padding_position_leaves_the_state_untouched():
    q, k, v, lg = _layer_inputs(L_=4)
    B, _, _, D = q.shape
    Hkv, P = k.shape[2], R.phi_dim(D)
    S0 = jax.random.normal(jax.random.PRNGKey(9), (B, Hkv, P, D))
    z0 = jnp.abs(jax.random.normal(jax.random.PRNGKey(8), (B, Hkv, P)))
    _, S, z = R.retention_chunk(q, k, v, lg, jnp.zeros((B, 4), bool), S0, z0,
                                eps=1e-6)
    np.testing.assert_array_equal(S, S0)
    np.testing.assert_array_equal(z, z0)
    _, S, z = R.retention_step(q[:, 0], k[:, 0], v[:, 0], lg[:, 0],
                               jnp.zeros((B,), bool), S0, z0, eps=1e-6)
    np.testing.assert_array_equal(S, S0)
    np.testing.assert_array_equal(z, z0)


def _reference_logprobs(ckpt, prompts, ids, lost_at=None):
    """The reference's log-probability of each emitted id, from its full
    forward over prompt + emitted ids (teacher-forced), and how far under its
    largest logit each emitted id's lies. ``lost_at``: the reference's
    control in which every prompt chunk of that size starts from nothing."""
    from benchmark.reference import brumby

    rows, visible = [], None if lost_at is None else []
    for p, e in zip(prompts, ids):
        seq = np.asarray(list(p) + list(e[:-1]))
        rows.append((seq, np.arange(len(seq)), len(p) - 1 + np.arange(len(e))))
        if lost_at is not None:
            t = np.minimum(np.arange(len(seq)), len(p) - 1)
            visible.append((t // lost_at * lost_at).astype(np.int32))
    logits = brumby.logits_at(brumby.Checkpoint(ckpt), REF_CFG, rows,
                              visible_from=visible)
    at = np.arange(len(ids[0]))
    lp = np.stack([np.asarray(jax.nn.log_softmax(jnp.asarray(l), -1))[at, e]
                   for l, e in zip(logits, ids)])
    margin = max(float((l.max(-1) - l[at, e]).max()) for l, e in zip(logits, ids))
    return lp, margin


@pytest.mark.parametrize("together", [True, False], ids=["one_batch", "each_alone"])
def test_prefill_and_cached_steps_are_the_references_full_forward(ckpt, model,
                                                                  together):
    """Logits, not tokens: rows of different prompt lengths in one batch,
    and each row alone. The program rounds operands to bfloat16; the
    reference is float32 (with its own products rounded to bfloat16 it reads
    0.02-0.044 from itself over three seeds of this checkpoint, the program
    0.014-0.035; float8 0.25-0.97; a state lost between chunks 2.7-5.9)."""
    if together:
        ids, lps = model.generate(PROMPTS, NEW)
    else:
        parts = [model.generate([p], NEW) for p in PROMPTS]
        ids = np.concatenate([a for a, _ in parts])
        lps = np.concatenate([b for _, b in parts])
    want, margin = _reference_logprobs(ckpt, PROMPTS, ids)
    np.testing.assert_allclose(lps, want, atol=0.06)
    assert margin < 0.06


def test_a_rows_answer_does_not_depend_on_its_neighbours(model):
    ids, lps = model.generate(PROMPTS, NEW)
    for r, p in enumerate(PROMPTS):
        alone_ids, alone_lps = model.generate([p], NEW)
        np.testing.assert_array_equal(alone_ids[0], ids[r])
        np.testing.assert_array_equal(alone_lps[0], lps[r])
    other = [[40, 41, 42, 43, 44, 45, 46, 47, 48], PROMPTS[1], [50, 51]]
    ids2, lps2 = model.generate(other, NEW)
    np.testing.assert_array_equal(ids2[1], ids[1])
    np.testing.assert_array_equal(lps2[1], lps[1])


def test_reused_slots_hold_nothing_of_the_last_batch(model):
    first = model.generate(PROMPTS, NEW)
    model.generate([[60 + i for i in range(12)]] * 5, NEW)   # fills five slots
    again = model.generate(PROMPTS, NEW)
    np.testing.assert_array_equal(first[0], again[0])
    np.testing.assert_array_equal(first[1], again[1])


def test_states_exchanged_after_prefill_change_the_answers(model):
    """The fault the benchmark's ``swapped`` control stands for: decode from
    another row's state reads other log-probabilities; a row whose state
    stayed reads its own."""
    ids, lps = model.generate(PROMPTS, NEW)
    lens = np.asarray([len(p) for p in PROMPTS] + [len(PROMPTS[-1])] * 5, np.int32)
    tok, lp = model._prefill(PROMPTS, lens, 8)
    idx = jnp.arange(8).at[0].set(2).at[2].set(0)
    model.cache.put([(S[idx], z[idx]) for S, z in model.cache.take()])
    ids2, lps2 = model._decode(tok, lp, lens, 3, 8, NEW)
    np.testing.assert_array_equal(lps2[1], lps[1])
    assert np.abs(lps2[[0, 2], 1:] - lps[[0, 2], 1:]).max() > 0.05


def test_a_state_lost_between_chunks_is_seen_and_is_the_references_control(
        ckpt, model, monkeypatch):
    """The fault the benchmark's ``chunk_state_dropped`` control stands for,
    planted in the program: every call of the prefill program starts from an
    empty state. The answers leave the reference's, and are what the
    reference reads when a position sees back to its chunk's start only."""
    real = model._program

    def lossy(kernel_id, builder, rows):
        prog = real(kernel_id, builder, rows)
        if kernel_id != "lm.prefill_chunk":
            return prog
        return lambda params, state, tokens, pos, first, *rest: prog(
            params, state, tokens, pos, True, *rest)

    monkeypatch.setattr(model, "_program", lossy)
    # each row alone: in a batch the planted fault would also empty the rows
    # whose prompt ended in an earlier chunk
    parts = [model.generate([p], NEW) for p in PROMPTS]
    ids = np.concatenate([a for a, _ in parts])
    lps = np.concatenate([b for _, b in parts])
    sound, _ = _reference_logprobs(ckpt, PROMPTS, ids)
    lost, _ = _reference_logprobs(ckpt, PROMPTS, ids, lost_at=CHUNK)
    # the 3-token prompt is one chunk and loses nothing
    np.testing.assert_allclose(lps[1], sound[1], atol=0.06)
    assert np.abs(lps[[0, 2]] - sound[[0, 2]]).max(axis=1).min() > 1.0
    np.testing.assert_allclose(lps, lost, atol=0.06)


def test_more_rows_than_slots_are_generated_in_groups(model):
    prompts = [[10 + i, 11 + i, 12 + i] for i in range(11)]
    ids, lps = model.generate(prompts, 3)
    assert ids.shape == lps.shape == (11, 3)
    np.testing.assert_array_equal(model.generate(prompts[9:10], 3)[0][0], ids[9])


def _generator_texts(vocab_words, n=5):
    rng = np.random.default_rng(1)
    return [" ".join(vocab_words[j] for j in rng.integers(0, len(vocab_words),
                                                         size=3 + 2 * i))
            for i in range(n)]


@pytest.mark.parametrize("path", ["batch_dag", "local_predictor", "model_server"])
def test_the_op_gives_the_same_ids_on_every_path(ckpt, path):
    from alink_tpu.common.mtable import MTable
    from alink_tpu.dl.pretrained import load_vocab_file
    from alink_tpu.operator.batch import (CausalLMGenerateBatchOp,
                                          TableSourceBatchOp)
    from alink_tpu.pipeline import (CausalLMGenerator, LocalPredictor,
                                    PipelineModel)
    from alink_tpu.serving import ModelServer, ServingConfig

    vocab = load_vocab_file(os.path.join(ckpt, "vocab.txt"))
    words = [t for t in vocab if len(t) == 6 and t.isalpha()]
    texts = _generator_texts(words)
    kw = dict(modelPath=ckpt, selectedCol="prompt", predictionCol="text",
              predictionDetailCol="detail", maxNewTokens=NEW, stateSlots=8)
    # what the model itself gives for these prompts' ids
    model, _ = L.load_causal_lm(ckpt, slots=8)
    index = {t: i for i, t in enumerate(vocab)}
    want, _ = model.generate([[index[w] for w in t.split()] for t in texts], NEW)
    if path == "batch_dag":
        out = CausalLMGenerateBatchOp(**kw).link_from(
            TableSourceBatchOp(MTable({"prompt": texts}))).collect()
        rows = [out.get_row(i) for i in range(out.num_rows)]
    elif path == "local_predictor":
        pred = LocalPredictor(PipelineModel(CausalLMGenerator(**kw)),
                              "prompt string")
        out = pred.predict_table(MTable.from_rows([(t,) for t in texts],
                                                  pred.input_schema))
        rows = [out.get_row(i) for i in range(out.num_rows)]
    else:
        server = ModelServer(ServingConfig(max_batch_rows=8, flush_deadline_s=0.05))
        try:
            server.load("lm", PipelineModel(CausalLMGenerator(**kw)),
                        "prompt string", warmup_rows=[(texts[0],)])
            futs = [server.submit("lm", (t,)) for t in texts]
            rows = [f.result(120) for f in futs]
        finally:
            server.close()
    assert [r[0] for r in rows] == texts
    for r, ids in zip(rows, want):
        detail = json.loads(r[2])
        assert detail["ids"] == ids.tolist()
        assert detail["prompt_tokens"] == len(r[0].split())
        assert len(detail["logprobs"]) == NEW and max(detail["logprobs"]) <= 0
        assert r[1].replace(" ", "") == "".join(
            vocab[i][2:] if vocab[i].startswith("##") else vocab[i] for i in ids)


def test_sharded_bf16_safetensors_round_trip(tmp_path, ckpt):
    """Shards written in the HF layout (here by the benchmark's writer) come
    back tensor by tensor, in the file's dtype, bit for bit, in the index's
    order of files."""
    from benchmark.gen_lm import _write_shard

    from alink_tpu.dl.pretrained import iter_safetensors, safetensors_files

    rng = np.random.default_rng(0)
    tensors = {f"t{i}.weight": rng.normal(size=(3 + i, 5)).astype(ml_dtypes.bfloat16)
               for i in range(6)}
    names = sorted(tensors)
    weight_map = {}
    for k in range(3):
        part = names[2 * k:2 * k + 2]
        _write_shard(str(tmp_path / f"model-{k}.safetensors"),
                     [(n, tensors[n]) for n in part])
        weight_map.update({n: f"model-{k}.safetensors" for n in part})
    with open(tmp_path / "model.safetensors.index.json", "w") as f:
        json.dump({"weight_map": weight_map}, f)
    assert [os.path.basename(f) for f in safetensors_files(str(tmp_path))] == [
        "model-0.safetensors", "model-1.safetensors", "model-2.safetensors"]
    back = dict(iter_safetensors(str(tmp_path)))
    assert sorted(back) == names
    for name, a in tensors.items():
        assert back[name].dtype == a.dtype and back[name].shape == a.shape
        np.testing.assert_array_equal(back[name].view(np.uint8), a.view(np.uint8))
    # and the seeded checkpoint's ten-odd shards hold every tensor once
    seen = [n for n, _ in iter_safetensors(ckpt)]
    assert len(seen) == len(set(seen)) == len(L.tensor_shapes(
        L.CausalLMConfig.from_hf(HF)))


def test_the_ingest_places_bfloat16_and_refuses_a_wrong_shape(ckpt, model):
    leaves = jax.tree_util.tree_leaves(model.params)
    assert all(x.dtype == jnp.bfloat16 for x in leaves)
    assert sum(x.size for x in leaves) == sum(
        int(np.prod(s)) for s in L.tensor_shapes(model.cfg).values())
    bad = dict(HF, num_key_value_heads=4)
    with pytest.raises(ValueError, match="shape"):
        from alink_tpu.dl.pretrained import iter_safetensors

        L.params_from_tensors(L.CausalLMConfig.from_hf(bad), iter_safetensors(ckpt))
    with pytest.raises(NotImplementedError, match="layer_types"):
        L.CausalLMConfig.from_hf(dict(HF, layer_types=["retention", "softmax"]))


def test_bf16_cast_keeps_two_bytes_a_value():
    from alink_tpu.common.quant import bf16_cast, bf16_round

    a = np.linspace(-3, 3, 17, dtype=np.float32)
    c = bf16_cast(a)
    assert c.dtype == ml_dtypes.bfloat16 and c.nbytes == 2 * a.size
    assert bf16_cast(c) is c
    np.testing.assert_array_equal(c.astype(np.float32), bf16_round(a))


@pytest.mark.parametrize("leaf,shape,spec", [
    ("layers/0/q_proj", (64, 64), ("model", None)),
    ("layers/1/k_proj", (32, 64), ("model", None)),
    ("layers/1/up_proj", (128, 64), ("model", None)),
    ("layers/0/o_proj", (64, 64), (None, "model")),
    ("layers/0/down_proj", (64, 128), (None, "model")),
    ("embed_tokens", (320, 64), ("model", None)),
    ("lm_head", (320, 64), ("model", None)),
    ("layers/0/g_proj", (2, 64), ()),
    ("layers/0/g_proj_bias", (2,), ()),
    ("layers/0/q_norm", (16,), ()),
    ("norm", (64,), ())])
def test_partition_specs_of_the_new_parameter_paths(leaf, shape, spec):
    from jax.sharding import PartitionSpec as P

    from alink_tpu.dl.sharding import _spec_for, make_dl_mesh, sharding_for

    assert _spec_for(leaf, shape) == P(*spec)
    mesh = make_dl_mesh(dp=4, tp=2)
    assert sharding_for(leaf, shape, mesh).spec == P(*spec)
    # an axis that does not divide the dimension falls back to replication
    assert sharding_for(leaf, (3, 5)[:len(shape)], mesh).spec == P()


def test_spans_and_counters_appear_once_a_batch(ckpt):
    from alink_tpu.common.mtable import MTable
    from alink_tpu.operator.batch import (CausalLMGenerateBatchOp,
                                          TableSourceBatchOp)
    from alink_tpu.dl.pretrained import load_vocab_file

    vocab = load_vocab_file(os.path.join(ckpt, "vocab.txt"))
    texts = _generator_texts([t for t in vocab if len(t) == 6 and t.isalpha()])

    def seen():
        h = metrics.histogram_states()
        spans = {n: (h[f"span.lm.{n}_s"]["count"] if f"span.lm.{n}_s" in h else 0)
                 for n in ("tokenize", "prefill", "decode", "detokenize",
                           "load_model")}
        steps = h["lm.decode_step_s"]["count"] if "lm.decode_step_s" in h else 0
        return spans, steps, dict(metrics.counters("lm."))

    before = seen()
    CausalLMGenerateBatchOp(
        modelPath=ckpt, selectedCol="prompt", predictionCol="text",
        maxNewTokens=NEW, stateSlots=8).link_from(
        TableSourceBatchOp(MTable({"prompt": texts}))).collect()
    after = seen()
    assert {n: after[0][n] - before[0][n] for n in after[0]} == {
        "tokenize": 1, "prefill": 1, "decode": 1, "detokenize": 1, "load_model": 1}
    assert after[1] - before[1] == NEW - 1
    grew = lambda n: after[2].get(n, 0) - before[2].get(n, 0)
    assert grew("lm.decode_tokens") == len(texts) * NEW
    assert grew("lm.prefill_tokens") == sum(len(t.split()) for t in texts)
    assert grew("lm.model_loads") == 1
    assert metrics.gauge("lm.state_slots") == 8
    assert metrics.gauge("lm.state_slots_in_use") == 0
    assert metrics.gauge("lm.state_bytes") == 8 * 2 * 2 * R.phi_dim(16) * 17 * 4
