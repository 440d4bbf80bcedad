"""Lower the generator's programs for the toy Brumby configuration, the
decoder's training step for the toy Moonlight one and BERT-tiny's train step
at one checkout, and print a digest of each. Run it at two checkouts: the
digests agree where the programs are the same text.

    JAX_PLATFORMS=cpu python docs/chip_calls/pr39/same_programs.py <checkout>
"""
import hashlib, json, os, sys
root = sys.argv[1]
sys.path.insert(0, root)
os.environ["JAX_PLATFORMS"] = "cpu"
import jax, jax.numpy as jnp, numpy as np
from alink_tpu.dl import lm
rows, P = 8, 176
out = {}
for name in ("toy_lm", "toy_moonlight"):
    hf = json.load(open(f"{root}/benchmark/tests/data/configs/{name}.json"))
    hf = {k: v for k, v in hf.items() if k != "fit"}
    cfg = lm.CausalLMConfig.from_hf(hf)
    rng = np.random.default_rng(0)
    params = lm.params_from_tensors(cfg, ((n, jnp.asarray(
        rng.standard_normal(s).astype(np.float32) * 0.02, jnp.bfloat16))
        for n, s in lm.tensor_shapes(cfg).items()))
    if name == "toy_moonlight":
        E = cfg.num_experts
        nexp = cfg.ffn_types.count("experts")
        tokens = jnp.zeros((2, 32), jnp.int32)
        bias = jnp.zeros((nexp, E), jnp.float32)
        f = jax.jit(lambda p, b, t: jax.grad(
            lambda p: lm.train_rows(cfg, p, b, t)[0].sum())(p))
        out[name + ".train"] = f.lower(params, bias, tokens).as_text()
        continue
    grows = bool(set(lm.GROWING) & set(cfg.layer_types)) if hasattr(lm, "GROWING") \
        else "mla" in cfg.layer_types
    state = tuple(tuple(jnp.zeros(s, d) for s, d in cfg.layer_state(i, rows, P if grows else 0))
                  for i in range(cfg.num_hidden_layers))
    T = cfg.prefill_chunk
    pre = lm._build_prefill_chunk(cfg, rows)
    out[name + ".prefill"] = pre.lower(
        params, state, jnp.zeros((rows, T), jnp.int32), jnp.zeros((rows, T), jnp.int32),
        jnp.bool_(True), jnp.zeros((rows,), jnp.int32),
        jnp.zeros((rows, cfg.hidden_size), jnp.float32)).as_text()
    dec = lm._build_decode_step(cfg, rows)
    out[name + ".decode"] = dec.lower(params, state, jnp.zeros((rows,), jnp.int32),
                                      jnp.zeros((rows,), jnp.int32)).as_text()
from alink_tpu.dl import train as T
from alink_tpu.dl.modules import BertConfig, TransformerEncoder
bert = TransformerEncoder(BertConfig.tiny(vocab_size=64, max_position=16,
                                          num_labels=2))
batch = {"input_ids": np.zeros((8, 16), np.int32),
         "attention_mask": np.ones((8, 16), np.int32),
         "token_type_ids": np.zeros((8, 16), np.int32)}
variables = bert.init(jax.random.PRNGKey(0), **batch, deterministic=True)
tx = T._make_optimizer(T.TrainConfig(learning_rate=1e-4, weight_decay=0.01), 10)
step = T.make_train_step(bert, tx, T._loss_fn("auto", False, weighted=True),
                         weighted=True, cache_key=("encoder-step-text",))
out["bert_tiny.train"] = step.lower(
    variables, tx.init(variables["params"]), batch, np.zeros(8, np.int32),
    np.ones(8, np.float32), jax.random.PRNGKey(1)).as_text()
for k, v in out.items():
    print(k, len(v), hashlib.sha256(v.encode()).hexdigest()[:16])
