"""Plain reference for the BERT cells: tokenizer, forward pass, loss, gradients
and the AdamW update, in straightforward ``jax.numpy`` and float32 with every
matrix product at ``highest`` precision. It imports nothing of ``alink_tpu``
and takes nothing the program made: the weights are the seeded tensors of
``benchmark/gen.py`` under their HF names, the head is drawn here, the batches
are tokenised here.

A configuration file holds the published model's values at its top level and,
under ``departures``, what the program runs instead where it has no option
(today the tanh form of GELU, a layer-norm epsilon of 1e-6, no dropout on the
attention probabilities). ``as_run`` lays the second over the first, and this
file follows the result, so that once the program has an option a benchmark
PR deletes one key of the file and the reference follows the published value.

Two things are the program's free random choices and no result of a
computation; the reference re-derives both from ``randomSeed`` by the public
rule of the library the program is built on (flax folds the SHA-1 of a
module's path and a call counter into the key), so that it follows the very
steps the window's object took:

- the dropout masks of a training step: key ``fold_in(PRNGKey(seed), step)``,
  then one mask per dropout site, Bernoulli(0.9) over the activation's shape;
- the classifier head's fresh weights (lecun-normal, truncated at two sigma).

``precision="fp8"`` is the control: the same arithmetic with the operands and
the result of every matrix product rounded to float8 (e4m3, one scale per
tensor), as the program's are to bfloat16: the nearest precision below the one
the configurations state. Rounding the operands alone reads no worse than the
bfloat16 program does (PERF.md section 6).
"""

from __future__ import annotations

import hashlib
import math
from functools import partial
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Params = Dict[str, jax.Array]
HEAD_W, HEAD_B = "classifier.weight", "classifier.bias"
PRECISIONS = ("f32", "fp8")


def as_run(cfg: Dict) -> Dict:
    """The model as the program runs it: the published values with the
    configuration's ``departures`` laid over them."""
    run = {**cfg, **cfg.get("departures", {})}
    if run["attention_probs_dropout_prob"]:
        raise NotImplementedError("dropout on the attention probabilities: the "
                                  "reference has no mask rule for that site yet")
    return run


# -- tokenizer ---------------------------------------------------------------

def wordpiece_encode(text: str, vocab: Dict[str, int], max_len: int
                     ) -> Tuple[List[int], List[int], List[int]]:
    """BERT's uncased tokenisation of one document: lower-case, split on
    white space and ASCII punctuation, greedy longest-match word pieces,
    ``[CLS] ... [SEP]``, cut and padded to ``max_len``."""
    words, cur = [], []
    for ch in text.lower():
        if ch.isspace():
            if cur:
                words.append("".join(cur)); cur = []
        elif not ch.isalnum():
            if cur:
                words.append("".join(cur)); cur = []
            words.append(ch)
        else:
            cur.append(ch)
    if cur:
        words.append("".join(cur))
    pieces: List[int] = []
    for w in words:
        start, sub = 0, []
        while start < len(w):
            end = len(w)
            while end > start:
                cand = ("##" if start else "") + w[start:end]
                if cand in vocab:
                    break
                end -= 1
            if end == start:
                sub = [vocab["[UNK]"]]
                break
            sub.append(vocab[cand])
            start = end
        pieces += sub
    ids = [vocab["[CLS]"]] + pieces[:max_len - 2] + [vocab["[SEP]"]]
    mask = [1] * len(ids) + [0] * (max_len - len(ids))
    ids = ids + [vocab["[PAD]"]] * (max_len - len(ids))
    return ids, mask, [0] * max_len


def encode_batch(texts: Sequence[str], vocab_list: Sequence[str], max_len: int
                 ) -> Dict[str, np.ndarray]:
    vocab = {t: i for i, t in enumerate(vocab_list)}
    rows = [wordpiece_encode(t, vocab, max_len) for t in texts]
    return {"input_ids": np.asarray([r[0] for r in rows], np.int32),
            "attention_mask": np.asarray([r[1] for r in rows], np.int32),
            "token_type_ids": np.asarray([r[2] for r in rows], np.int32)}


# -- the program's free random choices, re-derived ----------------------------

def _fold_path(key: jax.Array, path: Sequence) -> jax.Array:
    """flax's public rule for a module's key: fold the first four bytes of the
    SHA-1 of the path (names and the call counter, with no separator, which
    is flax's default) into the key."""
    m = hashlib.sha1()
    for x in path:
        m.update(x.encode() if isinstance(x, str)
                 else x.to_bytes((x.bit_length() + 7) // 8, "big"))
    return jax.random.fold_in(key, jnp.uint32(int.from_bytes(m.digest()[:4], "big")))


def dropout_sites(num_layers: int) -> List[Tuple]:
    """Module paths of the program's dropout sites, in forward order:
    embeddings, then attention output and MLP output of every layer."""
    sites: List[Tuple] = [("Dropout_0", 1)]
    for i in range(num_layers):
        sites += [(f"layer_{i}", "Dropout_0", 1), (f"layer_{i}", "Dropout_1", 1)]
    return sites


def head_init(cfg: Dict, num_labels: int, random_seed: int) -> Params:
    """The fresh classifier head as flax draws it for ``Dense(name='head')``
    from ``PRNGKey(random_seed)``: lecun-normal kernel, zero bias."""
    key = _fold_path(jax.random.PRNGKey(random_seed), ("head", 1))
    h = cfg["hidden_size"]
    std = math.sqrt(1.0 / h) / 0.87962566103423978
    w = jax.random.truncated_normal(key, -2.0, 2.0, (h, num_labels), jnp.float32) * std
    return {HEAD_W: w.T, HEAD_B: jnp.zeros((num_labels,), jnp.float32)}


# -- arithmetic ---------------------------------------------------------------

def _round_fp8(x: jax.Array) -> jax.Array:
    """Nearest value of the float8 e4m3 grid (three mantissa bits, least
    normal exponent -6) after scaling the tensor's largest magnitude to 448,
    in float32 arithmetic, so that every backend rounds alike."""
    s = jax.lax.stop_gradient(jnp.max(jnp.abs(x))) / 448.0 + 1e-30
    y = x / s
    _, e = jnp.frexp(y)                     # |y| = m * 2**e, m in [0.5, 1)
    step = jnp.ldexp(jnp.float32(1.0), jnp.maximum(e, -5) - 4)
    return jnp.round(y / step) * step * s


@jax.custom_vjp
def _fp8(x: jax.Array) -> jax.Array:
    """Round to float8 (e4m3) with one scale per tensor; the cotangent is
    rounded the same way with a scale of its own, as a float8 backward pass
    would (an unscaled cast would flush most gradients to zero)."""
    return _round_fp8(x)


_fp8.defvjp(lambda x: (_round_fp8(x), None), lambda _, g: (_round_fp8(g),))


def _mm(spec: str, a: jax.Array, b: jax.Array, precision: str) -> jax.Array:
    """A matrix product. At ``fp8`` its operands and its result are rounded
    to float8, as the program's are to bfloat16: the compute type one step
    down, with the accumulation in float32 as on the chip."""
    if precision == "fp8":
        a, b = _fp8(a), _fp8(b)
    out = jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST)
    return _fp8(out) if precision == "fp8" else out


def _layer_norm(x, g, b, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * g + b


def _act(x, name: str):
    if name in ("gelu_pytorch_tanh", "gelu_new"):
        return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                          * (x + 0.044715 * x ** 3)))
    if name == "gelu":
        return 0.5 * x * (1.0 + jax.lax.erf(x / math.sqrt(2.0)))
    raise ValueError(f"unknown hidden_act {name!r}")


def _keep(step_key, path, rate: float, shape, row_offset, rows: int):
    """The slice for ``rows`` rows from ``row_offset`` of the whole batch's
    mask at one dropout site; None where dropout is off."""
    if step_key is None or rate == 0.0:
        return None
    keep = jax.random.bernoulli(_fold_path(step_key, path), 1.0 - rate, shape)
    return jax.lax.dynamic_slice_in_dim(keep, row_offset, rows, 0)


def _drop(x, keep, rate: float):
    return x if keep is None else jnp.where(keep, x / (1.0 - rate), 0.0)


def _layer(x, mask, lp: Params, keeps, cfg: Dict, precision: str):
    b, s, h = x.shape
    nh = cfg["num_attention_heads"]
    d = h // nh
    rate = cfg["hidden_dropout_prob"]

    def lin(name, t):
        return _mm("bsi,oi->bso", t, lp[name + ".weight"], precision) \
            + lp[name + ".bias"]

    q = lin("attention.self.query", x).reshape(b, s, nh, d)
    k = lin("attention.self.key", x).reshape(b, s, nh, d)
    v = lin("attention.self.value", x).reshape(b, s, nh, d)
    sc = _mm("bqhd,bkhd->bhqk", q, k, precision) / math.sqrt(d)
    sc = jnp.where(mask[:, None, None, :] > 0, sc, -1e30)
    p = jax.nn.softmax(sc, axis=-1)
    o = _mm("bhqk,bkhd->bqhd", p, v, precision).reshape(b, s, h)
    a = _drop(lin("attention.output.dense", o), keeps[0], rate)
    x = _layer_norm(x + a, lp["attention.output.LayerNorm.weight"],
                    lp["attention.output.LayerNorm.bias"], cfg["layer_norm_eps"])
    f = _act(lin("intermediate.dense", x), cfg["hidden_act"])
    f = _drop(lin("output.dense", f), keeps[1], rate)
    return _layer_norm(x + f, lp["output.LayerNorm.weight"],
                       lp["output.LayerNorm.bias"], cfg["layer_norm_eps"])


def forward(params: Params, batch: Dict[str, jax.Array], cfg: Dict, *,
            step_key: Optional[jax.Array] = None, row_offset=0,
            rows_total: Optional[int] = None, precision: str = "f32"
            ) -> jax.Array:
    """Logits (rows, labels). ``step_key`` switches the training-mode dropout
    on; a block of rows of a larger batch passes ``row_offset``/``rows_total``
    so that it uses its slice of the whole batch's masks. Every layer is
    rematerialised in the backward pass, so that the float32 activations of a
    block of rows fit beside the program's freed memory."""
    cfg = as_run(cfg)
    ids, mask, types = (batch["input_ids"], batch["attention_mask"],
                        batch["token_type_ids"])
    b, s = ids.shape
    rate = cfg["hidden_dropout_prob"]
    shape = (rows_total or b, s, cfg["hidden_size"])
    sites = dropout_sites(cfg["num_hidden_layers"])
    keep = lambda j: _keep(step_key, sites[j], rate, shape, row_offset, b)

    pre = "bert.embeddings."
    x = (params[pre + "word_embeddings.weight"][ids]
         + params[pre + "position_embeddings.weight"][jnp.arange(s)][None]
         + params[pre + "token_type_embeddings.weight"][types])
    x = _layer_norm(x, params[pre + "LayerNorm.weight"],
                    params[pre + "LayerNorm.bias"], cfg["layer_norm_eps"])
    x = _drop(x, keep(0), rate)
    layer = jax.checkpoint(
        lambda x, lp, keeps: _layer(x, mask, lp, keeps, cfg, precision))
    for i in range(cfg["num_hidden_layers"]):
        p = f"bert.encoder.layer.{i}."
        lp = {k[len(p):]: v for k, v in params.items() if k.startswith(p)}
        x = layer(x, lp, (keep(1 + 2 * i), keep(2 + 2 * i)))
    pooled = jnp.tanh(_mm("bi,oi->bo", x[:, 0], params["bert.pooler.dense.weight"],
                          precision) + params["bert.pooler.dense.bias"])
    return _mm("bi,oi->bo", pooled, params[HEAD_W], precision) + params[HEAD_B]


def predict_proba(params: Params, batch: Dict[str, np.ndarray], cfg: Dict, *,
                  precision: str = "f32", block_rows: int = 32) -> np.ndarray:
    """Class probabilities of a served batch, deterministic mode, in blocks
    of rows."""
    fn = jax.jit(lambda p, b: jax.nn.softmax(
        forward(p, b, cfg, precision=precision), axis=-1))
    n = batch["input_ids"].shape[0]
    out = [np.asarray(fn(params, {k: jnp.asarray(v[i:i + block_rows])
                                  for k, v in batch.items()}))
           for i in range(0, n, block_rows)]
    return np.concatenate(out, axis=0)


# -- training: loss, gradients, AdamW ----------------------------------------

def lr_at(step: int, opt: Dict, total_steps: int) -> float:
    """Linear warm-up from 0 over ``warmup_ratio`` of the job, then cosine
    decay to 0 at its end: the rate the optimizer uses at ``step`` (from 0)."""
    warm = max(1, int(total_steps * opt["warmup_ratio"]))
    end = max(total_steps, warm + 1)
    peak = opt["learning_rate"]
    if step < warm:
        return peak * step / warm
    frac = min(step - warm, end - warm) / (end - warm)
    return peak * 0.5 * (1.0 + math.cos(math.pi * frac))


def follow_steps(params: Params, batches: Sequence[Dict[str, np.ndarray]],
                 labels: Sequence[np.ndarray], cfg: Dict, opt: Dict,
                 total_steps: int, random_seed: int, *, precision: str = "f32",
                 block_rows: int = 8, fault: Optional[str] = None) -> Dict:
    """Follow the job's first ``len(batches)`` optimizer steps. Returns each
    step's loss, the first gradient (on the host), and the squared norm of
    every tensor's change after the last step.

    ``fault`` plants what a broken step would do, for the tests and the
    readings of PERF.md: ``half_batch`` leaves the second half of every batch
    out and takes the mean over the rest; ``frozen`` returns the state
    unchanged."""
    n = int(labels[0].shape[0])
    used = n // 2 if fault == "half_batch" else n
    block_rows = min(block_rows, used)
    if used % block_rows:
        raise ValueError(f"{used} rows do not divide into blocks of {block_rows}")

    def block_loss(p, batch, y, step_key, off):
        logits = forward(p, batch, cfg, step_key=step_key, row_offset=off,
                         rows_total=n, precision=precision)
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        return -jnp.take_along_axis(logp, y[:, None], axis=1).sum()

    grad_fn = jax.jit(jax.value_and_grad(block_loss))
    b1, b2, eps, wd = opt["b1"], opt["b2"], opt["eps"], opt["weight_decay"]

    @jax.jit
    def update(p, m, v, g, lr, t):
        m = jax.tree.map(lambda a, b: b1 * a + (1 - b1) * b, m, g)
        v = jax.tree.map(lambda a, b: b2 * a + (1 - b2) * b * b, v, g)
        c1, c2 = 1 - b1 ** t, 1 - b2 ** t
        p = jax.tree.map(
            lambda w, a, b: w - lr * ((a / c1) / (jnp.sqrt(b / c2) + eps) + wd * w),
            p, m, v)
        return p, m, v

    sq = jax.jit(lambda t: jax.tree.map(lambda a: jnp.sum(a * a), t))
    base_key = jax.random.PRNGKey(random_seed)
    p = params
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    losses, first_grad = [], None
    for t, (batch, y) in enumerate(zip(batches, labels)):
        step_key = jax.random.fold_in(base_key, t)
        loss, g = 0.0, None
        for off in range(0, used, block_rows):
            blk = {k: jnp.asarray(a[off:off + block_rows]) for k, a in batch.items()}
            l, gb = grad_fn(p, blk, jnp.asarray(y[off:off + block_rows], jnp.int32),
                            step_key, off)
            loss += float(l)
            g = gb if g is None else jax.tree.map(jnp.add, g, gb)
        g = jax.tree.map(lambda a: a / used, g)
        losses.append(loss / used)
        if t == 0:
            first_grad = {k: np.asarray(a) for k, a in jax.device_get(g).items()}
        if fault != "frozen":
            p, m, v = update(p, m, v, g, lr_at(t, opt, total_steps), float(t + 1))
        del g
    delta_sq = jax.device_get(sq(jax.tree.map(jnp.subtract, p, params)))
    return {"loss": losses, "first_grad": first_grad,
            "delta_sq": {k: float(x) for k, x in delta_sq.items()}}
