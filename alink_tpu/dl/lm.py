"""A causal language model served as a text generator.

The block is the decoder block of today's open models: pre-norm residual
stream, RMS norm, grouped query heads with a per-head RMS norm on queries and
keys, rotary positions, a SiLU-gated feed-forward and an untied head. What
mixes positions is named per layer in the configuration's ``layer_types``;
``retention`` (:mod:`alink_tpu.dl.retention`) is the kind there is, so the
sequence's memory is a fixed-size state per layer, not a cache that grows.
Its gate is ``logsigmoid(a W_g + b_g)``, one scalar a key/value head and
position (``g_proj``, the only linear of the block with a bias).

Parameters keep the checkpoint's layout (HF: a linear's weight is
``(out, in)``) and its bfloat16 on the device; nothing is transposed or
widened on the way in. Three programs per rung of the row ladder, all built
through the program cache:

- ``lm.prefill_chunk``: one fixed-size chunk of every row's prompt through
  all layers, the state carried in the cache between chunks, so prompts of
  any length run the same program;
- ``lm.sample``: final norm, head, greedy choice and its log-probability;
- ``lm.decode_step``: one new token of every row through all layers and
  the head.

The state cache (:class:`StateCache`) is allocated once for ``slots``
sequences, handed to each program as donated buffers and taken back updated;
a batch's first chunk starts from zeros, so a slot holds nothing of the
sequence that used it last.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, List, Sequence, Tuple

import numpy as np

from ..common.metrics import metrics
from ..common.tracing import step_annotation, trace_span
from .retention import (einsum_f32, phi_dim, retention_chunk,
                        retention_step)

MIXERS = ("retention",)
# prompt positions one call of the prefill program takes: the chunk's scores
# are (rows, heads, chunk, chunk) and the FFN's intermediate (rows, chunk,
# intermediate), and both fit beside sixteen rows' state at this size
PREFILL_CHUNK = 256
_STEP_BUCKETS = (0.0005, 0.001, 0.002, 0.004, 0.006, 0.008, 0.010, 0.012,
                 0.014, 0.016, 0.018, 0.020, 0.022, 0.024, 0.026, 0.028, 0.030,
                 0.035, 0.040, 0.050, 0.065, 0.080, 0.1, 0.15, 0.25, 0.5, 1.0,
                 2.5, 10.0)
_ROW_BUCKETS = (1.0, 2.0, 4.0, 8.0, 16.0, 32.0, 64.0, 128.0, 256.0)


@dataclasses.dataclass(frozen=True)
class CausalLMConfig:
    vocab_size: int
    hidden_size: int
    intermediate_size: int
    num_hidden_layers: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1e6
    retention_eps: float = 1e-6
    layer_types: Tuple[str, ...] = ()
    dtype: str = "bfloat16"

    @classmethod
    def from_hf(cls, hf: Dict[str, Any], **over) -> "CausalLMConfig":
        """From an HF ``config.json``; ``layer_types`` defaults to retention
        in every layer (the checkpoint family this block was written for
        publishes the dense block's keys and no key of its mixer)."""
        if hf.get("hidden_act", "silu") != "silu":
            raise NotImplementedError(f"hidden_act {hf['hidden_act']!r}")
        n = int(hf["num_hidden_layers"])
        heads = int(hf["num_attention_heads"])
        kind = tuple(hf.get("layer_types") or ("retention",) * n)
        bad = sorted(set(kind) - set(MIXERS))
        if bad or len(kind) != n:
            raise NotImplementedError(
                f"layer_types {bad or kind}: the block has {MIXERS}")
        fields = dict(
            vocab_size=int(hf["vocab_size"]), hidden_size=int(hf["hidden_size"]),
            intermediate_size=int(hf["intermediate_size"]), num_hidden_layers=n,
            num_attention_heads=heads,
            num_key_value_heads=int(hf.get("num_key_value_heads", heads)),
            head_dim=int(hf.get("head_dim", int(hf["hidden_size"]) // heads)),
            rms_norm_eps=float(hf.get("rms_norm_eps", 1e-6)),
            rope_theta=float(hf.get("rope_theta", 1e4)),
            retention_eps=float(hf.get("retention_eps", 1e-6)),
            layer_types=kind)
        fields.update(over)
        return cls(**fields)

    @property
    def state_shape(self) -> Tuple[int, int, int]:
        """One sequence's state in one layer: ``(Hkv, P, D)``."""
        return (self.num_key_value_heads, phi_dim(self.head_dim), self.head_dim)

    def state_bytes_per_slot(self) -> int:
        hkv, p, d = self.state_shape
        return self.num_hidden_layers * hkv * p * (d + 1) * 4


def tensor_shapes(cfg: CausalLMConfig) -> Dict[str, Tuple[int, ...]]:
    """HF tensor name to shape, for every tensor of the model."""
    h, f, d = cfg.hidden_size, cfg.intermediate_size, cfg.head_dim
    hq, hkv = cfg.num_attention_heads, cfg.num_key_value_heads
    out = {"model.embed_tokens.weight": (cfg.vocab_size, h),
           "model.norm.weight": (h,), "lm_head.weight": (cfg.vocab_size, h)}
    per_layer = {"input_layernorm": (h,), "self_attn.q_proj": (hq * d, h),
                 "self_attn.k_proj": (hkv * d, h), "self_attn.v_proj": (hkv * d, h),
                 "self_attn.g_proj": (hkv, h), "self_attn.q_norm": (d,),
                 "self_attn.k_norm": (d,), "self_attn.o_proj": (h, hq * d),
                 "post_attention_layernorm": (h,), "mlp.gate_proj": (f, h),
                 "mlp.up_proj": (f, h), "mlp.down_proj": (h, f)}
    for i in range(cfg.num_hidden_layers):
        for name, shape in per_layer.items():
            out[f"model.layers.{i}.{name}.weight"] = shape
        out[f"model.layers.{i}.self_attn.g_proj.bias"] = (hkv,)
    return out


def tree_path(hf_name: str) -> Tuple:
    """Where an HF tensor lives in the parameter tree: ``("embed_tokens",)``,
    ``("norm",)``, ``("lm_head",)`` or ``("layers", i, leaf)``; a bias is
    the leaf ``<linear>_bias``."""
    parts = hf_name.split(".")
    if parts[0] == "lm_head":
        return ("lm_head",)
    if parts[1] != "layers":
        return (parts[1],)
    leaf = parts[-2] + ("_bias" if parts[-1] == "bias" else "")
    return ("layers", int(parts[2]), leaf)


def params_from_tensors(cfg: CausalLMConfig, tensors) -> Dict[str, Any]:
    """The parameter tree from ``(hf_name, array)`` pairs, each checked
    against the configuration's shape; every tensor has to arrive once."""
    want = tensor_shapes(cfg)
    tree: Dict[str, Any] = {"layers": [dict() for _ in
                                       range(cfg.num_hidden_layers)]}
    seen = set()
    for name, arr in tensors:
        if name not in want:
            continue
        if tuple(arr.shape) != want[name]:
            raise ValueError(f"{name}: shape {tuple(arr.shape)}, the "
                             f"configuration says {want[name]}")
        path = tree_path(name)
        node = tree
        for key in path[:-1]:
            node = node[key]
        node[path[-1]] = arr
        seen.add(name)
    missing = sorted(set(want) - seen)
    if missing:
        raise ValueError(f"checkpoint lacks {len(missing)} tensors, first "
                         f"{missing[:3]}")
    return tree


# -- the block ---------------------------------------------------------------

def _rms_norm(x, w, eps):
    import jax
    import jax.numpy as jnp

    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return y * w.astype(jnp.float32)


def _rope(x, pos, theta):
    """Rotary positions, HF's half-split convention: ``x`` ``(..., H, D)``
    float32, ``pos`` broadcastable to its leading dimensions."""
    import jax.numpy as jnp

    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = pos.astype(jnp.float32)[..., None, None] * inv       # (..., 1, D/2)
    cos = jnp.concatenate([jnp.cos(ang)] * 2, axis=-1)
    sin = jnp.concatenate([jnp.sin(ang)] * 2, axis=-1)
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], axis=-1) * sin


def _linear(x, w):
    """``x @ w.T`` for a weight in the checkpoint's ``(out, in)`` layout."""
    return einsum_f32("...i,oi->...o", x.astype(w.dtype), w)


def _mixer_inputs(cfg: CausalLMConfig, layer, a, pos):
    import jax

    lead = a.shape[:-1]
    d = cfg.head_dim
    a = a.astype(layer["q_proj"].dtype)
    q = _linear(a, layer["q_proj"]).reshape(*lead, cfg.num_attention_heads, d)
    k = _linear(a, layer["k_proj"]).reshape(*lead, cfg.num_key_value_heads, d)
    v = _linear(a, layer["v_proj"]).reshape(*lead, cfg.num_key_value_heads, d)
    log_g = jax.nn.log_sigmoid(_linear(a, layer["g_proj"])
                               + layer["g_proj_bias"].astype("float32"))
    q = _rope(_rms_norm(q, layer["q_norm"], cfg.rms_norm_eps), pos, cfg.rope_theta)
    k = _rope(_rms_norm(k, layer["k_norm"], cfg.rms_norm_eps), pos, cfg.rope_theta)
    return q, k, v, log_g


def _ffn(cfg: CausalLMConfig, layer, x):
    import jax

    n = _rms_norm(x, layer["post_attention_layernorm"], cfg.rms_norm_eps)
    n = n.astype(layer["gate_proj"].dtype)
    hidden = jax.nn.silu(_linear(n, layer["gate_proj"])) * _linear(n, layer["up_proj"])
    return x + _linear(hidden, layer["down_proj"])


def _block(cfg: CausalLMConfig, layer, x, pos, valid, S, z, *, chunk: bool):
    """One layer over a chunk ``x (B,T,H)`` or a step ``x (B,H)``; the
    residual stream is float32."""
    import jax.numpy as jnp

    a = _rms_norm(x, layer["input_layernorm"], cfg.rms_norm_eps)
    q, k, v, log_g = _mixer_inputs(cfg, layer, a, pos)
    if chunk:
        o, S, z = retention_chunk(q, k, v, log_g, valid, S, z,
                                  eps=cfg.retention_eps,
                                  dtype=jnp.dtype(cfg.dtype))
    else:
        o, S, z = retention_step(q, k, v, log_g, valid, S, z,
                                 eps=cfg.retention_eps)
    x = x + _linear(o.reshape(*x.shape[:-1], -1), layer["o_proj"])
    return _ffn(cfg, layer, x), S, z


def _greedy(cfg: CausalLMConfig, params, x):
    """Final norm, the head, the largest logit's token and its
    log-probability, for ``x (B,H)``."""
    import jax
    import jax.numpy as jnp

    with jax.named_scope("lm_head"):
        n = _rms_norm(x, params["norm"], cfg.rms_norm_eps)
        logits = _linear(n, params["lm_head"])                  # (B,V) f32
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        top = jnp.take_along_axis(logits, tok[:, None], axis=-1)[:, 0]
        return tok, top - jax.nn.logsumexp(logits, axis=-1)


def _rows_of(state, rows: int):
    """The first ``rows`` slots of every layer's ``(S, z)``."""
    return [(S[:rows], z[:rows]) for S, z in state]


def _rows_back(state, new, rows: int):
    """``new`` written over the first ``rows`` slots (the whole buffer where
    the rung fills it, so that no slice is cut)."""
    return [(Sn, zn) if S.shape[0] == rows
            else (S.at[:rows].set(Sn), z.at[:rows].set(zn))
            for (S, z), (Sn, zn) in zip(state, new)]


def _build_prefill_chunk(cfg: CausalLMConfig, rows: int):
    import jax
    import jax.numpy as jnp

    def run(params, state, tokens, pos, first, last_idx, hidden):
        """tokens, pos ``(rows, T)`` (pos < 0: padding); first: the batch's
        first chunk; last_idx ``(rows,)``: where in this chunk a row's
        prompt ends, or -1; hidden ``(rows, H)``: the last position's
        residual of the rows whose prompt ended in an earlier chunk."""
        valid = pos >= 0
        x = jnp.take(params["embed_tokens"], tokens, axis=0).astype(jnp.float32)
        new = []
        for layer, (S, z) in zip(params["layers"], _rows_of(state, rows)):
            S, z = jnp.where(first, 0.0, S), jnp.where(first, 0.0, z)
            x, S, z = _block(cfg, layer, x, jnp.maximum(pos, 0), valid, S, z,
                             chunk=True)
            new.append((S, z))
        at = jnp.take_along_axis(
            x, jnp.maximum(last_idx, 0)[:, None, None], axis=1)[:, 0]
        hidden = jnp.where((last_idx >= 0)[:, None], at, hidden)
        return _rows_back(state, new, rows), hidden

    return jax.jit(run, donate_argnums=(1,))


def _build_sample(cfg: CausalLMConfig, rows: int):
    import jax

    return jax.jit(lambda params, hidden: _greedy(cfg, params, hidden))


def _build_decode_step(cfg: CausalLMConfig, rows: int):
    import jax
    import jax.numpy as jnp

    def run(params, state, tok, pos):
        x = jnp.take(params["embed_tokens"], tok, axis=0).astype(jnp.float32)
        new = []
        for layer, (S, z) in zip(params["layers"], _rows_of(state, rows)):
            x, S, z = _block(cfg, layer, x, pos, None, S, z, chunk=False)
            new.append((S, z))
        tok, logprob = _greedy(cfg, params, x)
        return _rows_back(state, new, rows), tok, logprob

    return jax.jit(run, donate_argnums=(1,))


class StateCache:
    """The retention state of ``slots`` sequences: per layer one ``S``
    ``(slots, Hkv, P, D)`` and one ``z`` ``(slots, Hkv, P)``, float32,
    allocated once. A program takes the buffers donated (:meth:`take`) and
    what it returns is kept (:meth:`put`), so there is one copy."""

    def __init__(self, cfg: CausalLMConfig, slots: int):
        import jax.numpy as jnp

        hkv, p, d = cfg.state_shape
        self.slots = int(slots)
        self._state = [(jnp.zeros((self.slots, hkv, p, d), jnp.float32),
                        jnp.zeros((self.slots, hkv, p), jnp.float32))
                       for _ in range(cfg.num_hidden_layers)]
        metrics.set_gauge("lm.state_slots", self.slots)
        metrics.set_gauge("lm.state_bytes",
                          self.slots * cfg.state_bytes_per_slot())
        self.use(0)

    def use(self, rows: int) -> None:
        metrics.set_gauge("lm.state_slots_in_use", rows)

    def take(self):
        state, self._state = self._state, None
        if state is None:
            raise RuntimeError("the state cache is out with a running program")
        return state

    def put(self, state) -> None:
        self._state = state


class CausalLM:
    """The model placed on the device with its state cache: greedy
    generation for batches of token-id prompts."""

    def __init__(self, cfg: CausalLMConfig, params, *, slots: int,
                 prefill_chunk: int = PREFILL_CHUNK):
        from ..common.jitcache import bucket_rows

        self.cfg, self.params = cfg, params
        self.prefill_chunk = int(prefill_chunk)
        self.cache = StateCache(cfg, bucket_rows(slots))

    def _program(self, kernel_id: str, builder, rows: int):
        from ..common.jitcache import cached_jit

        return cached_jit(kernel_id, lambda: builder(self.cfg, rows),
                          key_extra=(dataclasses.astuple(self.cfg), rows))

    def generate(self, prompts: Sequence[Sequence[int]], max_new_tokens: int
                 ) -> Tuple[np.ndarray, np.ndarray]:
        """Greedy continuation of every prompt by ``max_new_tokens`` tokens,
        with no stop token. Returns the ids and each id's log-probability,
        ``(n, max_new_tokens)``."""
        n = len(prompts)
        ids = np.zeros((n, max_new_tokens), np.int32)
        logprobs = np.zeros((n, max_new_tokens), np.float32)
        for s in range(0, n, self.cache.slots):
            part = prompts[s:s + self.cache.slots]
            ids[s:s + len(part)], logprobs[s:s + len(part)] = \
                self._generate_batch(part, max_new_tokens)
        return ids, logprobs

    def _generate_batch(self, prompts, max_new: int):
        from ..common.jitcache import bucket_rows

        n = len(prompts)
        if any(len(p) == 0 for p in prompts):
            raise ValueError("an empty prompt has no position to continue")
        rows = min(bucket_rows(n), self.cache.slots)
        lens = np.asarray([len(p) for p in prompts]
                          + [len(prompts[-1])] * (rows - n), np.int32)
        self.cache.use(n)
        try:
            tok, logprob = self._prefill(prompts, lens, rows)
            out = self._decode(tok, logprob, lens, n, rows, max_new)
        finally:
            self.cache.use(0)
        return out[0][:n], out[1][:n]

    def _prefill(self, prompts, lens: np.ndarray, rows: int):
        import jax.numpy as jnp

        T = self.prefill_chunk
        n, total = len(prompts), int(lens[:len(prompts)].sum())
        chunks = -(-int(lens.max()) // T)
        with trace_span("lm.prefill", rows=n, tokens=total, chunks=chunks):
            tokens = np.zeros((rows, chunks * T), np.int32)
            for r in range(rows):       # rows beyond n repeat the last prompt
                p = prompts[min(r, n - 1)]
                tokens[r, :len(p)] = p
            pos = np.arange(chunks * T, dtype=np.int32)[None, :]
            pos = np.where(pos < lens[:, None], pos, -1).astype(np.int32)
            prog = self._program("lm.prefill_chunk", _build_prefill_chunk, rows)
            hidden = jnp.zeros((rows, self.cfg.hidden_size), jnp.float32)
            for c in range(chunks):
                sl = slice(c * T, (c + 1) * T)
                last = lens - 1 - c * T
                last = np.where((last >= 0) & (last < T), last, -1).astype(np.int32)
                state, hidden = prog(self.params, self.cache.take(),
                                     tokens[:, sl], pos[:, sl], c == 0, last,
                                     hidden)
                self.cache.put(state)
            tok, logprob = self._program("lm.sample", _build_sample, rows)(
                self.params, hidden)
            tok.block_until_ready()
        metrics.incr("lm.prefill_tokens", total)
        return tok, logprob

    def _decode(self, tok, logprob, lens: np.ndarray, n: int, rows: int,
                max_new: int):
        """``max_new - 1`` steps through the cache after the prefill's token.
        The host waits for step ``i - 1`` while step ``i`` runs, so the time
        between two steps' ends is a step's and the device never waits."""
        toks, logprobs = [tok], [logprob]
        with trace_span("lm.decode", rows=n, steps=max_new - 1):
            prog = self._program("lm.decode_step", _build_decode_step, rows)
            t_last = time.perf_counter()
            for i in range(1, max_new):
                with step_annotation("lm.decode_step", i):
                    state, tok, logprob = prog(
                        self.params, self.cache.take(), tok,
                        (lens + i - 1).astype(np.int32))
                    self.cache.put(state)
                    toks.append(tok)
                    logprobs.append(logprob)
                    toks[i - 1] = np.asarray(toks[i - 1])
                now = time.perf_counter()
                metrics.observe("lm.decode_step_s", now - t_last,
                                buckets=_STEP_BUCKETS)
                metrics.observe("lm.step_slots_in_use", float(n),
                                buckets=_ROW_BUCKETS)
                t_last = now
            ids = np.stack([np.asarray(t) for t in toks], axis=1)
            lps = np.stack([np.asarray(l) for l in logprobs], axis=1)
        metrics.incr("lm.decode_tokens", n * max_new)
        return ids, lps


def load_causal_lm(path: str, *, slots: int) -> Tuple[CausalLM, List[str]]:
    """The model of an HF-layout checkpoint directory (``config.json``,
    sharded safetensors, ``vocab.txt``) on the first device in bfloat16, and
    its vocabulary. Each tensor goes from the memory-mapped file to the
    device on its own, in the checkpoint's bfloat16 or cast to it on the
    host, so the tree never exists whole on the host, in any precision."""
    import json
    import os

    import jax

    from ..common.quant import bf16_cast
    from .pretrained import iter_safetensors, load_vocab_file

    with open(os.path.join(path, "config.json")) as f:
        cfg = CausalLMConfig.from_hf(json.load(f))
    device = jax.devices()[0]
    params = params_from_tensors(cfg, (
        (name, jax.device_put(bf16_cast(arr), device))
        for name, arr in iter_safetensors(path)))
    jax.block_until_ready(params)
    return (CausalLM(cfg, params, slots=slots),
            load_vocab_file(os.path.join(path, "vocab.txt")))
