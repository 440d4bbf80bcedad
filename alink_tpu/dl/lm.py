"""A causal language model served as a text generator.

The block is the decoder block of today's open models: pre-norm residual
stream, RMS norm, grouped query heads with a per-head RMS norm on queries and
keys, rotary positions, a SiLU-gated feed-forward and an untied head. What
mixes positions is named per layer in the configuration's ``layer_types``:

- ``retention`` (:mod:`alink_tpu.dl.retention`): a fixed-size state a layer.
  Its gate is ``logsigmoid(a W_g + b_g)``, one scalar a key/value head and
  position (``g_proj``, the only linear of the block with a bias).
- ``kda`` (:mod:`alink_tpu.dl.kda`): the delta rule with a per-channel gate
  behind short convolutions; a fixed-size state and the convolutions' tails.
- ``mla`` (:mod:`alink_tpu.dl.mla`): softmax attention over a compressed
  cache that grows with the sequence, one latent a position.

What follows the mixer is named per layer in ``ffn_types``: the ``dense``
SiLU-gated feed-forward, or ``experts`` (:mod:`alink_tpu.dl.moe`), the routed
experts this process holds and the shared expert. ``from_hf`` derives both
patterns from a checkpoint's ``model_type``.

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

The cache manager (:class:`StateCache`) holds, slot for slot, what each layer
keeps of a sequence: a recurrent state of fixed size, a latent cache of
``positions`` positions with its length, an expert layer's count of the
assignments it served. It is allocated once for ``slots`` sequences, handed
to each program as donated buffers and taken back updated; a batch's first
chunk starts every state, length and count from zero, so a slot holds nothing
of the sequence that used it last.
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

MIXERS = ("retention", "kda", "mla")
FFNS = ("dense", "experts")
# prompt positions one call of the prefill program takes: the chunk's scores
# are (rows, heads, chunk, chunk) and the FFN's intermediate (rows, chunk,
# intermediate), and both fit beside sixteen rows' state at this size
PREFILL_CHUNK = 256
# of a stack with kda, mla or expert layers: one chunk of the delta rule's WY
# form (four sub-blocks of its gate's bound), and small enough that 128 rows'
# projections, sorted expert inputs and scores fit beside 128 rows' state
HYBRID_PREFILL_CHUNK = 64
_STEP_BUCKETS = (0.0005, 0.001, 0.002, 0.004, 0.006, 0.008, 0.010, 0.012,
                 0.014, 0.016, 0.018, 0.020, 0.022, 0.024, 0.026, 0.028, 0.030,
                 0.035, 0.040, 0.050, 0.065, 0.080, 0.1, 0.15, 0.25, 0.5, 1.0,
                 2.5, 10.0)
_ROW_BUCKETS = (1.0, 2.0, 4.0, 8.0, 16.0, 32.0, 64.0, 128.0, 256.0)
_POSITION_BUCKETS = tuple(float(2 ** i) for i in range(4, 21))
LOAD_BUCKETS = (1.0, 1.25, 1.5, 2.0, 2.5, 3.0, 4.0, 6.0, 8.0, 16.0, 64.0, 1024.0)


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
    # what follows each layer's mixer; empty: dense everywhere
    ffn_types: Tuple[str, ...] = ()
    # kda
    conv_kernel: int = 4
    kda_lower_bound: float = -5.0
    # mla
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # experts: the router's outputs, and the experts [lo, hi) held here
    num_experts: int = 0
    experts_held: Tuple[int, int] = (0, 0)
    num_experts_per_tok: int = 0
    n_group: int = 1
    topk_group: int = 1
    routed_scaling_factor: float = 1.0
    moe_intermediate_size: int = 0
    shared_intermediate_size: int = 0
    # what differs between the families that share the mla and expert layers:
    # a gate a head on the mla layer's output (bailing_hybrid), and the name
    # the checkpoint gives the router's bias
    mla_head_gate: bool = True
    router_bias_name: str = "expert_bias"
    # training an expert layer: the weight of the sequence-wise balance term
    # and the step of the router's bias (DeepSeek-V3 section 4.2), unless the
    # checkpoint's config.json says otherwise
    balance_alpha: float = 1e-4
    bias_update_rate: float = 1e-3

    @classmethod
    def from_hf(cls, hf: Dict[str, Any], **over) -> "CausalLMConfig":
        """From an HF ``config.json``, by its ``model_type``: ``brumby``
        (retention in every layer unless ``layer_types`` says otherwise: the
        family publishes the dense block's keys and no key of its mixer),
        ``bailing_hybrid`` (:func:`_bailing_hybrid_fields`) or
        ``deepseek_v3`` (:func:`_deepseek_v3_fields`)."""
        if hf.get("hidden_act", "silu") != "silu":
            raise NotImplementedError(f"hidden_act {hf['hidden_act']!r}")
        n = int(hf["num_hidden_layers"])
        heads = int(hf["num_attention_heads"])
        fields = dict(
            vocab_size=int(hf["vocab_size"]), hidden_size=int(hf["hidden_size"]),
            intermediate_size=int(hf["intermediate_size"]), num_hidden_layers=n,
            num_attention_heads=heads,
            num_key_value_heads=int(hf.get("num_key_value_heads", heads)),
            head_dim=int(hf.get("head_dim") or int(hf["hidden_size"]) // heads),
            rms_norm_eps=float(hf.get("rms_norm_eps", 1e-6)),
            rope_theta=float(hf.get("rope_theta", 1e4)))
        model_type = hf.get("model_type")
        if model_type == "brumby":
            kind = tuple(hf.get("layer_types") or ("retention",) * n)
            if set(kind) != {"retention"} or len(kind) != n:
                raise NotImplementedError(
                    f"layer_types {kind}: a brumby block has retention alone")
            fields.update(retention_eps=float(hf.get("retention_eps", 1e-6)),
                          layer_types=kind)
        elif model_type == "bailing_hybrid":
            fields.update(_bailing_hybrid_fields(hf, n))
        elif model_type == "deepseek_v3":
            fields.update(_deepseek_v3_fields(hf, n))
        else:
            raise NotImplementedError(
                f"model_type {model_type!r}: the block is written for "
                f"'brumby', 'bailing_hybrid' and 'deepseek_v3'")
        fields.update(over)
        return cls(**fields)

    def __post_init__(self):
        n = self.num_hidden_layers
        bad = (set(self.layer_types) - set(MIXERS)) | (set(self.ffn_types)
                                                       - set(FFNS))
        if bad or len(self.layer_types) != n or len(self.ffn_types) not in (0, n):
            raise NotImplementedError(
                f"layer_types {self.layer_types} and ffn_types "
                f"{self.ffn_types} for {n} layers: the block has {MIXERS} "
                f"and {FFNS}")

    def ffn_type(self, i: int) -> str:
        return self.ffn_types[i] if self.ffn_types else "dense"

    @property
    def prefill_chunk(self) -> int:
        plain = set(self.layer_types) <= {"retention"} and not self.ffn_types
        return PREFILL_CHUNK if plain else HYBRID_PREFILL_CHUNK

    @property
    def state_shape(self) -> Tuple[int, int, int]:
        """One sequence's retention state in one layer: ``(Hkv, P, D)``."""
        return (self.num_key_value_heads, phi_dim(self.head_dim), self.head_dim)

    @property
    def latent_width(self) -> int:
        """What an mla layer caches of a position: the latent and the
        shared rotary key."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    def layer_state(self, i: int, slots: int, positions: int
                    ) -> Tuple[Tuple[Tuple[int, ...], str], ...]:
        """Shape and dtype of what layer ``i`` keeps for ``slots``
        sequences, the mixer's entries first."""
        heads, d = self.num_attention_heads, self.head_dim
        kind = self.layer_types[i]
        if kind == "retention":
            hkv, p, _ = self.state_shape
            out = [((slots, hkv, p, d), "float32"), ((slots, hkv, p), "float32")]
        elif kind == "kda":
            out = [((slots, heads, d, d), "float32"),
                   ((slots, self.conv_kernel - 1, 3 * heads * d), "float32")]
        else:
            out = [((slots, positions, self.latent_width), self.dtype),
                   ((slots,), "int32")]
        if self.ffn_type(i) == "experts":
            lo, hi = self.experts_held
            out.append(((slots, hi - lo), "int32"))
        return tuple(out)

    def state_bytes_per_slot(self, kinds=("retention", "kda")) -> int:
        """The recurrent states of one sequence in all layers of ``kinds``:
        what does not grow with its length."""
        total = 0
        for i, kind in enumerate(self.layer_types):
            if kind in kinds:
                total += sum(4 * int(np.prod(shape[1:]))
                             for shape, _ in self.layer_state(i, 1, 0)[:2])
        return total

    def latent_bytes_per_position(self) -> int:
        return (self.layer_types.count("mla") * self.latent_width
                * np.dtype(self.dtype).itemsize)


def _bailing_hybrid_fields(hf: Dict[str, Any], n: int) -> Dict[str, Any]:
    """The ``bailing_hybrid`` keys: layer ``i`` is mla where ``(i + 1) %
    layer_group_size == 0`` and kda elsewhere; the first
    ``first_k_dense_replace`` layers have the dense feed-forward, the rest
    experts. ``experts_held`` (``[lo, hi)``, this process's share of a
    checkpoint; all of them where the key is absent) is this repo's key. A
    setting the block does not compute is refused, not ignored."""
    want = dict(q_lora_rank=None, scoring_func="sigmoid", topk_method="noaux_tc",
                norm_topk_prob=True, use_qk_norm=True, rope_interleave=True,
                kda_safe_gate=True, no_kda_lora=True, linear_silu=True,
                num_shared_experts=1, group_norm_size=1,
                moe_router_enable_expert_bias=True,
                gated_attention_proj_granularity_type="head_wise")
    bad = {k: hf[k] for k, v in want.items() if k in hf and hf[k] != v}
    if bad:
        raise NotImplementedError(f"bailing_hybrid with {bad}: the block "
                                  f"computes {({k: want[k] for k in bad})}")
    for key in ("expert_swiglu_limit_list", "share_expert_swiglu_limit_list"):
        at = [i for i, v in enumerate((hf.get(key) or ())[:n]) if v]
        if at:      # one entry a published layer; the first n are the kept ones
            raise NotImplementedError(
                f"{key} is nonzero in layers {at}: the clamped SwiGLU is not "
                f"written down here")
    group = int(hf["layer_group_size"])
    dense = int(hf.get("first_k_dense_replace", 0))
    experts = int(hf["num_experts"])
    groups = int(hf.get("n_group", 1))
    held = _held(hf, experts, groups)
    return dict(
        layer_types=tuple("mla" if (i + 1) % group == 0 else "kda"
                          for i in range(n)),
        ffn_types=tuple("dense" if i < dense else "experts" for i in range(n)),
        conv_kernel=int(hf.get("short_conv_kernel_size", 4)),
        kda_lower_bound=float(hf.get("kda_lower_bound", -5)),
        kv_lora_rank=int(hf["kv_lora_rank"]),
        qk_nope_head_dim=int(hf["qk_nope_head_dim"]),
        qk_rope_head_dim=int(hf["qk_rope_head_dim"]),
        v_head_dim=int(hf["v_head_dim"]), num_experts=experts,
        experts_held=held, num_experts_per_tok=int(hf["num_experts_per_tok"]),
        n_group=groups, topk_group=int(hf.get("topk_group", 1)),
        routed_scaling_factor=float(hf.get("routed_scaling_factor", 1.0)),
        moe_intermediate_size=int(hf["moe_intermediate_size"]),
        shared_intermediate_size=int(hf.get(
            "moe_shared_expert_intermediate_size", hf["moe_intermediate_size"])))


def _held(hf: Dict[str, Any], experts: int, groups: int) -> Tuple[int, int]:
    """``experts_held`` (``[lo, hi)``, this process's share of a checkpoint;
    all of them where the key is absent): this repo's key."""
    held = tuple(int(v) for v in hf.get("experts_held") or (0, experts))
    if not (0 <= held[0] < held[1] <= experts) or experts % groups:
        raise ValueError(f"experts_held {held} of {experts} experts in "
                         f"{groups} groups")
    return held


def _deepseek_v3_fields(hf: Dict[str, Any], n: int) -> Dict[str, Any]:
    """The ``deepseek_v3`` keys (Moonlight-16B-A3B publishes under them):
    latent attention in every layer with no gate on its heads, the dense
    feed-forward in the first ``first_k_dense_replace`` layers and experts in
    the rest, ``n_shared_experts`` shared experts as one of their summed
    width, the router's bias ``e_score_correction_bias``. A setting the block
    does not compute is refused, not ignored."""
    want = dict(q_lora_rank=None, scoring_func="sigmoid", topk_method="noaux_tc",
                norm_topk_prob=True, rope_scaling=None, moe_layer_freq=1,
                num_nextn_predict_layers=0, attention_bias=False,
                tie_word_embeddings=False)
    bad = {k: hf[k] for k, v in want.items() if k in hf and hf[k] != v}
    if bad:
        raise NotImplementedError(f"deepseek_v3 with {bad}: the block "
                                  f"computes {({k: want[k] for k in bad})}")
    dense = int(hf.get("first_k_dense_replace", 0))
    experts = int(hf["n_routed_experts"])
    groups = int(hf.get("n_group", 1))
    dn, dr = int(hf["qk_nope_head_dim"]), int(hf["qk_rope_head_dim"])
    return dict(
        head_dim=dn + dr, layer_types=("mla",) * n,
        ffn_types=tuple("dense" if i < dense else "experts" for i in range(n)),
        kv_lora_rank=int(hf["kv_lora_rank"]), qk_nope_head_dim=dn,
        qk_rope_head_dim=dr, v_head_dim=int(hf["v_head_dim"]),
        num_experts=experts, experts_held=_held(hf, experts, groups),
        num_experts_per_tok=int(hf["num_experts_per_tok"]), n_group=groups,
        topk_group=int(hf.get("topk_group", 1)),
        routed_scaling_factor=float(hf.get("routed_scaling_factor", 1.0)),
        moe_intermediate_size=int(hf["moe_intermediate_size"]),
        shared_intermediate_size=int(hf.get("n_shared_experts", 1))
        * int(hf["moe_intermediate_size"]),
        mla_head_gate=False, router_bias_name="e_score_correction_bias",
        **{field: float(hf[key]) for field, key in (
            ("balance_alpha", "aux_loss_alpha"),
            ("bias_update_rate", "bias_update_rate")) if key in hf})


def _mixer_shapes(cfg: CausalLMConfig, kind: str) -> Dict[str, Tuple[int, ...]]:
    """A mixer's tensors by their name inside ``self_attn``; a name without
    ``.weight`` or ``.bias`` is a bare parameter."""
    h, d = cfg.hidden_size, cfg.head_dim
    hq, hkv = cfg.num_attention_heads, cfg.num_key_value_heads
    if kind == "retention":
        return {"q_proj.weight": (hq * d, h), "k_proj.weight": (hkv * d, h),
                "v_proj.weight": (hkv * d, h), "g_proj.weight": (hkv, h),
                "g_proj.bias": (hkv,), "q_norm.weight": (d,),
                "k_norm.weight": (d,), "o_proj.weight": (h, hq * d)}
    if kind == "kda":
        wide, conv = (hq * d, h), (hq * d, 1, cfg.conv_kernel)
        return {"q_proj.weight": wide, "k_proj.weight": wide,
                "v_proj.weight": wide, "q_conv1d.weight": conv,
                "k_conv1d.weight": conv, "v_conv1d.weight": conv,
                "f_proj.weight": wide, "dt_bias": (hq * d,), "A_log": (hq,),
                "b_proj.weight": (hq, h), "g_proj.weight": wide,
                "o_norm.weight": (d,), "o_proj.weight": (h, hq * d)}
    r, dn, dr, dv = (cfg.kv_lora_rank, cfg.qk_nope_head_dim,
                     cfg.qk_rope_head_dim, cfg.v_head_dim)
    out = {"q_proj.weight": (hq * (dn + dr), h),
           "kv_a_proj_with_mqa.weight": (r + dr, h),
           "kv_a_layernorm.weight": (r,),
           "kv_b_proj.weight": (hq * (dn + dv), r),
           "o_proj.weight": (h, hq * dv)}
    if cfg.mla_head_gate:
        out["g_proj.weight"] = (hq, h)
    return out


def _ffn_shapes(cfg: CausalLMConfig, kind: str) -> Dict[str, Tuple[int, ...]]:
    h = cfg.hidden_size

    def swiglu(prefix: str, f: int):
        return {f"{prefix}gate_proj.weight": (f, h),
                f"{prefix}up_proj.weight": (f, h),
                f"{prefix}down_proj.weight": (h, f)}

    if kind == "dense":
        return swiglu("", cfg.intermediate_size)
    out = {"gate.weight": (cfg.num_experts, h),
           "gate." + cfg.router_bias_name: (cfg.num_experts,)}
    for e in range(*cfg.experts_held):
        out.update(swiglu(f"experts.{e}.", cfg.moe_intermediate_size))
    out.update(swiglu("shared_experts.", cfg.shared_intermediate_size))
    return out


def tensor_shapes(cfg: CausalLMConfig) -> Dict[str, Tuple[int, ...]]:
    """HF tensor name to shape, for every tensor of the model."""
    h = cfg.hidden_size
    out = {"model.embed_tokens.weight": (cfg.vocab_size, h),
           "model.norm.weight": (h,), "lm_head.weight": (cfg.vocab_size, h)}
    for i, kind in enumerate(cfg.layer_types):
        p = f"model.layers.{i}."
        out[p + "input_layernorm.weight"] = (h,)
        out[p + "post_attention_layernorm.weight"] = (h,)
        for name, shape in _mixer_shapes(cfg, kind).items():
            out[p + "self_attn." + name] = shape
        for name, shape in _ffn_shapes(cfg, cfg.ffn_type(i)).items():
            out[p + "mlp." + name] = shape
    return out


def tree_path(hf_name: str) -> Tuple:
    """Where an HF tensor lives in the parameter tree: ``("embed_tokens",)``,
    ``("norm",)``, ``("lm_head",)`` or ``("layers", i, leaf)``. A linear's
    leaf is its name, its bias ``<linear>_bias``, a bare parameter
    (``A_log``) its own name, but the router's bias, ``expert_bias`` whatever
    the family calls it; the shared expert's leaves are
    ``shared_<linear>``, and expert ``e``'s lie under ``("layers", i,
    "experts", e, leaf)`` until :func:`_stack_experts` joins them."""
    parts = hf_name.split(".")
    if parts[0] == "lm_head":
        return ("lm_head",)
    if parts[1] != "layers":
        return (parts[1],)
    if parts[-1] in ("weight", "bias"):
        leaf = parts[-2] + ("_bias" if parts[-1] == "bias" else "")
        inside = parts[3:-2]
    else:
        leaf, inside = parts[-1], parts[3:-1]
        if leaf == "e_score_correction_bias":
            leaf = "expert_bias"
    if inside[-1:] == ["shared_experts"]:
        leaf = "shared_" + leaf
    if inside[-2:-1] == ["experts"]:
        return ("layers", int(parts[2]), "experts", int(inside[-1]), leaf)
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
            node = node[key] if isinstance(node, list) else node.setdefault(key, {})
        node[path[-1]] = arr
        seen.add(name)
    missing = sorted(set(want) - seen)
    if missing:
        raise ValueError(f"checkpoint lacks {len(missing)} tensors, first "
                         f"{missing[:3]}")
    if "experts" in cfg.ffn_types:
        _stack_experts(cfg, tree)
    return tree


def _build_stack_experts():
    import jax
    import jax.numpy as jnp

    def run(gate, up, down):
        """Lists of one layer's expert matrices, ``(out, in)`` each, to the
        grouped products' operands: ``(E, H, 2F)`` and ``(E, F, H)``."""
        return (jnp.stack([jnp.concatenate([g.T, u.T], axis=1)
                           for g, u in zip(gate, up)]),
                jnp.stack([d.T for d in down]))

    return jax.jit(run)


def _stack_experts(cfg: CausalLMConfig, tree) -> None:
    """Each expert layer's ``experts`` (a dict by expert of three matrices)
    replaced by ``experts_gate_up`` and ``experts_down``, a layer at a time
    and each waited for, so that one layer's experts alone exist twice
    (dispatched one after another without waiting, every layer's result is
    allocated before the first layer's inputs are let go: 16.5 GB of live
    buffers on a 16.9 GB chip in a checkout's first run, PERF.md PR 32)."""
    import jax

    from ..common.jitcache import cached_jit

    lo, hi = cfg.experts_held
    stack = cached_jit("lm.stack_experts", _build_stack_experts,
                       key_extra=(hi - lo, cfg.moe_intermediate_size,
                                  cfg.hidden_size))
    for layer in tree["layers"]:
        by_expert = layer.pop("experts", None)
        if by_expert is None:
            continue
        held = [by_expert[e] for e in range(lo, hi)]
        del by_expert
        layer["experts_gate_up"], layer["experts_down"] = stack(
            *([e[name] for e in held]
              for name in ("gate_proj", "up_proj", "down_proj")))
        del held
        jax.block_until_ready(layer["experts_down"])


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


def _swiglu(n, gate, up, down):
    import jax

    n = n.astype(gate.dtype)
    return _linear(jax.nn.silu(_linear(n, gate)) * _linear(n, up), down)


def _ffn(cfg: CausalLMConfig, layer, x):
    n = _rms_norm(x, layer["post_attention_layernorm"], cfg.rms_norm_eps)
    return x + _swiglu(n, layer["gate_proj"], layer["up_proj"],
                       layer["down_proj"])


def _route(cfg: CausalLMConfig, layer, flat, bias):
    """The router over ``flat (N,H)`` float32: its logits ``(N,E)``, the
    experts each token chose ``(N,K)`` and their weights."""
    import jax
    import jax.numpy as jnp

    from . import moe

    with jax.named_scope(moe.ROUTE_SCOPE):
        logits = jnp.einsum("nh,eh->ne", flat, layer["gate"].astype(jnp.float32),
                            precision=jax.lax.Precision.HIGHEST)
        idx, w = moe.route(logits, bias, n_group=cfg.n_group,
                           topk_group=cfg.topk_group,
                           top_k=cfg.num_experts_per_tok,
                           scale=cfg.routed_scaling_factor)
    return logits, idx, w


def _experts_ffn(cfg: CausalLMConfig, layer, x, valid, load):
    """The expert layer over ``x (B,T,H)``: the routed experts held here,
    the shared expert, and each row's count a held expert added to
    ``load (B,E)``."""
    import jax
    import jax.numpy as jnp

    from . import moe

    B, T, H = x.shape
    n = _rms_norm(x, layer["post_attention_layernorm"], cfg.rms_norm_eps)
    flat = n.reshape(B * T, H)
    _, idx, w = _route(cfg, layer, flat, layer["expert_bias"])
    with jax.named_scope(moe.ROUTE_SCOPE):
        local, added = moe.held_load(idx.reshape(B, T, -1), valid,
                                     cfg.experts_held)
    y = moe.routed_experts(flat, local.reshape(B * T, -1), w, added.sum(axis=0),
                           layer["experts_gate_up"], layer["experts_down"],
                           dtype=jnp.dtype(cfg.dtype))
    shared = _swiglu(n, layer["shared_gate_proj"], layer["shared_up_proj"],
                     layer["shared_down_proj"])
    return x + y.reshape(B, T, H) + shared, load + added


def _experts_ffn_whole(cfg: CausalLMConfig, layer, x, bias, piece: int):
    """The expert layer over whole sequences ``x (B,T,H)`` with the router's
    bias handed in (training keeps it beside the parameters): the layer's
    output, each row's balance term ``(B,)`` and how many assignments each of
    the router's outputs got ``(E,)``, held here or not."""
    import jax
    import jax.numpy as jnp

    from . import moe

    B, T, H = x.shape
    n = _rms_norm(x, layer["post_attention_layernorm"], cfg.rms_norm_eps)
    flat = n.reshape(B * T, H)
    logits, idx, w = _route(cfg, layer, flat, bias)
    with jax.named_scope(moe.ROUTE_SCOPE):
        idx = idx.reshape(B, T, -1)
        by_row = moe.load_counts(idx, cfg.num_experts)               # (B,E)
        balance = moe.seq_balance(logits.reshape(B, T, -1), by_row,
                                  top_k=cfg.num_experts_per_tok)
        local, held = moe.held_load(idx, jnp.ones((B, T), bool),
                                    cfg.experts_held)
    y = moe.routed_experts(flat, local.reshape(B * T, -1), w, held.sum(axis=0),
                           layer["experts_gate_up"], layer["experts_down"],
                           dtype=jnp.dtype(cfg.dtype), piece=piece)
    shared = _swiglu(n, layer["shared_gate_proj"], layer["shared_up_proj"],
                     layer["shared_down_proj"])
    return x + y.reshape(B, T, H) + shared, balance, by_row.sum(axis=0)


def _kda_mixer(cfg: CausalLMConfig, layer, a, valid, S, tail, *, chunk: bool):
    """``a (B,T,H)`` of a chunk or ``(B,H)`` of a step through one kda
    layer's mixer; ``tail`` is the three convolutions' inputs, q, k and v
    side by side."""
    import jax
    import jax.numpy as jnp

    from . import kda

    heads, d = cfg.num_attention_heads, cfg.head_dim
    lead = a.shape[:-1]
    a = a.astype(layer["q_proj"].dtype)
    qkv = jnp.concatenate([_linear(a, layer[p]) for p in
                           ("q_proj", "k_proj", "v_proj")], axis=-1)
    w = jnp.concatenate([layer[p][:, 0] for p in
                         ("q_conv1d", "k_conv1d", "v_conv1d")], axis=0)
    conv = kda.short_conv if chunk else kda.short_conv_step
    qkv, tail = conv(qkv, w, tail, valid)
    q, k, v = (x.reshape(*lead, heads, d) for x in
               jnp.split(jax.nn.silu(qkv), 3, axis=-1))
    unit = lambda x: x * jax.lax.rsqrt((x * x).sum(-1, keepdims=True) + 1e-6)
    q, k = unit(q) * d ** -0.5, unit(k)
    f32 = jnp.float32
    rate = jnp.exp(layer["A_log"].astype(f32))[:, None]
    g = cfg.kda_lower_bound * jax.nn.sigmoid(rate * (
        _linear(a, layer["f_proj"]).reshape(*lead, heads, d)
        + layer["dt_bias"].astype(f32).reshape(heads, d)))
    beta = jax.nn.sigmoid(_linear(a, layer["b_proj"]))
    if chunk:
        o, S = kda.kda_chunk(q, k, v, g, beta, valid, S,
                             lower_bound=cfg.kda_lower_bound,
                             dtype=jnp.dtype(cfg.dtype))
    else:
        o, S = kda.kda_step(q, k, v, g, beta, valid, S)
    o = _rms_norm(o, layer["o_norm"], cfg.rms_norm_eps) * jax.nn.sigmoid(
        _linear(a, layer["g_proj"]).reshape(*lead, heads, d))
    return _linear(o.reshape(*lead, heads * d), layer["o_proj"]), S, tail


def _mla_inputs(cfg: CausalLMConfig, layer, a, pos):
    """What an mla layer makes of ``a (B,T,H)``: the queries' two parts
    (the second rotated), and each position's cache entry, the normed latent
    and the rotated shared key side by side."""
    import jax.numpy as jnp

    from . import mla

    B, T, _ = a.shape
    r, dn, dr = cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    q = _linear(a, layer["q_proj"]).reshape(B, T, cfg.num_attention_heads,
                                            dn + dr)
    q_r = mla.rope_interleaved(q[..., dn:], pos, cfg.rope_theta)
    kv = _linear(a, layer["kv_a_proj_with_mqa"])
    new = jnp.concatenate(
        [_rms_norm(kv[..., :r], layer["kv_a_layernorm"], cfg.rms_norm_eps),
         mla.rope_interleaved(kv[..., r:], pos, cfg.rope_theta)], axis=-1)
    return q[..., :dn], q_r, new


def _mla_output(cfg: CausalLMConfig, layer, a, o):
    """The heads' outputs ``o (B,T,heads,Dv)``, gated a head where the
    family has the gate, through ``o_proj``."""
    import jax

    if cfg.mla_head_gate:
        o = o * jax.nn.sigmoid(_linear(a, layer["g_proj"]))[..., None]
    return _linear(o.reshape(*o.shape[:2], -1), layer["o_proj"])


def _mla_mixer(cfg: CausalLMConfig, layer, a, pos, valid, latent, length):
    """``a (B,T,H)`` through one mla layer's mixer: the positions' latents
    written to the cache, then attention over it in the absorbed form."""
    import jax.numpy as jnp

    from . import mla

    heads = cfg.num_attention_heads
    r, dn, dr, dv = (cfg.kv_lora_rank, cfg.qk_nope_head_dim,
                     cfg.qk_rope_head_dim, cfg.v_head_dim)
    a = a.astype(layer["q_proj"].dtype)
    q_n, q_r, new = _mla_inputs(cfg, layer, a, pos)
    start = length
    latent, length = mla.cache_write(latent, length, new, valid)
    w_kvb = layer["kv_b_proj"].reshape(heads, dn + dv, r)
    o = mla.attend(q_n, q_r, latent, start, w_kvb[:, :dn], w_kvb[:, dn:],
                   scale=(dn + dr) ** -0.5, dtype=jnp.dtype(cfg.dtype))
    return _mla_output(cfg, layer, a, o), latent, length


def _mla_mixer_whole(cfg: CausalLMConfig, layer, a, pos):
    """``a (B,T,H)``, whole sequences with no cache (training), through one
    mla layer's mixer in the expanded form: keys ``[k_n, k_r]`` and values a
    head from the latent, then the causal core in blocks."""
    import jax.numpy as jnp

    from . import mla

    B, T, _ = a.shape
    heads = cfg.num_attention_heads
    r, dn, dr, dv = (cfg.kv_lora_rank, cfg.qk_nope_head_dim,
                     cfg.qk_rope_head_dim, cfg.v_head_dim)
    a = a.astype(layer["q_proj"].dtype)
    q_n, q_r, new = _mla_inputs(cfg, layer, a, pos)
    kv = _linear(new[..., :r], layer["kv_b_proj"]).reshape(B, T, heads, dn + dv)
    k_r = jnp.broadcast_to(new[..., None, r:], (B, T, heads, dr))
    o = mla.causal_core(jnp.concatenate([q_n, q_r], axis=-1),
                        jnp.concatenate([kv[..., :dn], k_r], axis=-1),
                        kv[..., dn:], scale=(dn + dr) ** -0.5,
                        dtype=jnp.dtype(cfg.dtype))
    return _mla_output(cfg, layer, a, o)


def _block(cfg: CausalLMConfig, i: int, layer, x, pos, valid, state, *,
           chunk: bool):
    """Layer ``i`` over a chunk ``x (B,T,H)`` or a step ``x (B,H)``, with
    what the layer keeps of each row (:meth:`CausalLMConfig.layer_state`);
    the residual stream is float32."""
    import jax.numpy as jnp

    kind, ffn = cfg.layer_types[i], cfg.ffn_type(i)
    # the mla mixer and the expert layer take a step as a chunk of one position
    one = (lambda t: t) if chunk else (lambda t: t[:, None])
    back = (lambda t: t) if chunk else (lambda t: t[:, 0])
    a = _rms_norm(x, layer["input_layernorm"], cfg.rms_norm_eps)
    if kind == "retention":
        S, z = state[:2]
        q, k, v, log_g = _mixer_inputs(cfg, layer, a, pos)
        if chunk:
            o, S, z = retention_chunk(q, k, v, log_g, valid, S, z,
                                      eps=cfg.retention_eps,
                                      dtype=jnp.dtype(cfg.dtype))
        else:
            o, S, z = retention_step(q, k, v, log_g, None, S, z,
                                     eps=cfg.retention_eps)
        x = x + _linear(o.reshape(*x.shape[:-1], -1), layer["o_proj"])
        kept = (S, z)
    elif kind == "kda":
        y, S, tail = _kda_mixer(cfg, layer, a, valid, *state[:2], chunk=chunk)
        x, kept = x + y, (S, tail)
    else:
        y, latent, length = _mla_mixer(cfg, layer, one(a), one(pos), one(valid),
                                       *state[:2])
        x, kept = x + back(y), (latent, length)
    if ffn == "dense":
        return _ffn(cfg, layer, x), kept
    x, load = _experts_ffn(cfg, layer, one(x), one(valid), state[2])
    return back(x), kept + (load,)


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
    """The first ``rows`` slots of what every layer keeps."""
    return [tuple(a[:rows] for a in kept) for kept in state]


def _rows_back(state, new, rows: int):
    """``new`` written over the first ``rows`` slots (the whole buffer where
    the rung fills it, so that no slice is cut)."""
    return [tuple(b if a.shape[0] == rows else a.at[:rows].set(b)
                  for a, b in zip(kept, kept_new))
            for kept, kept_new in zip(state, new)]


def _fresh(cfg: CausalLMConfig, i: int, kept, first):
    """What layer ``i`` keeps, emptied where ``first`` says a batch begins:
    states, lengths and counts to zero. A latent cache is not rewritten:
    its length says how much of it is the sequence's."""
    import jax.numpy as jnp

    skip = 1 if cfg.layer_types[i] == "mla" else 0
    return kept[:skip] + tuple(jnp.where(first, jnp.zeros((), a.dtype), a)
                               for a in kept[skip:])


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
        for i, (layer, kept) in enumerate(zip(params["layers"],
                                              _rows_of(state, rows))):
            x, kept = _block(cfg, i, layer, x, jnp.maximum(pos, 0), valid,
                             _fresh(cfg, i, kept, first), chunk=True)
            new.append(kept)
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
        """tok, pos ``(rows,)``; pos < 0: a row beyond the batch's, which
        writes no state it could read and asks no expert."""
        valid = pos >= 0
        x = jnp.take(params["embed_tokens"], tok, axis=0).astype(jnp.float32)
        new = []
        for i, (layer, kept) in enumerate(zip(params["layers"],
                                              _rows_of(state, rows))):
            x, kept = _block(cfg, i, layer, x, jnp.maximum(pos, 0), valid, kept,
                             chunk=False)
            new.append(kept)
        tok, logprob = _greedy(cfg, params, x)
        return _rows_back(state, new, rows), tok, logprob

    return jax.jit(run, donate_argnums=(1,))


class StateCache:
    """What ``slots`` sequences keep in every layer, allocated once: per
    layer a tuple of arrays with the slots leading, as
    :meth:`CausalLMConfig.layer_state` lays them out (retention ``(S, z)``;
    kda ``(S, convolution tails)``; mla ``(latent cache of `positions`
    positions, its length)``; then an expert layer's count a held expert).
    A program takes the buffers donated (:meth:`take`) and what it returns
    is kept (:meth:`put`), so there is one copy."""

    def __init__(self, cfg: CausalLMConfig, slots: int, positions: int = 0):
        import jax.numpy as jnp

        self.slots, self.positions = int(slots), int(positions)
        self._state = [tuple(jnp.zeros(shape, dtype) for shape, dtype in
                             cfg.layer_state(i, self.slots, self.positions))
                       for i in range(cfg.num_hidden_layers)]
        metrics.set_gauge("lm.state_slots", self.slots)
        metrics.set_gauge("lm.state_bytes",
                          self.slots * cfg.state_bytes_per_slot())
        if set(cfg.layer_types) - {"retention"}:
            metrics.set_gauge("lm.kda_state_bytes",
                              self.slots * cfg.state_bytes_per_slot(("kda",)))
            metrics.set_gauge("lm.latent_cache_positions", self.positions)
            metrics.set_gauge("lm.latent_cache_bytes", self.slots * self.positions
                              * cfg.latent_bytes_per_position())
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

    def peek(self):
        """The buffers as they lie, for a read between programs."""
        if self._state is None:
            raise RuntimeError("the state cache is out with a running program")
        return self._state


class CausalLM:
    """The model placed on the device with its cache manager: greedy
    generation for batches of token-id prompts. ``positions``: what a slot's
    latent cache holds, prompt and new tokens together (a stack without mla
    layers keeps none)."""

    def __init__(self, cfg: CausalLMConfig, params, *, slots: int,
                 positions: int = 0, prefill_chunk: int = 0):
        from ..common.jitcache import bucket_rows

        self.cfg, self.params = cfg, params
        self.prefill_chunk = int(prefill_chunk) or cfg.prefill_chunk
        # of the last call of generate(), where the stack has expert layers:
        # (rows, expert layers, experts held), each row's assignments served
        self.expert_load = None
        self._grows = "mla" in cfg.layer_types
        if self._grows and positions < 1:
            raise ValueError("a stack with mla layers needs `positions`, the "
                             "length of a slot's latent cache")
        self.cache = StateCache(cfg, bucket_rows(slots),
                                positions if self._grows else 0)

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
        loads = []
        for s in range(0, n, self.cache.slots):
            part = prompts[s:s + self.cache.slots]
            ids[s:s + len(part)], logprobs[s:s + len(part)], load = \
                self._generate_batch(part, max_new_tokens)
            loads.append(load)
        self.expert_load = None if loads[0] is None else np.concatenate(loads)
        return ids, logprobs

    def _generate_batch(self, prompts, max_new: int):
        from ..common.jitcache import bucket_rows

        n = len(prompts)
        if any(len(p) == 0 for p in prompts):
            raise ValueError("an empty prompt has no position to continue")
        rows = min(bucket_rows(n), self.cache.slots)
        lens = np.asarray([len(p) for p in prompts]
                          + [len(prompts[-1])] * (rows - n), np.int32)
        if self._grows and int(lens.max()) + max_new - 1 > self.cache.positions:
            raise ValueError(
                f"a prompt of {int(lens.max())} tokens and {max_new} new ones "
                f"pass the latent cache's {self.cache.positions} positions")
        self.cache.use(n)
        load = None
        try:
            tok, logprob = self._prefill(prompts, lens, rows)
            out = self._decode(tok, logprob, lens, n, rows, max_new)
            if "experts" in self.cfg.ffn_types:
                load = self._count_assignments(
                    n, int(lens[:n].sum()) + n * (max_new - 1))
        finally:
            self.cache.use(0)
        return out[0][:n], out[1][:n], load

    def _count_assignments(self, n: int, tokens: int) -> np.ndarray:
        """One read a batch, after its last step: per row and expert layer
        the assignments each held expert served, ``(rows, expert layers,
        experts held)``, returned; from them the counters: the assignments
        the batch's tokens made, those held here, and each expert layer's
        largest load over its mean."""
        import jax.numpy as jnp

        by_row = np.asarray(jnp.stack(
            [kept[-1][:n] for kept, ffn in zip(self.cache.peek(),
                                               self.cfg.ffn_types)
             if ffn == "experts"], axis=1))
        loads = by_row.sum(axis=0)
        metrics.incr("moe.assignments",
                     tokens * self.cfg.num_experts_per_tok * len(loads))
        metrics.incr("moe.assignments_held", int(loads.sum()))
        for load in loads:
            if load.sum():
                metrics.observe("moe.expert_load_max_over_mean",
                                float(load.max() / load.mean()),
                                buckets=LOAD_BUCKETS)
        return by_row

    def _prefill(self, prompts, lens: np.ndarray, rows: int):
        import jax.numpy as jnp

        T = self.prefill_chunk
        n, total = len(prompts), int(lens[:len(prompts)].sum())
        chunks = -(-int(lens.max()) // T)
        with trace_span("lm.prefill", rows=n, tokens=total,
                        chunks=chunks) as sp:
            tokens = np.zeros((rows, chunks * T), np.int32)
            for r in range(rows):       # rows beyond n repeat the last prompt
                p = prompts[min(r, n - 1)]
                tokens[r, :len(p)] = p
            pos = np.arange(chunks * T, dtype=np.int32)[None, :]
            live = (np.arange(rows) < n)[:, None]    # rows beyond n: padding
            pos = np.where((pos < lens[:, None]) & live, pos, -1).astype(np.int32)
            prog = self._program("lm.prefill_chunk", _build_prefill_chunk, rows)
            hidden = jnp.zeros((rows, self.cfg.hidden_size), jnp.float32)
            # a chunk's hand-over: the host's pace until the runtime's queue
            # is full, the device's after; the last one waits for the token
            slowest, t_last = (0, 0.0), time.perf_counter()
            for c in range(chunks):
                sl = slice(c * T, (c + 1) * T)
                last = lens - 1 - c * T
                last = np.where((last >= 0) & (last < T), last, -1).astype(np.int32)
                state, hidden = prog(self.params, self.cache.take(),
                                     tokens[:, sl], pos[:, sl], c == 0, last,
                                     hidden)
                self.cache.put(state)
                now = time.perf_counter()
                if c < chunks - 1:
                    if now - t_last > slowest[1]:
                        slowest = (c, now - t_last)
                    t_last = now
            tok, logprob = self._program("lm.sample", _build_sample, rows)(
                self.params, hidden)
            tok.block_until_ready()
            if time.perf_counter() - t_last > slowest[1]:
                slowest = (chunks - 1, time.perf_counter() - t_last)
            if sp is not None:
                sp.attrs["slowest_step"] = slowest[0]
                sp.attrs["slowest_step_s"] = round(slowest[1], 6)
        metrics.incr("lm.prefill_tokens", total)
        return tok, logprob

    def _decode(self, tok, logprob, lens: np.ndarray, n: int, rows: int,
                max_new: int):
        """``max_new - 1`` steps through the cache after the prefill's token.
        The host waits for step ``i - 1`` while step ``i`` runs, so the time
        between two steps' ends is a step's and the device never waits."""
        toks, logprobs = [tok], [logprob]
        live = np.arange(rows) < n
        with trace_span("lm.decode", rows=n, steps=max_new - 1) as sp:
            prog = self._program("lm.decode_step", _build_decode_step, rows)
            slowest, t_last = (0, 0.0), time.perf_counter()
            for i in range(1, max_new):
                with step_annotation("lm.decode_step", i):
                    state, tok, logprob = prog(
                        self.params, self.cache.take(), tok,
                        np.where(live, lens + i - 1, -1).astype(np.int32))
                    self.cache.put(state)
                    toks.append(tok)
                    logprobs.append(logprob)
                    toks[i - 1] = np.asarray(toks[i - 1])
                now = time.perf_counter()
                metrics.observe("lm.decode_step_s", now - t_last,
                                buckets=_STEP_BUCKETS)
                metrics.observe("lm.step_slots_in_use", float(n),
                                buckets=_ROW_BUCKETS)
                if self._grows:
                    metrics.observe("lm.step_latent_positions",
                                    float(lens[:n].mean()) + i,
                                    buckets=_POSITION_BUCKETS)
                if now - t_last > slowest[1]:
                    slowest = (i, now - t_last)
                t_last = now
            if sp is not None:
                sp.attrs["slowest_step"] = slowest[0]
                sp.attrs["slowest_step_s"] = round(slowest[1], 6)
            ids = np.stack([np.asarray(t) for t in toks], axis=1)
            lps = np.stack([np.asarray(l) for l in logprobs], axis=1)
        metrics.incr("lm.decode_tokens", n * max_new)
        return ids, lps


def load_causal_lm(path: str, *, slots: int, positions: int = 0
                   ) -> Tuple[CausalLM, List[str]]:
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
    return (CausalLM(cfg, params, slots=slots, positions=positions),
            load_vocab_file(os.path.join(path, "vocab.txt")))


# -- training ------------------------------------------------------------------

# sorted rows of one expert that the training step takes through its matrices
# at a time: 16,384 tokens give a held expert 1,536 under an even router, two
# pieces of which a quarter is padding; a piece of 2,048 would be one
TRAIN_EXPERT_PIECE = 1024
# positions whose logits are live at once in the training loss (4,096 x 20,480
# float32 are 335 MB, and as much again for their gradient), and whose
# intermediates are live at once in the dense feed-forward (4,096 x 11,264
# float32 are 185 MB, several times over in its backward pass)
TRAIN_LOSS_PIECE = 4096


def _compute_dtype(cfg: CausalLMConfig, layer):
    """A layer's matrices in the dtype its products run in; the norms'
    scales and the router stay as they are kept (float32)."""
    import jax.numpy as jnp

    dtype = jnp.dtype(cfg.dtype)
    return {k: v.astype(dtype) if v.ndim >= 2 and k != "gate" else v
            for k, v in layer.items()}


def _pieces(n: int, piece: int) -> int:
    """The largest count of positions at or under ``piece`` that divides
    ``n``."""
    import math

    piece = min(piece, n)
    return piece if n % piece == 0 else math.gcd(n, piece)


def _ffn_by_piece(cfg: CausalLMConfig, layer, x, piece: int):
    """:func:`_ffn` over ``x (B,T,H)``, ``piece`` positions at a time, each
    piece's intermediates computed again in the backward pass."""
    import jax

    B, T, H = x.shape
    piece = _pieces(B * T, piece)
    out = jax.lax.map(jax.checkpoint(lambda part: _ffn(cfg, layer, part)),
                      x.reshape(B * T // piece, piece, H))
    return out.reshape(B, T, H)


def _layer_whole(cfg: CausalLMConfig, i: int, layer, bias, x, pos):
    """Layer ``i`` over whole sequences ``x (B,T,H)`` float32, no cache."""
    layer = _compute_dtype(cfg, layer)
    a = _rms_norm(x, layer["input_layernorm"], cfg.rms_norm_eps)
    x = x + _mla_mixer_whole(cfg, layer, a, pos)
    if cfg.ffn_type(i) == "dense":
        return _ffn_by_piece(cfg, layer, x, TRAIN_LOSS_PIECE), None, None
    return _experts_ffn_whole(cfg, layer, x, bias, TRAIN_EXPERT_PIECE)


def _token_losses(cfg: CausalLMConfig, params, x, targets, piece: int):
    """The cross-entropy of every position's logits against its target:
    ``x (N,H)`` float32, targets ``(N,)``; ``piece`` positions' logits at a
    time, each piece's computed again in the backward pass."""
    import jax
    import jax.numpy as jnp

    N, H = x.shape
    piece = _pieces(N, piece)
    head = params["lm_head"].astype(jnp.dtype(cfg.dtype))

    @jax.checkpoint
    def part(norm, head, h, tgt):
        with jax.named_scope("lm_head"):
            logits = _linear(_rms_norm(h, norm, cfg.rms_norm_eps), head)
        with jax.named_scope("loss"):
            at = jnp.take_along_axis(logits, tgt[:, None], axis=-1)[:, 0]
            return jax.nn.logsumexp(logits, axis=-1) - at

    out = jax.lax.map(lambda xs: part(params["norm"], head, *xs),
                      (x.reshape(N // piece, piece, H),
                       targets.reshape(N // piece, piece)))
    return out.reshape(N)


def train_rows(cfg: CausalLMConfig, params, bias, tokens):
    """Whole rows of tokens ``(B,T)`` through the stack, a layer
    rematerialised at a time. ``bias`` ``(expert layers, E)``: the router's
    bias of each. Returns each row's loss ``(B,)``: the mean over its
    positions but the last of the cross-entropy against the next token, plus
    ``cfg.balance_alpha`` times the mean over the expert layers of the row's
    balance term; and the assignments each router output got, ``(expert
    layers, E)`` int32."""
    import jax
    import jax.numpy as jnp
    from functools import partial

    if set(cfg.layer_types) != {"mla"}:
        raise NotImplementedError(
            f"layer_types {sorted(set(cfg.layer_types))}: the training step "
            f"is written for mla layers (retention and kda have no backward "
            f"pass)")
    B, T = tokens.shape
    pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    x = jnp.take(params["embed_tokens"], tokens, axis=0).astype(jnp.float32)
    balances, counts, e = [], [], 0
    for i, layer in enumerate(params["layers"]):
        dense = cfg.ffn_type(i) == "dense"
        x, balance, count = jax.checkpoint(
            partial(_layer_whole, cfg, i))(
                layer, None if dense else bias[e], x, pos)
        if not dense:
            balances.append(balance)
            counts.append(count)
            e += 1
    targets = jnp.concatenate([tokens[:, 1:], tokens[:, :1]], axis=1)
    ce = _token_losses(cfg, params, x.reshape(B * T, -1),
                       targets.reshape(-1), TRAIN_LOSS_PIECE).reshape(B, T)
    rows = ce[:, :-1].sum(axis=1) / (T - 1)
    if balances:
        rows = rows + cfg.balance_alpha * jnp.stack(balances).mean(axis=0)
    return rows, (jnp.stack(counts) if counts
                  else jnp.zeros((0, max(cfg.num_experts, 1)), jnp.int32))


@dataclasses.dataclass(frozen=True)
class CausalLMTrainer:
    """The decoder stack as :func:`alink_tpu.dl.train.train_model` takes a
    model: ``apply`` over the variables ``{"params": the parameter tree
    without the routers' biases, "router": {"expert_bias": (expert layers, E)
    float32, "load": (expert layers, E) int32}}`` and a batch ``tokens
    (B,T)`` gives each row's loss (the loop's loss ``"rows"``). The
    ``router`` collection is state a rule updates and no gradient: asked for
    as ``mutable``, it comes back with each bias moved by
    :func:`alink_tpu.dl.moe.bias_step` from this step's assignments, and with
    those assignments added to ``load``, which the op reads once an epoch."""

    cfg: CausalLMConfig

    def apply(self, variables, tokens, deterministic=True, rngs=None,
              mutable=()):
        import jax

        from . import moe

        router = variables["router"]
        rows, counts = train_rows(self.cfg, variables["params"],
                                  router["expert_bias"], tokens)
        if not mutable:
            return rows
        with jax.named_scope(moe.ROUTE_SCOPE):
            bias = jax.vmap(lambda b, c: moe.bias_step(
                b, c, self.cfg.bias_update_rate))(
                router["expert_bias"], counts) if counts.shape[0] \
                else router["expert_bias"]
        return rows, {"router": {"expert_bias": bias,
                                 "load": router["load"] + counts}}


def training_variables(cfg: CausalLMConfig, path: str) -> Dict[str, Any]:
    """The variables :class:`CausalLMTrainer` takes, from an HF-layout
    checkpoint directory: float32 master weights on the host (the stacked
    experts on the device, where they are joined), the routers' biases taken
    out of the parameter tree into the ``router`` collection."""
    from .pretrained import iter_safetensors

    params = params_from_tensors(cfg, (
        (name, np.asarray(arr, np.float32))
        for name, arr in iter_safetensors(path)))
    biases = [layer.pop("expert_bias") for layer in params["layers"]
              if "expert_bias" in layer]
    e = max(cfg.num_experts, 1)
    bias = np.stack(biases) if biases else np.zeros((0, e), np.float32)
    return {"params": params,
            "router": {"expert_bias": bias.astype(np.float32),
                       "load": np.zeros(bias.shape, np.int32)}}


def hf_tensors(cfg: CausalLMConfig, variables):
    """A trained state's tensors under their HF names, a layer at a time
    (``(shard, [(name, array)])``: the embedding, each layer, the final norm
    and head), the stacked experts taken apart again and each router's bias
    back under the family's name."""
    params, bias = variables["params"], variables["router"]["expert_bias"]
    yield [("model.embed_tokens.weight", params["embed_tokens"])]
    lo, hi = cfg.experts_held
    f, e = cfg.moe_intermediate_size, 0
    for i, layer in enumerate(params["layers"]):
        p = f"model.layers.{i}."
        out = []
        names = {**_mixer_shapes(cfg, cfg.layer_types[i]),
                 **{"mlp." + k: v for k, v in
                    _ffn_shapes(cfg, cfg.ffn_type(i)).items()}}
        for name in names:
            full = p + (name if name.startswith("mlp.") else "self_attn." + name)
            path = tree_path(full)
            if len(path) == 5:                   # ("layers", i, "experts", e, leaf)
                at, leaf = path[3] - lo, path[4]
                if leaf == "down_proj":
                    arr = np.asarray(layer["experts_down"][at]).T
                else:
                    gu = np.asarray(layer["experts_gate_up"][at])
                    arr = (gu[:, :f] if leaf == "gate_proj" else gu[:, f:]).T
            elif path[-1] == "expert_bias":
                arr = bias[e]
            else:
                arr = layer[path[-1]]
            out.append((full, np.asarray(arr)))
        for norm in ("input_layernorm", "post_attention_layernorm"):
            out.append((p + norm + ".weight", np.asarray(layer[norm])))
        e += cfg.ffn_type(i) == "experts"
        yield out
    yield [("model.norm.weight", params["norm"]),
           ("lm_head.weight", params["lm_head"])]
