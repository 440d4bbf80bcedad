"""Operations and bytes of a ``deepseek_v3`` training step (latent attention
in every layer, an expert layer of which this chip holds a share), from the
configuration's shapes and the program's counters, and the per-layer metrics
built on them. It imports nothing of the program.

The counts are what the algorithm needs and nothing a rematerialised layer
computes twice: a matrix product is multiply-adds times two, the backward pass
twice the forward; the attention core is the causal half (position ``t`` sees
``t + 1`` keys); the held experts count the assignments they got. Norms,
rotary positions, the router and the embedding look-up are left out (under
1%).
"""

from __future__ import annotations

import re
from typing import Any, Dict, List, Optional


def _layers(cfg: Dict[str, Any]) -> Dict[str, int]:
    dense = min(cfg["first_k_dense_replace"], cfg["num_hidden_layers"])
    return {"all": cfg["num_hidden_layers"], "dense": dense,
            "experts": cfg["num_hidden_layers"] - dense}


def _routed(cfg: Dict[str, Any]) -> int:
    """The router's outputs: the published count where the file states a share."""
    return (cfg.get("published") or {}).get("n_routed_experts",
                                            cfg["n_routed_experts"])


def mixer_weights(cfg: Dict[str, Any]) -> int:
    h, hq, r = cfg["hidden_size"], cfg["num_attention_heads"], cfg["kv_lora_rank"]
    dn, dr, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    return hq * (dn + dr) * h + (r + dr) * h + hq * (dn + dv) * r + h * hq * dv


def expert_weights(cfg: Dict[str, Any]) -> int:
    """One routed expert's three matrices."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def dense_weights(cfg: Dict[str, Any]) -> int:
    """Every matrix a token passes whatever the router says: the mixers, the
    dense feed-forward, the shared experts, the head over the slice."""
    n, h = _layers(cfg), cfg["hidden_size"]
    return (n["all"] * mixer_weights(cfg)
            + n["dense"] * 3 * h * cfg["intermediate_size"]
            + n["experts"] * cfg["n_shared_experts"] * expert_weights(cfg)
            + h * cfg["vocab_size"])


def parameters(cfg: Dict[str, Any]) -> int:
    """Parameters on this chip: what the optimizer passes every step."""
    lo, hi = cfg["deployment"]["experts_held"]
    n, h = _layers(cfg), cfg["hidden_size"]
    return (dense_weights(cfg) + h * cfg["vocab_size"]
            + n["experts"] * ((hi - lo) * expert_weights(cfg)
                              + _routed(cfg) * h))


def core_forward_flops(cfg: Dict[str, Any], seq: int, rows: float) -> float:
    """The attention core of ``rows`` rows of ``seq`` positions, all layers,
    forward: scores over ``qk_nope + qk_rope`` and the weighted sum over
    ``v_head_dim``, a head and seen position, the causal half."""
    width = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"] + cfg["v_head_dim"]
    seen = seq * (seq + 1) / 2.0
    return rows * _layers(cfg)["all"] * cfg["num_attention_heads"] * 2.0 * width * seen


def _held_per_token(facts) -> Optional[float]:
    """Assignments a token and expert layer that went to an expert held
    here, from the window's counters."""
    c = (facts.get("counters_window") or {}).get("counters") or {}
    if not c.get("moe.assignments"):
        return None
    return facts["config"]["num_experts_per_tok"] \
        * c.get("moe.assignments_held", 0) / c["moe.assignments"]


def step_flops(cfg: Dict[str, Any], seq: int, rows: float, held_per_token: float
               ) -> Dict[str, float]:
    """Required operations of training ``rows`` rows, forward and backward,
    by part."""
    tokens = rows * seq
    return {"dense": 3 * tokens * 2.0 * dense_weights(cfg),
            "experts": 3 * tokens * _layers(cfg)["experts"] * held_per_token
            * 2.0 * expert_weights(cfg),
            "core": 3 * core_forward_flops(cfg, seq, rows)}


def mfu_pct(facts) -> Optional[float]:
    """The whole step's share of the chips' bf16 peak: required operations of
    the window's rows a second of the window."""
    held = _held_per_token(facts) if facts.get("peaks") and facts.get("rows") \
        else None
    if held is None or facts.get("mode") != "train":
        return None
    ops = sum(step_flops(facts["config"], facts["seq_len"], facts["rows"],
                         held).values())
    return 100.0 * ops / facts["window_s"] / (
        facts["chips"] * facts["peaks"]["bf16_flops_per_s"])


def _sizes(facts) -> Dict[str, int]:
    cfg = facts["config"]
    b = int(facts["batch"]) // int(facts["chips"])
    k = cfg["num_experts_per_tok"]
    return {"{B}": b, "{T}": int(facts["seq_len"]),
            "{N}": b * int(facts["seq_len"]), "{NK}": b * int(facts["seq_len"]) * k,
            "{K}": k, "{H}": cfg["num_attention_heads"], "{R}": _routed(cfg),
            "{DQ}": cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"],
            "{DV}": cfg["v_head_dim"], "{HID}": cfg["hidden_size"],
            "{F}": cfg["moe_intermediate_size"],
            "{F2}": 2 * cfg["moe_intermediate_size"]}


def _matched_seconds(facts, patterns: List[str], sizes: Dict[str, int]) -> float:
    tr = facts["trace"]
    rx = []
    for p in patterns:
        for key, value in sizes.items():
            p = p.replace(key, str(value))
        rx.append(re.compile(p))
    # a loop is a container: the trace holds its body's operations too
    matched = [(n, s) for n, s, _, reads in tr["ops_all"]
               if not n.startswith(("while", "conditional", "call"))
               and any(r.search(n) or r.search(reads) for r in rx)]
    tr.setdefault("matched", {})[facts.get("metric", "moonlight")] = matched[:16]
    return sum(s for _, s in matched)


def _traced_rows(facts) -> float:
    return facts["rows_per_s"] * facts["trace"]["window_s"] / facts["chips"]


def _least(facts, flops: float, nbytes: float) -> float:
    peaks = facts["peaks"]
    return max(flops / peaks["bf16_flops_per_s"], nbytes / peaks["hbm_bytes_per_s"])


def mla_roofline_pct(facts, patterns: List[str], block: int) -> Optional[float]:
    """Least time of the attention core, forward and backward, of the rows
    the traced window trained (operations over peak, or q, k, v, o and their
    gradients once in bfloat16 over bandwidth, whichever is larger) over the
    device time of the operations ``patterns`` match."""
    if not facts.get("trace") or not facts.get("peaks") \
            or facts.get("mode") != "train":
        return None
    seconds = _matched_seconds(facts, patterns, dict(_sizes(facts),
                                                     **{"{BLK}": block}))
    if seconds <= 0:
        return None
    cfg, rows = facts["config"], _traced_rows(facts)
    width = 2 * (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) \
        + 2 * cfg["v_head_dim"]
    nbytes = rows * facts["seq_len"] * _layers(cfg)["all"] \
        * cfg["num_attention_heads"] * width * 2.0 * 3
    return 100.0 * _least(facts, 3 * core_forward_flops(cfg, facts["seq_len"],
                                                        rows), nbytes) / seconds


def moe_roofline_pct(facts, patterns: List[str], piece: int) -> Optional[float]:
    """Least time of the routed experts held here, forward and backward, for
    the assignments the traced window's tokens gave them (operations over
    peak, or the held experts' weights read once forward and once backward
    and their gradient written once a step, whichever is larger) over the
    device time of the experts' products and of the operations that route,
    sort, gather, weigh and scatter their rows, ``piece`` sorted rows of one
    expert at a time."""
    held = _held_per_token(facts) if facts.get("trace") and facts.get("peaks") \
        else None
    if held is None or facts.get("mode") != "train":
        return None
    seconds = _matched_seconds(facts, patterns, dict(_sizes(facts),
                                                     **{"{P}": piece}))
    if seconds <= 0:
        return None
    cfg, rows = facts["config"], _traced_rows(facts)
    lo, hi = cfg["deployment"]["experts_held"]
    steps = rows / (facts["batch"] / facts["chips"])
    flops = step_flops(cfg, facts["seq_len"], rows, held)["experts"]
    nbytes = steps * _layers(cfg)["experts"] * (hi - lo) * expert_weights(cfg) \
        * (2.0 + 2.0 + 4.0)
    return 100.0 * _least(facts, flops, nbytes) / seconds


def optimizer_hbm_share_pct(facts) -> Optional[float]:
    """What AdamW has to move a step (float32 weight, gradient and two
    moments read, weight and moments written: 28 bytes a parameter) times
    steps a second of the window, over the chip's memory bandwidth."""
    if not facts.get("peaks") or not facts.get("steps") \
            or facts.get("mode") != "train":
        return None
    return 100.0 * 28.0 * parameters(facts["config"]) * facts["steps"] \
        / facts["window_s"] / facts["peaks"]["hbm_bytes_per_s"]


def tokens_per_expert(facts) -> Optional[float]:
    """Assignments a step and expert layer that a held expert got, the mean
    over the experts held: what fills the grouped products' rows."""
    c = (facts.get("counters_window") or {}).get("counters") or {}
    if not c.get("moe.assignments_held") or not facts.get("steps"):
        return None
    cfg = facts["config"]
    lo, hi = cfg["deployment"]["experts_held"]
    return c["moe.assignments_held"] / facts["steps"] \
        / _layers(cfg)["experts"] / (hi - lo)
