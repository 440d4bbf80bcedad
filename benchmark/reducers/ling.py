"""Operations and bytes of a ``bailing_hybrid`` generator (KDA and latent
attention layers, an expert layer of which this chip holds a share), from the
configuration's shapes and the program's counters, and the per-layer metrics
built on them. It imports nothing of the program.

The counts are what the algorithm needs: weights in bfloat16 read once a
decode step, and of the experts only those an assignment reached; a
sequence's KDA state (float32) read and written once a step or prompt chunk;
the latent cache read once a step; products as multiply-adds times two. Norms,
rotary positions, gates, the router and the embedding look-up are left out
(under 1%).
"""

from __future__ import annotations

import re
from typing import Any, Dict, List, Optional

from benchmark.reducers.lm import _window


def kinds(cfg: Dict[str, Any]) -> List[tuple]:
    return [("mla" if (i + 1) % cfg["layer_group_size"] == 0 else "kda",
             "dense" if i < cfg["first_k_dense_replace"] else "experts")
            for i in range(cfg["num_hidden_layers"])]


def _count(cfg, what: str) -> int:
    return sum(what in k for k in kinds(cfg))


def mixer_weights(cfg: Dict[str, Any], kind: str) -> int:
    h, d, hq = cfg["hidden_size"], cfg["head_dim"], cfg["num_attention_heads"]
    if kind == "kda":
        return 6 * hq * d * h + hq * h
    r, dn, dr, dv = (cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
                     cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    return hq * (dn + dr) * h + (r + dr) * h + hq * (dn + dv) * r + hq * h \
        + h * hq * dv


def expert_weights(cfg: Dict[str, Any]) -> int:
    """One expert's three matrices."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def dense_weights(cfg: Dict[str, Any]) -> int:
    """Every matrix a token passes whatever the router says: mixers, the
    dense feed-forward, the shared experts."""
    h = cfg["hidden_size"]
    return (sum(mixer_weights(cfg, m) for m, _ in kinds(cfg))
            + _count(cfg, "dense") * 3 * h * cfg["intermediate_size"]
            + _count(cfg, "experts") * 3 * h
            * cfg["moe_shared_expert_intermediate_size"])


def kda_state_bytes_per_row(cfg: Dict[str, Any]) -> float:
    hq, d = cfg["num_attention_heads"], cfg["head_dim"]
    tails = 3 * (cfg["short_conv_kernel_size"] - 1) * hq * d
    return _count(cfg, "kda") * (hq * d * d + tails) * 4.0


def kda_step(cfg: Dict[str, Any], rows: float) -> Dict[str, float]:
    """One decode step's KDA core over ``rows`` sequences, all KDA layers:
    the state read and written once, q, k, v, g read and o written
    (float32); decay, the two reads along k and q, and the update."""
    hq, d = cfg["num_attention_heads"], cfg["head_dim"]
    qkvgo = _count(cfg, "kda") * 5 * hq * d * 4.0
    return {"bytes": rows * (2 * kda_state_bytes_per_row(cfg) + qkvgo),
            "flops": rows * _count(cfg, "kda") * hq * 7.0 * d * d}


def kda_prompt(cfg: Dict[str, Any], tokens: float, chunk: int) -> Dict[str, float]:
    """A prompt's KDA core in chunks of ``chunk`` positions: per position and
    head the lower halves of the two ``chunk x chunk`` products, the
    substitution and the product with the corrected values, and three
    products through the state; per chunk the state read and written once
    and q, k, v, g, o once."""
    hq, d = cfg["num_attention_heads"], cfg["head_dim"]
    layers = _count(cfg, "kda")
    per_pos = 2.0 * chunk * d + 2.0 * chunk * d + 6.0 * d * d
    return {"flops": tokens * layers * hq * per_pos,
            "bytes": tokens * layers * hq * (2.0 * d * d * 4 / chunk + 5 * d * 4)}


def mla_core(cfg: Dict[str, Any], queries: float, seen: float) -> Dict[str, float]:
    """The attention core in the absorbed form for ``queries`` positions that
    see ``seen`` cached positions in all: the query into the latent space and
    the mix out of it, a head; scores over ``kv_lora_rank + qk_rope_head_dim``
    and the weighted sum over ``kv_lora_rank``, a head and seen position."""
    hq, r = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    dn, dr, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    layers = _count(cfg, "mla")
    return {"flops": layers * hq * 2.0 * (queries * r * (dn + dv)
                                          + seen * (2 * r + dr)),
            "bytes": 0.0}


def latent_bytes_per_position(cfg: Dict[str, Any]) -> float:
    return _count(cfg, "mla") * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) * 2.0


def experts_touched(cfg: Dict[str, Any], assignments: float) -> float:
    """Experts of one layer that ``assignments`` held assignments reach, were
    they spread evenly: ``E (1 - (1 - 1/E)^a)``."""
    e = float(cfg["num_experts"])
    return e * (1.0 - (1.0 - 1.0 / e) ** assignments)


def _moe_window(facts) -> Optional[Dict[str, float]]:
    c = (facts.get("counters_window") or {}).get("counters") or {}
    w = _window(facts)
    if not w or not c.get("moe.assignments"):
        return None
    cfg = facts["config"]
    new = facts["new_tokens"]
    rows = w["decode_tokens"] / max(1, new)
    step_tokens = rows * (new - 1)
    tokens = w["prefill_tokens"] + step_tokens
    layers = _count(cfg, "experts")
    held_share = c.get("moe.assignments_held", 0) / c["moe.assignments"]
    per_token = cfg["num_experts_per_tok"] * held_share     # held, a layer
    return {"rows": rows, "steps": w["steps"], "tokens": tokens,
            "prefill_tokens": w["prefill_tokens"], "step_tokens": step_tokens,
            "layers": layers, "held_per_token": per_token,
            "held_per_step": per_token * step_tokens / max(1, w["steps"])}


def _patterns(facts, patterns: List[str], chunk: int) -> List["re.Pattern"]:
    cfg = facts["config"]
    k = cfg["num_experts_per_tok"]
    b = int(facts["batch"])
    routed = (cfg.get("published") or {}).get("num_experts", cfg["num_experts"])
    sizes = {"{B}": b, "{T}": chunk, "{H}": cfg["num_attention_heads"],
             "{D}": cfg["head_dim"], "{NKP}": b * chunk * k, "{NKD}": b * k,
             "{P}": int(facts.get("latent_positions") or 0),
             "{R}": routed, "{G}": cfg.get("n_group", 1),
             "{GE}": routed // cfg.get("n_group", 1),
             "{C3}": 3 * cfg["num_attention_heads"] * cfg["head_dim"]}
    out = []
    for p in patterns:
        for key, value in sizes.items():
            p = p.replace(key, str(value))
        out.append(re.compile(p))
    return out


def _matched_seconds(facts, patterns: List[str], chunk: int) -> float:
    tr = facts["trace"]
    rx = _patterns(facts, patterns, chunk)
    # a loop is a container: the trace holds its body's operations too
    matched = [(n, s) for n, s, _, reads in tr["ops_all"]
               if not n.startswith(("while", "conditional", "call"))
               and any(r.search(n) or r.search(reads) for r in rx)]
    tr.setdefault("matched", {})[facts.get("metric", "ling")] = matched[:12]
    return sum(s for _, s in matched)


def _least(facts, need: Dict[str, float]) -> float:
    peaks = facts["peaks"]
    return max(need["flops"] / peaks["bf16_flops_per_s"],
               need["bytes"] / peaks["hbm_bytes_per_s"])


def kda_roofline_pct(facts, patterns: List[str], chunk: int) -> Optional[float]:
    """Least time of the KDA core over the traced window (decode steps and
    prompt chunks, each by the larger of operations over peak and bytes over
    bandwidth) over the device time of the operations ``patterns`` match."""
    w = _window(facts)
    if not facts.get("trace") or not w or not facts.get("peaks"):
        return None
    seconds = _matched_seconds(facts, patterns, chunk)
    if seconds <= 0:
        return None
    cfg = facts["config"]
    steps = w["decode_tokens"] / max(1, facts["new_tokens"]) \
        * (facts["new_tokens"] - 1)
    return 100.0 * (_least(facts, kda_step(cfg, steps))
                    + _least(facts, kda_prompt(cfg, w["prefill_tokens"], chunk))
                    ) / seconds


def _mla_need(facts, w) -> Dict[str, float]:
    """The window's attention over the latent cache: a decode step reads
    each row's cache as far as it is filled (``lm.step_latent_positions``);
    a prompt token sees half its prompt on average."""
    cfg = facts["config"]
    h = ((facts.get("counters_window") or {}).get("hists") or {}).get(
        "lm.step_latent_positions")
    new = facts["new_tokens"]
    rows = w["decode_tokens"] / max(1, new)
    filled = h["sum"] / h["count"] if h and h["count"] > 0 else 0.0
    step = mla_core(cfg, rows * (new - 1), rows * (new - 1) * filled)
    step["bytes"] = rows * (new - 1) * filled * latent_bytes_per_position(cfg)
    mean_prompt = w["prefill_tokens"] / max(1.0, rows)
    prompt = mla_core(cfg, w["prefill_tokens"],
                      w["prefill_tokens"] * (mean_prompt + 1) / 2)
    return {"step": step, "prompt": prompt}


def mla_roofline_pct(facts, patterns: List[str], chunk: int) -> Optional[float]:
    w = _window(facts)
    if not facts.get("trace") or not w or not facts.get("peaks") \
            or not facts.get("latent_positions"):
        return None
    seconds = _matched_seconds(facts, patterns, chunk)
    if seconds <= 0:
        return None
    need = _mla_need(facts, w)
    return 100.0 * (_least(facts, need["step"]) + _least(facts, need["prompt"])
                    ) / seconds


def _moe_need(facts, m) -> Dict[str, Dict[str, float]]:
    cfg = facts["config"]
    one = expert_weights(cfg)
    touched = experts_touched(cfg, m["held_per_step"])
    return {"step": {"flops": 2.0 * one * m["held_per_token"] * m["step_tokens"]
                     * m["layers"],
                     "bytes": m["steps"] * m["layers"] * touched * one * 2.0},
            "prompt": {"flops": 2.0 * one * m["held_per_token"]
                       * m["prefill_tokens"] * m["layers"], "bytes": 0.0}}


def moe_roofline_pct(facts, patterns: List[str], chunk: int) -> Optional[float]:
    """Least time of the routed experts held here (decode steps by the bytes
    of the experts an assignment reached, prompt tokens by their operations)
    over the device time of the grouped products and of the operations that
    sort, gather and weigh their rows."""
    m = _moe_window(facts) if facts.get("trace") and facts.get("peaks") else None
    if not m:
        return None
    seconds = _matched_seconds(facts, patterns, chunk)
    if seconds <= 0:
        return None
    need = _moe_need(facts, m)
    return 100.0 * (_least(facts, need["step"]) + _least(facts, need["prompt"])
                    ) / seconds


def gen_mfu_pct(facts, chunk: int) -> Optional[float]:
    """The whole step's share of the chip's bf16 peak: required operations of
    the window (every token through the matrices all tokens pass, the experts
    held here for the assignments they got, the KDA and attention cores, the
    head at the sampled positions) a second of the window."""
    m = _moe_window(facts) if facts.get("peaks") and facts.get("rows") else None
    w = _window(facts)
    if not m or not w:
        return None
    cfg = facts["config"]
    mla = _mla_need(facts, w)
    ops = (m["tokens"] * 2.0 * dense_weights(cfg)
           + m["tokens"] * m["layers"] * m["held_per_token"] * 2.0
           * expert_weights(cfg)
           + kda_prompt(cfg, m["tokens"], chunk)["flops"]
           + mla["step"]["flops"] + mla["prompt"]["flops"]
           + m["rows"] * facts["new_tokens"] * 2.0 * cfg["hidden_size"]
           * cfg["vocab_size"])
    return 100.0 * ops / facts["window_s"] / (
        facts["chips"] * facts["peaks"]["bf16_flops_per_s"])


def decode_hbm_share_pct(facts) -> Optional[float]:
    """Required bytes of the window's decode steps (the matrices every token
    passes and the head once, the experts an assignment reached, each row's
    KDA state twice, its latent cache once, a step) a second of the window,
    over the chip's memory bandwidth."""
    m = _moe_window(facts) if facts.get("peaks") else None
    w = _window(facts)
    if not m or not w:
        return None
    cfg = facts["config"]
    rows_per_step = m["step_tokens"] / max(1, m["steps"])
    fixed = 2.0 * (dense_weights(cfg) + cfg["hidden_size"] * cfg["vocab_size"])
    per_step = fixed + 2.0 * rows_per_step * kda_state_bytes_per_row(cfg)
    need = (m["steps"] * per_step + _moe_need(facts, m)["step"]["bytes"]
            + _mla_need(facts, w)["step"]["bytes"])
    return 100.0 * need / facts["window_s"] / facts["peaks"]["hbm_bytes_per_s"]


def hist_mean(facts, phase: str, name: str) -> Optional[float]:
    h = ((facts.get(f"counters_{phase}") or {}).get("hists") or {}).get(name)
    if not h or h["count"] <= 0:
        return None
    return h["sum"] / h["count"]


def latent_fill_pct(facts) -> Optional[float]:
    """Mean positions of a row's latent cache in use at the window's decode
    steps over the positions a slot holds."""
    mean = hist_mean(facts, "window", "lm.step_latent_positions")
    if mean is None or not facts.get("latent_positions"):
        return None
    return 100.0 * mean / facts["latent_positions"]
