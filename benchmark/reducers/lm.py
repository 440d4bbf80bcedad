"""Operations and bytes of a causal language model with power-retention
layers, from the configuration's shapes alone (the same whatever implements
the layer), and the per-layer metrics built on them.

The counts are what the algorithm needs: weights in bfloat16 read once a
step, a sequence's retention state (float32) read and written once a step,
products as multiply-adds times two. Norms, rotary positions, the gate and the
embedding look-up are left out (under 1%).
"""

from __future__ import annotations

import re
from typing import Any, Dict, List, Optional


def phi_dim(cfg: Dict[str, Any]) -> int:
    d = cfg["head_dim"]
    return d * (d + 1) // 2


def layer_weights(cfg: Dict[str, Any]) -> int:
    """Matrix parameters of one block."""
    h, f, d = cfg["hidden_size"], cfg["intermediate_size"], cfg["head_dim"]
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return h * d * (2 * hq + 2 * hkv) + hkv * h + 3 * h * f


def state_bytes_per_row(cfg: Dict[str, Any]) -> float:
    """S and z of one sequence in all layers, float32."""
    return (cfg["num_hidden_layers"] * cfg["num_key_value_heads"] * phi_dim(cfg)
            * (cfg["head_dim"] + 1) * 4.0)


def retention_step(cfg: Dict[str, Any], rows: float) -> Dict[str, float]:
    """One decode step's retention core over ``rows`` sequences, all layers:
    the state read and written once, q, k, v read and o written (float32);
    the update ``phi(k) v^T`` and the reads ``phi(q)^T S``, ``phi(q)^T z``."""
    p, d = phi_dim(cfg), cfg["head_dim"]
    hq, hkv, layers = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                       cfg["num_hidden_layers"])
    qkvo = layers * (2 * hq + 2 * hkv) * d * 4.0
    return {"bytes": rows * (2 * state_bytes_per_row(cfg) + qkvo),
            "flops": rows * layers * 2.0 * p * (d + 1) * (hq + hkv)}


def retention_prompt(cfg: Dict[str, Any], tokens: float) -> Dict[str, float]:
    """The part of a prompt's retention core that goes through the state,
    for ``tokens`` positions in all layers: every query head's read of the
    state before its chunk and every key/value head's update of it. The
    quadratic part inside a chunk (under a twentieth of this at 256
    positions a chunk) is left out, here and among the matched operations."""
    p, d = phi_dim(cfg), cfg["head_dim"]
    hq, hkv, layers = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                       cfg["num_hidden_layers"])
    return {"flops": tokens * layers * 2.0 * p * (d + 1) * (hq + hkv),
            "bytes": 0.0}


def dense_flops_per_token(cfg: Dict[str, Any]) -> float:
    return 2.0 * cfg["num_hidden_layers"] * layer_weights(cfg)


def head_flops(cfg: Dict[str, Any]) -> float:
    return 2.0 * cfg["hidden_size"] * cfg["vocab_size"]


def decode_step_bytes(cfg: Dict[str, Any], rows: float) -> float:
    """Least bytes one decode step moves: every layer's matrices and the
    head once in bfloat16, each row's state read and written once."""
    weights = 2.0 * (cfg["num_hidden_layers"] * layer_weights(cfg)
                     + cfg["hidden_size"] * cfg["vocab_size"])
    return weights + 2.0 * rows * state_bytes_per_row(cfg)


def _window(facts) -> Optional[Dict[str, Any]]:
    """Tokens and steps of the window from the program's counters, or None
    where the program counts none (a program without the generator)."""
    got = facts.get("counters_window") or {}
    steps = (got.get("hists") or {}).get("lm.decode_step_s")
    if not steps or steps["count"] <= 0:
        return None
    c = got["counters"]
    return {"steps": steps["count"], "step_seconds": steps["sum"],
            "prefill_tokens": c.get("lm.prefill_tokens", 0),
            "decode_tokens": c.get("lm.decode_tokens", 0)}


def retention_roofline_pct(facts, patterns: List[str]) -> Optional[float]:
    """Least time the chip could take for the retention core of the traced
    window (each decode step and the prompts' tokens, each by the larger of
    its operations over peak and its bytes over bandwidth) over the device
    time of the operations ``patterns`` match by what they write or read.
    In a pattern ``{P}`` is the width of phi, ``{D}`` a head's, ``{R}`` the
    rolled copies of a vector that phi is built from (D/2 + 1) and ``{R1}``
    one more; ``{RD}``, ``{RD1}`` and ``{R1D}`` are R x D, R x (D + 1) and
    (R + 1) x D, the widths those copies have when laid out flat."""
    tr, w = facts.get("trace"), _window(facts)
    if not tr or not w or not facts.get("peaks"):
        return None
    cfg = facts["config"]
    d = cfg["head_dim"]
    r = d // 2 + 1
    sizes = {"{P}": phi_dim(cfg), "{D}": d, "{R}": r, "{R1}": r + 1,
             "{RD}": r * d, "{RD1}": r * (d + 1), "{R1D}": (r + 1) * d}
    rx = []
    for p in patterns:
        for key, value in sizes.items():
            p = p.replace(key, str(value))
        rx.append(re.compile(p))
    # a loop is a container: the trace holds its body's operations too
    matched = [(n, s) for n, s, _, reads in tr["ops_all"]
               if not n.startswith(("while", "conditional", "call"))
               and any(r.search(n) or r.search(reads) for r in rx)]
    seconds = sum(s for _, s in matched)
    tr.setdefault("matched", {})[facts.get("metric", "retention")] = matched[:12]
    if seconds <= 0:
        return None
    peaks = facts["peaks"]
    least = lambda need: max(need["flops"] / peaks["bf16_flops_per_s"],
                             need["bytes"] / peaks["hbm_bytes_per_s"])
    rows = w["decode_tokens"] / max(1, facts["new_tokens"])   # rows decoded
    steps = rows * (facts["new_tokens"] - 1)                  # row-steps
    return 100.0 * (least(retention_step(cfg, steps))
                    + least(retention_prompt(cfg, w["prefill_tokens"]))) / seconds


def gen_mfu_pct(facts) -> Optional[float]:
    """The whole step's share of the chip's bf16 peak: required operations a
    row (its prompt and its new tokens through every layer, the retention
    core, the head at the sampled positions) times rows per second."""
    if not facts.get("peaks") or not facts.get("rows"):
        return None
    cfg = facts["config"]
    new, rows = facts["new_tokens"], facts["rows"]
    tokens = facts["prompt_tokens"] + rows * (new - 1)
    ops = (tokens * dense_flops_per_token(cfg)
           + retention_prompt(cfg, tokens)["flops"] + rows * new * head_flops(cfg))
    return 100.0 * ops / facts["window_s"] / (
        facts["chips"] * facts["peaks"]["bf16_flops_per_s"])


def decode_hbm_share_pct(facts) -> Optional[float]:
    """Required bytes of the window's decode steps (weights once and each
    decoded row's state twice, a step) a second of the window, over the
    chip's memory bandwidth."""
    w = _window(facts)
    if not w or not facts.get("peaks"):
        return None
    cfg = facts["config"]
    rows_per_step = w["decode_tokens"] / max(1, facts["new_tokens"]) \
        * (facts["new_tokens"] - 1) / w["steps"]
    need = w["steps"] * decode_step_bytes(cfg, rows_per_step)
    return 100.0 * need / facts["window_s"] / facts["peaks"]["hbm_bytes_per_s"]


def state_fill_pct(facts) -> Optional[float]:
    """Mean slots in use at the window's decode steps over the cache's slots."""
    h = ((facts.get("counters_window") or {}).get("hists") or {}).get(
        "lm.step_slots_in_use")
    if not h or h["count"] <= 0 or not facts.get("state_slots"):
        return None
    return 100.0 * h["sum"] / h["count"] / facts["state_slots"]
