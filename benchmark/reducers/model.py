"""Operations and bytes from shapes, the table of peaks, and the shares built
on them. Nothing here asks the compiler: the counts are what the algorithm
needs (no recomputed work), from the configuration's sizes alone.
"""

from __future__ import annotations

import json
import os
import re
from typing import Any, Dict, List, Optional


def device_peaks(device_kind: str) -> Dict[str, Any]:
    """The row of ``peaks.json`` for exactly this ``device_kind``; any other
    kind is an error, never a default."""
    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device_kind {device_kind!r}; the table "
                       f"has {sorted(table)}")
    return table[device_kind]


def forward_flops_per_row(cfg: Dict[str, Any], seq: int) -> float:
    """Multiply-adds times two of one row's forward pass: the four attention
    projections, QK^T and PV, the two MLP products, the pooler and the head.
    Embedding look-ups, layer norms, softmax and GELU are left out (under 1%)."""
    h, f, layers = cfg["hidden_size"], cfg["intermediate_size"], cfg["num_hidden_layers"]
    per_token = layers * (2 * 4 * h * h + 2 * 2 * h * f + 2 * 2 * seq * h)
    return seq * per_token + 2 * h * h + 2 * h * 2


def attention_core(cfg: Dict[str, Any], seq: int, rows: float, mode: str
                   ) -> Dict[str, float]:
    """Operations and least bytes of the attention core (QK^T, softmax, PV)
    over ``rows`` rows. Bytes: Q, K, V read and O written once, in bfloat16;
    training adds the backward pass (twice the products; dO, Q, K, V, O read
    and dQ, dK, dV written)."""
    h, layers = cfg["hidden_size"], cfg["num_hidden_layers"]
    flops = rows * layers * (2 * 2 * seq * seq * h)
    tensors = rows * layers * seq * h * 2.0        # one of Q/K/V/O in bf16
    if mode == "train":
        return {"flops": 3 * flops, "bytes": (4 + 8) * tensors}
    return {"flops": flops, "bytes": 4 * tensors}


def mfu_pct(facts, mode: str) -> Optional[float]:
    """The whole step's share of the chips' bf16 peak: required operations
    per row (forward, and twice that again for the backward pass in training)
    times rows per second of the window, over chips times peak."""
    if not facts.get("peaks") or facts.get("mode") != mode:
        return None
    per_row = forward_flops_per_row(facts["config"], facts["seq_len"])
    if mode == "train":
        per_row *= 3
    return 100.0 * per_row * facts["rows_per_s"] / (
        facts["chips"] * facts["peaks"]["bf16_flops_per_s"])


def attn_roofline_pct(facts, mode: str, patterns: List[str]) -> Optional[float]:
    """Least time the chip could take for the attention core of the rows the
    traced window processed (the larger of operations over peak and bytes over
    bandwidth) over the device time of the operations ``patterns`` match, by
    what they write or by what they read."""
    tr = facts.get("trace")
    if not tr or not facts.get("peaks") or facts.get("mode") != mode:
        return None
    rx = [re.compile(p.replace("{B}", str(facts["batch"] // facts["chips"]))
                      .replace("{H}", str(facts["config"]["num_attention_heads"]))
                      .replace("{S}", str(facts["seq_len"]))
                      .replace("{D}", str(facts["config"]["hidden_size"]
                                          // facts["config"]["num_attention_heads"])))
          for p in patterns]
    matched = [(n, s) for n, s, _, reads in tr["ops_all"]
               if any(r.search(n) or r.search(reads) for r in rx)]
    seconds = sum(s for _, s in matched)
    tr.setdefault("matched", {})[facts.get("metric", "attention")] = matched[:12]
    if seconds <= 0:
        return None
    rows = facts.get("trace_rows") or facts["rows_per_s"] * tr["window_s"]
    rows /= facts["chips"]
    need = attention_core(facts["config"], facts["seq_len"], rows, mode)
    least = max(need["flops"] / facts["peaks"]["bf16_flops_per_s"],
                need["bytes"] / facts["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / seconds
