"""Operations and bytes of an ``afmoe`` generator (gated grouped-query
attention over a sliding window or every earlier position, the dense
feed-forward, an expert layer of which this chip holds a share beside a
shared expert), from the configuration's shapes and the program's counters,
and the per-layer metrics built on them. It imports nothing of the program.

The attention cores are the kernels ``reducers/mimo.py`` counts, with the
same shapes of work, so their counts are that module's, read through
:func:`as_mimo` (this family's keys under the names it reads). What this
family adds to the work every token passes (the output gate, the router,
the shared expert) is counted here, and the routed experts' need is
``reducers/ling.py``'s. Products are multiply-adds times two, weights are
bfloat16 read once a decode step; norms, rotary positions, the gate's
sigmoid and the embedding look-up are left out (under 1%).
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional

from benchmark.reducers import ling, mimo
from benchmark.reducers.ling import _least


def kinds(cfg: Dict[str, Any]) -> List[tuple]:
    """(mixer, ffn) of every kept layer, as ``reference/trinity.py`` reads
    ``layer_types`` and ``num_dense_layers``."""
    mixer = {"sliding_attention": "window", "full_attention": "attention"}
    return [(mixer[t], "dense" if i < cfg["num_dense_layers"] else "experts")
            for i, t in enumerate(cfg["layer_types"][:cfg["num_hidden_layers"]])]


def as_mimo(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """The configuration with the keys ``reducers/mimo.py`` reads: the layer
    pattern, one key/value width for both kinds, the routed experts held."""
    k = kinds(cfg)
    return dict(cfg, hybrid_layer_pattern=[int(m == "window") for m, _ in k],
                moe_layer_freq=[int(f == "experts") for _, f in k],
                swa_num_key_value_heads=cfg["num_key_value_heads"],
                v_head_dim=cfg["head_dim"], n_routed_experts=cfg["num_experts"])


def _view(facts) -> Dict[str, Any]:
    return dict(facts, config=as_mimo(facts["config"]))


def mixer_weights(cfg: Dict[str, Any]) -> int:
    """q, k, v, o and the output gate of one layer (both kinds alike)."""
    h, hq, hkv = (cfg["hidden_size"], cfg["num_attention_heads"],
                  cfg["num_key_value_heads"])
    return h * cfg["head_dim"] * (3 * hq + 2 * hkv)


def dense_weights(cfg: Dict[str, Any]) -> int:
    """Every matrix a token passes whatever the router says: mixers, the
    dense feed-forward, and in each expert layer the router and the shared
    expert."""
    h = cfg["hidden_size"]
    experts = sum(f == "experts" for _, f in kinds(cfg))
    per_expert_layer = (cfg["published"]["num_experts"] * h
                        + cfg["num_shared_experts"] * ling.expert_weights(cfg))
    return (cfg["num_hidden_layers"] * mixer_weights(cfg)
            + (len(kinds(cfg)) - experts) * 3 * h * cfg["intermediate_size"]
            + experts * per_expert_layer)


def gqa_roofline_pct(facts, patterns: List[str]) -> Optional[float]:
    """``gqa_roofline.serve``'s reading (``reducers/mimo.py``) of the full
    layer's core: decode steps by the cache's bytes as far as filled, prompt
    chunks by their causal operations, over the kernel's device time."""
    return mimo.gqa_roofline_pct(_view(facts), patterns)


def window_roofline_pct(facts, patterns: List[str]) -> Optional[float]:
    """``window_roofline.serve``'s reading of the sliding layers' core: decode
    steps by the ring's bytes, prompt positions by ``min(t + 1, W)`` keys."""
    return mimo.window_roofline_pct(_view(facts), patterns)


def _moe_window(facts) -> Optional[Dict[str, float]]:
    """``reducers/mimo.py``'s tokens, steps and held assignments of the
    window, with the prompt's and the steps' tokens apart."""
    m = mimo._moe_window(_view(facts))
    if not m:
        return None
    step_tokens = m["rows"] * (facts["new_tokens"] - 1)
    return dict(m, step_tokens=step_tokens,
                prefill_tokens=m["tokens"] - step_tokens)


def _expert_seconds(facts, patterns: List[str], chunk: int) -> float:
    """Device seconds of the operations ``patterns`` match (``reducers/
    ling.py``'s placeholders), containers apart: a loop, a call and the
    rungs' switch (``cond``) hold operations the trace lists too."""
    tr = facts["trace"]
    rx = ling._patterns(facts, patterns, chunk)
    matched = [(n, s) for n, s, _, reads in tr["ops_all"]
               if not n.startswith(("while", "conditional", "call", "cond"))
               and any(r.search(n) or r.search(reads) for r in rx)]
    tr.setdefault("matched", {})[facts.get("metric", "trinity")] = matched[:12]
    return sum(s for _, s in matched)


def moe_roofline_pct(facts, patterns: List[str], chunk: int) -> Optional[float]:
    """Least time of the routed experts held here (decode steps by the bytes
    of the experts an assignment reached, prompt tokens by their operations)
    over the device time of the grouped products and of the operations that
    route, sort, gather and weigh their rows."""
    m = _moe_window(facts) if facts.get("trace") and facts.get("peaks") else None
    if not m:
        return None
    seconds = _expert_seconds(facts, patterns, chunk)
    if seconds <= 0:
        return None
    need = ling._moe_need(facts, m)
    return 100.0 * (_least(facts, need["step"]) + _least(facts, need["prompt"])
                    ) / seconds


def _cores(facts):
    v = _view(facts)
    a = mimo._attention_window(v)
    return None if not a else (mimo._full_need(v, a), mimo._window_need(v, a))


def gen_mfu_pct(facts) -> Optional[float]:
    """The whole step's share of the chip's bf16 peak: required operations of
    the window (every token through the matrices all tokens pass, the gate
    and the shared expert among them, the experts held here for the
    assignments they got, both cores at the pairs seen, the head at the
    sampled positions) a second of the window."""
    m = _moe_window(facts) if facts.get("peaks") and facts.get("rows") else None
    cores = _cores(facts)
    if not m or not cores:
        return None
    cfg = facts["config"]
    ops = (m["tokens"] * 2.0 * dense_weights(cfg)
           + m["tokens"] * m["layers"] * m["held_per_token"] * 2.0
           * ling.expert_weights(cfg)
           + sum(part["flops"] for need in cores for part in need.values())
           + m["rows"] * facts["new_tokens"] * 2.0 * cfg["hidden_size"]
           * cfg["vocab_size"])
    return 100.0 * ops / facts["window_s"] / (
        facts["chips"] * facts["peaks"]["bf16_flops_per_s"])


def decode_hbm_share_pct(facts) -> Optional[float]:
    """Required bytes of the window's decode steps (the matrices every token
    passes and the head once a step, the routed experts an assignment
    reached, each row's full-layer cache as far as filled and its rings) a
    second of the window, over the chip's memory bandwidth."""
    m = _moe_window(facts) if facts.get("peaks") else None
    cores = _cores(facts)
    if not m or not cores:
        return None
    cfg = facts["config"]
    fixed = 2.0 * (dense_weights(cfg) + cfg["hidden_size"] * cfg["vocab_size"])
    need = (m["steps"] * fixed + ling._moe_need(facts, m)["step"]["bytes"]
            + sum(need["step"]["bytes"] for need in cores))
    return 100.0 * need / facts["window_s"] / facts["peaks"]["hbm_bytes_per_s"]
