"""Per-layer metrics read from the program's counters and histograms
(``alink_tpu.common.metrics``) and from the benchmark's own host timers.

``facts["counters_setup"]`` and ``facts["counters_window"]`` hold, for the two
phases of a run, the growth of every counter and of every histogram (bucket
counts, sum, count). A reducer that finds nothing to read returns None.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional


def hist_delta(before: Optional[Dict[str, Any]], after: Dict[str, Any]
               ) -> Dict[str, Any]:
    """Growth of one fixed-bucket histogram between two of its states."""
    if before is None:
        before = {"counts": [0] * len(after["counts"]), "count": 0, "sum": 0.0}
    return {"buckets": list(after["buckets"]),
            "counts": [a - b for a, b in zip(after["counts"], before["counts"])],
            "count": after["count"] - before["count"],
            "sum": after["sum"] - before["sum"]}


def _quantile(h: Dict[str, Any], q: float) -> Optional[float]:
    """Quantile by linear interpolation inside the bucket the rank falls in;
    the overflow bucket answers with the last edge."""
    if h["count"] <= 0:
        return None
    target, cum, lo = q * h["count"], 0.0, 0.0
    for edge, c in zip(h["buckets"], h["counts"]):
        if c and cum + c >= target:
            return lo + (target - cum) / c * (edge - lo)
        cum, lo = cum + c, edge
    return float(h["buckets"][-1])


def _phase(facts, phase: str) -> Dict[str, Any]:
    return facts.get(f"counters_{phase}") or {"counters": {}, "hists": {}}


def counter(facts, phase: str, name: str) -> Optional[float]:
    """Growth of one counter over a phase; 0 is a reading here (a count)."""
    return float(_phase(facts, phase)["counters"].get(name, 0))


def ratio_pct(facts, phase: str, num: str, den: List[str]) -> Optional[float]:
    c = _phase(facts, phase)["counters"]
    total = sum(c.get(n, 0) for n in den)
    return 100.0 * c.get(num, 0) / total if total > 0 else None


def hist_quantile_ms(facts, phase: str, name: str, q: float) -> Optional[float]:
    h = _phase(facts, phase)["hists"].get(name)
    v = _quantile(h, q) if h else None
    return None if v is None else 1e3 * v


def hist_sum_share_pct(facts, phase: str, name: str) -> Optional[float]:
    """Sum of a histogram of seconds over the phase's wall."""
    h = _phase(facts, phase)["hists"].get(name)
    if not h or h["count"] <= 0:
        return None
    return 100.0 * h["sum"] / facts["window_s"]


def hist_mean_over_pct(facts, phase: str, name: str, over: List[str]
                       ) -> Optional[float]:
    """Mean of a histogram over a number of the traffic file (a batch's rows
    over the batch cap)."""
    h = _phase(facts, phase)["hists"].get(name)
    if not h or h["count"] <= 0:
        return None
    cap: Any = facts["traffic"]
    for k in over:
        cap = cap[k]
    return 100.0 * h["sum"] / h["count"] / cap


def host_span_share_pct(facts, num: str, den: str) -> Optional[float]:
    """Host time under one benchmark annotation over that under another."""
    spans = facts.get("host_spans") or {}
    if not spans.get(num) or not spans.get(den):
        return None
    return 100.0 * sum(spans[num]) / sum(spans[den])


def fact_ms(facts, name: str) -> Optional[float]:
    v = facts.get(name)
    return None if v is None else 1e3 * v
