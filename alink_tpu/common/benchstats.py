"""Variance-hardened benchmark statistics + the BENCH regression gate.

Benchmark rounds once accumulated with no tool that compared them — a 12%
drop of the headline BERT number sat on record with no detector. This
module is that detector, in two layers:

1. **In-process measurement** — :func:`measure_interleaved` runs competing
   configurations A,B,A,B,... (never a block of A then a block of B, so
   allocator/page-cache/thermal drift between blocks charges both sides
   equally), :func:`trimmed_mean`/:func:`mean_ci` reject interference
   outliers, and :func:`compare_samples`/:func:`perf_gate` emit a
   noise-thresholded verdict: a delta only counts when it clears BOTH the
   configured noise floor and the combined confidence interval of the two
   measurements. This is the in-process perf gate tests pin.

2. **BENCH-file comparison** — :func:`compare_bench_files` (the engine
   behind ``python bench.py --compare OLD.json NEW.json``) flattens two
   BENCH round files (raw driver output or the ``{"parsed": ...}`` wrapper
   the round archive uses — see docs/bench_schema.md), classifies each
   shared numeric metric as higher-is-better / lower-is-better by name,
   applies a per-metric noise threshold (wider for wall-clocks and cold
   numbers, which ride compile caches and shared-container load), and
   reports regressions/improvements sorted by severity.

Only stdlib + no jax: importable anywhere, including the bench driver
before the platform loads.
"""

from __future__ import annotations

import json
import math
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

# Relative noise floors. Rates on a quiet machine repeat within a few
# percent; wall-clocks on a shared container swing harder; cold numbers
# additionally ride the persistent-XLA-cache state of the machine.
DEFAULT_NOISE_FLOOR = 0.08     # in-process gate (interleaved, CI-backed)
DEFAULT_THRESHOLD = 0.10       # file compare: rates/quality metrics
WALL_THRESHOLD = 0.25          # file compare: wall-clock / latency metrics
COLD_THRESHOLD = 0.35          # file compare: anything cold-start


# ---------------------------------------------------------------------------
# Robust statistics
# ---------------------------------------------------------------------------


def trimmed(xs, trim: float = 0.2) -> List[float]:
    """Samples with the top and bottom ``trim`` fraction dropped (at least
    one sample always survives)."""
    xs = sorted(float(x) for x in xs)
    k = int(len(xs) * trim)
    return xs[k:len(xs) - k] or xs


def trimmed_mean(xs, trim: float = 0.2) -> float:
    core = trimmed(xs, trim)
    return sum(core) / len(core)


def mean_ci(xs, trim: float = 0.2, z: float = 2.0) -> Tuple[float, float]:
    """(trimmed mean, ~95% half-width) — the half-width is ``z`` standard
    errors of the trimmed samples; 0 when fewer than two survive."""
    core = trimmed(xs, trim)
    m = sum(core) / len(core)
    if len(core) < 2:
        return m, 0.0
    var = sum((x - m) ** 2 for x in core) / (len(core) - 1)
    return m, z * math.sqrt(var / len(core))


def measure_interleaved(fns: Dict[str, Callable[[], Any]],
                        repeats: int = 7,
                        warmup: int = 1) -> Dict[str, List[float]]:
    """Wall-time samples for every named thunk, interleaved round-robin so
    machine drift during the window charges all configurations equally.
    ``warmup`` un-timed calls per thunk absorb compile/cache effects."""
    names = list(fns)
    for name in names:
        for _ in range(warmup):
            fns[name]()
    samples: Dict[str, List[float]] = {n: [] for n in names}
    for _ in range(repeats):
        for n in names:
            t0 = time.perf_counter()
            fns[n]()
            samples[n].append(time.perf_counter() - t0)
    return samples


def compare_samples(base: List[float], cand: List[float], *,
                    noise_floor: float = DEFAULT_NOISE_FLOOR,
                    trim: float = 0.2,
                    higher_is_better: bool = False) -> Dict[str, Any]:
    """Noise-thresholded verdict between two sample sets (timings by
    default: lower is better). A delta is significant only when it clears
    max(noise_floor, combined CI half-widths) — so a genuinely noisy pair
    of measurements widens its own gate instead of false-flagging."""
    mb, hb = mean_ci(base, trim)
    mc, hc = mean_ci(cand, trim)
    if mb == 0:
        delta = 0.0 if mc == 0 else math.inf
        u = 0.0
    else:
        delta = (mc - mb) / abs(mb)
        u = (hb + hc) / abs(mb)
    gate = max(noise_floor, u)
    if higher_is_better:
        worse, better = delta < -gate, delta > gate
    else:
        worse, better = delta > gate, delta < -gate
    return {
        "base_mean_s": round(mb, 6),
        "cand_mean_s": round(mc, 6),
        "delta_pct": round(delta * 100, 2) if math.isfinite(delta) else None,
        "ci_pct": round(u * 100, 2),
        "gate_pct": round(gate * 100, 2),
        "significant": bool(worse or better),
        "verdict": ("regression" if worse
                    else "improvement" if better else "no-change"),
        "samples": {"base": len(base), "cand": len(cand)},
    }


def perf_gate(base_fn: Callable[[], Any], cand_fn: Callable[[], Any], *,
              repeats: int = 7, warmup: int = 1,
              noise_floor: float = DEFAULT_NOISE_FLOOR,
              trim: float = 0.2) -> Dict[str, Any]:
    """Interleave-measure two thunks and return the comparison verdict —
    the smallest useful perf gate: noise-level deltas read ``no-change``,
    a real slowdown reads ``regression``."""
    samples = measure_interleaved({"base": base_fn, "cand": cand_fn},
                                  repeats=repeats, warmup=warmup)
    return compare_samples(samples["base"], samples["cand"],
                           noise_floor=noise_floor, trim=trim)


# ---------------------------------------------------------------------------
# BENCH-file comparison
# ---------------------------------------------------------------------------


def metric_direction(path: str) -> Optional[str]:
    """"higher" / "lower" is-better classification by metric name; None for
    config constants and counts that carry no direction (reported as
    informational, never flagged)."""
    p = path.lower()
    leaf = p.rsplit(".", 1)[-1]
    if leaf == "value":           # the primary metric is a throughput
        return "higher"
    if "pct" in leaf:
        # signed percentages centered on 0 (overhead_pct, delta_pct,
        # ci_pct): a relative delta between two near-zero noise readings
        # is meaningless and would false-flag healthy rounds
        return None
    if "accuracy_delta" in p or "accuracy_band" in p:
        # quantized-serving gate readouts: near-zero diffs against the
        # fp32 baseline, directionless for the same reason parity_max_diff
        # is — must be classified BEFORE the "accuracy"→higher substring
        return None
    for s in ("per_sec", "accuracy", "purity", "mfu", "hit_rate",
              "speedup", "tflops", "batch_fill", "bandwidth", "mb_per_s",
              "efficiency"):
        if s in p:
            return "higher"
    for s in ("wall", "latency", "overhead", "tax", "span_cost",
              "load_s", "restore", "_ms", "p50", "p90", "p99"):
        if s in p:
            return "lower"
    if leaf.endswith("_s"):
        return "lower"
    return None


def metric_threshold(path: str, override: Optional[float] = None) -> float:
    if override is not None:
        return override
    p = path.lower()
    if "cold" in p:
        return COLD_THRESHOLD
    if "efficiency" in p:
        # roofline efficiency = achieved / ceiling with the achieved side
        # read off a measured wall — it inherits the wall's jitter, not a
        # rate metric's stability
        return WALL_THRESHOLD
    if metric_direction(p) == "lower":
        return WALL_THRESHOLD
    return DEFAULT_THRESHOLD


def flatten_metrics(doc: Dict[str, Any]) -> Dict[str, float]:
    """Dot-path -> number map of one BENCH round: the primary ``value``
    plus every finite numeric leaf under ``extras`` (lists and booleans are
    skipped — traces and parity bits are not comparable scalars). Accepts
    both the raw driver line and the archived ``{"parsed": {...}}``
    wrapper."""
    root = doc.get("parsed") if isinstance(doc.get("parsed"), dict) else doc
    out: Dict[str, float] = {}

    def walk(prefix: str, v: Any) -> None:
        if isinstance(v, dict):
            for k, x in v.items():
                walk(f"{prefix}.{k}", x)
        elif isinstance(v, bool):
            return
        elif isinstance(v, (int, float)) and math.isfinite(v):
            out[prefix] = float(v)

    v = root.get("value")
    if isinstance(v, (int, float)) and not isinstance(v, bool):
        out["value"] = float(v)
    walk("extras", root.get("extras") or {})
    return out


def round_device_kind(doc: Dict[str, Any]) -> Optional[str]:
    """The accelerator a BENCH round ran on, read from the round's own
    extras (``profiling.device.device_kind``, falling back to
    ``bert_mfu.device_kind`` for rounds archived before the profiling
    extra existed). None when the round carries no device evidence."""
    root = doc.get("parsed") if isinstance(doc.get("parsed"), dict) else doc
    extras = root.get("extras") or {}
    for probe in (("profiling", "device", "device_kind"),
                  ("bert_mfu", "device_kind")):
        v: Any = extras
        for k in probe:
            v = v.get(k) if isinstance(v, dict) else None
        if isinstance(v, str) and v:
            return v
    return None


# metric-name substrings that stay comparable ACROSS accelerators: model
# quality and cache-behavior ratios do not change when the chip does, so a
# platform-change compare still gates them. Everything with a direction
# that is not in this list is hardware-bound (rates, wall-clocks, FLOPs)
# and demotes to an explicit "platform-change" verdict instead of
# false-flagging a hardware swap as a code regression.
_PLATFORM_INDEPENDENT = ("accuracy", "purity", "hit_rate", "holdout")


def compare_bench_files(old_path: str, new_path: str, *,
                        threshold: Optional[float] = None) -> Dict[str, Any]:
    """Compare two BENCH round files metric-by-metric and return the
    regression report ``bench.py --compare`` prints. ``threshold``
    overrides every per-metric noise threshold (fraction, e.g. 0.1).

    Platform awareness: when the two rounds ran on different accelerators
    (``round_device_kind`` differs — e.g. a TPU round vs a CPU container),
    hardware-bound perf metrics cannot evidence a code regression; they are
    reported under the explicit ``platform-change`` verdict (loud, counted,
    never silently dropped) while hardware-independent quality metrics
    (accuracy/purity/hit-rate) keep gating."""
    with open(old_path) as f:
        old = json.load(f)
    with open(new_path) as f:
        new = json.load(f)
    kind_old, kind_new = round_device_kind(old), round_device_kind(new)
    platform_changed = bool(kind_old and kind_new and kind_old != kind_new)
    mo, mn = flatten_metrics(old), flatten_metrics(new)
    entries: List[Dict[str, Any]] = []
    for path in sorted(set(mo) & set(mn)):
        if "error" in path.lower():
            continue
        a, b = mo[path], mn[path]
        if a == 0 and b == 0:
            delta = 0.0
        elif a == 0:
            continue                      # no relative scale to judge by
        else:
            delta = (b - a) / abs(a)
        direction = metric_direction(path)
        thr = metric_threshold(path, threshold)
        if direction is None:
            verdict = "info"
        elif platform_changed and not any(
                s in path.lower() for s in _PLATFORM_INDEPENDENT):
            verdict = "platform-change"
        elif direction == "higher":
            verdict = ("regression" if delta < -thr
                       else "improvement" if delta > thr else "no-change")
        else:
            verdict = ("regression" if delta > thr
                       else "improvement" if delta < -thr else "no-change")
        entries.append({
            "metric": path, "old": a, "new": b,
            "delta_pct": round(delta * 100, 2),
            "direction": direction,
            "threshold_pct": round(thr * 100, 1),
            "verdict": verdict,
        })
    by_sev = lambda e: -abs(e["delta_pct"])  # noqa: E731
    regressions = sorted((e for e in entries if e["verdict"] == "regression"),
                         key=by_sev)
    improvements = sorted((e for e in entries
                           if e["verdict"] == "improvement"), key=by_sev)
    return {
        "old": str(old_path),
        "new": str(new_path),
        "platform_change": ({"old": kind_old, "new": kind_new}
                            if platform_changed else None),
        "metrics_compared": len(entries),
        "only_in_old": len(set(mo) - set(mn)),
        "only_in_new": len(set(mn) - set(mo)),
        "regressions": regressions,
        "improvements": improvements,
        "no_change": sum(1 for e in entries if e["verdict"] == "no-change"),
        "informational": sum(1 for e in entries if e["verdict"] == "info"),
        "platform_demoted": sum(1 for e in entries
                                if e["verdict"] == "platform-change"),
        "verdict": "regression" if regressions else "ok",
    }
