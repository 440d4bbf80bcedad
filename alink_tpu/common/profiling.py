"""Performance observatory: XLA cost/memory accounting + roofline attribution.

PR 5's span tracer says *where time goes*; nothing in the repo said *what
each compiled program should cost*. This module adds the missing static
side of the ledger and joins it with the measured one:

1. **Program-cost registry** — every first call of a cached program with a
   new shape signature (``common/jitcache.py``) registers a cost record.
   Capture is LAZY: the hot path only stores the signature + pytree
   structure (a dict insert); the actual ``Lowered.cost_analysis()`` —
   FLOPs, transcendentals, bytes accessed — runs on the first *readout*
   (:func:`profile_summary`, ``job_report()``, a ``/metrics`` scrape), by
   re-lowering the cached program on zeros of the recorded signature. That
   keeps the execution path bit-identical to profiling-off
   (``tests/test_profiling.py``); its cost on the chip is not measured.
   ``ALINK_PROFILING=deep`` switches to eager capture at compile time and
   additionally runs ``Compiled.memory_analysis()`` for exact
   argument/output/temp/peak HBM; the default ``on`` mode estimates memory
   from the live call's argument and output buffers.

2. **Measured join** — warm calls of every cached program are timed
   (dispatch wall; ``ALINK_PROFILE_SYNC=on`` blocks on the result for true
   device wall at the cost of pipelining overlap — results unchanged), so
   each kernel reports achieved FLOP/s next to its static cost.

3. **Roofline attribution** (Williams et al., 2009) — arithmetic
   intensity = FLOPs / bytes accessed, compared against the device ridge
   point (peak FLOP/s ÷ HBM bandwidth, from a per-generation table with
   ``ALINK_PEAK_TFLOPS`` / ``ALINK_PEAK_HBM_GBS`` overrides): a kernel is
   *compute-bound* above the ridge, *bandwidth-bound* below it, and its
   efficiency is achieved/ceiling at its own intensity.

4. **HBM watermarks** — :func:`sample_device_memory` reads
   ``device.memory_stats()`` at executor node boundaries (graceful no-op
   on backends without stats, e.g. CPU) and keeps the process-wide peak.

Registry records survive program-cache eviction: costs live here, not on
the ``CachedProgram`` (an evicted program that was never read resolves to
``capture="evicted"`` — read ``profile_summary()`` before eviction, or run
under ``deep``, to pin exact numbers).

Everything is gated by ``ALINK_PROFILING`` (default **on**; ``off``
restores zero-capture execution, read per event so tests can flip it).
Profiling NEVER changes results — the off-vs-on bit-parity contract is
CI-pinned in ``tests/test_profiling.py``.
"""

from __future__ import annotations

import re
import sys
import threading
import time
from collections import OrderedDict
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from .env import env_flag, env_float, env_int, env_str
from .metrics import metrics

# ---------------------------------------------------------------------------
# Gates
# ---------------------------------------------------------------------------

_MODES = ("off", "on", "deep")


def profiling_mode() -> str:
    """``ALINK_PROFILING``: ``on`` (default — lazy cost capture, estimated
    memory), ``deep`` (eager capture at compile time + exact
    ``memory_analysis()``), or ``off``. Unrecognized values degrade to the
    nearest boolean reading (config typos must not crash a job)."""
    raw = (env_str("ALINK_PROFILING", "on") or "on").strip().lower()
    if raw in _MODES:
        return raw
    return "off" if raw in ("0", "false", "no", "none", "") else "on"


def profiling_enabled() -> bool:
    return profiling_mode() != "off"


def sync_enabled() -> bool:
    """``ALINK_PROFILE_SYNC=on`` blocks on every profiled program result so
    exec timings measure device wall, not dispatch. Results are unchanged;
    transfer/compute overlap is serialized, so leave it off in
    production."""
    return env_flag("ALINK_PROFILE_SYNC", default=False)


# ---------------------------------------------------------------------------
# Cost-analysis normalization
# ---------------------------------------------------------------------------


def xla_cost_analysis(stage) -> Dict[str, float]:
    """Normalize ``Lowered``/``Compiled``.cost_analysis() across jax
    versions (older backends return a list of per-computation dicts, newer
    a flat dict) into ``{"flops", "transcendentals", "bytes_accessed"}``
    with absent properties omitted. Never raises — an empty dict means the
    backend reported nothing (callers fall back to analytic formulas)."""
    try:
        ca = stage.cost_analysis()
    except Exception:
        return {}
    if isinstance(ca, (list, tuple)):
        merged: Dict[str, float] = {}
        for d in ca:
            if isinstance(d, dict):
                for k, v in d.items():
                    if isinstance(v, (int, float)):
                        merged[k] = merged.get(k, 0.0) + float(v)
        ca = merged
    if not isinstance(ca, dict):
        return {}
    out: Dict[str, float] = {}
    for src, dst in (("flops", "flops"), ("transcendentals", "transcendentals"),
                     ("bytes accessed", "bytes_accessed")):
        v = ca.get(src)
        if isinstance(v, (int, float)) and v >= 0:
            out[dst] = float(v)
    return out


_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2,
                "bf16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
                "f64": 8, "c64": 8, "c128": 16}

_SHAPE_RE = re.compile(r"(pred|bf16|[fsuc]\d+)\[([\d,]*)\]")
_COLLECTIVE_RE = re.compile(
    r"=\s+(\([^)]*\)|\S+)\s+"
    r"(all-to-all|all-gather|all-reduce|reduce-scatter|collective-permute)"
    r"(?:-start)?\(")
_COMPUTATION_RE = re.compile(r"^(?:ENTRY\s+)?%(\S+)\s+\(.*\)\s+->.*\{")
_CALLEE_RE = re.compile(
    r"(?:calls|to_apply|body|condition|true_computation|false_computation)="
    r"%(\S+?)[,)\s]|branch_computations=\{([^}]*)\}")


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for dtype, dims in _SHAPE_RE.findall(shape_str):
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES.get(dtype, 4)
    return total


def collective_bytes(compiled, include_conditional: bool = False) -> int:
    """Per-device bytes moved by collectives (all-to-all / all-gather /
    all-reduce / reduce-scatter / collective-permute) in a ``Compiled``'s
    HLO, summed over result shapes.

    ``include_conditional=False`` (default) skips computations reachable
    only through ``conditional`` branches — i.e. reports the steady-state
    wire cost, excluding rarely-taken fallbacks (the APS bucket-overflow
    path) that XLA compiles in but a normal step never executes. Returns 0
    when the backend exposes no HLO text."""
    try:
        hlo = compiled.as_text()
    except Exception:
        return 0

    # split into computation blocks; record each block's collective bytes,
    # its callees, and the roots referenced from `conditional` instructions
    per_comp: Dict[str, int] = {}
    callees: Dict[str, list] = {}
    cond_roots: list = []
    name = ""
    entry = ""
    for line in hlo.splitlines():
        header = _COMPUTATION_RE.match(line)
        if header:
            name = header.group(1)
            if line.startswith("ENTRY"):
                entry = name
            per_comp.setdefault(name, 0)
            callees.setdefault(name, [])
            continue
        refs = []
        for single, branches in _CALLEE_RE.findall(line):
            if single:
                refs.append(single)
            refs.extend(b.strip().lstrip("%")
                        for b in branches.split(",") if b.strip())
        if " conditional(" in line:
            cond_roots.extend(refs)
        elif name:
            callees[name].extend(refs)
        m = _COLLECTIVE_RE.search(line)
        if m and name:
            per_comp[name] += _shape_bytes(m.group(1))

    excluded: set = set()
    if not include_conditional:
        # a computation is steady-state if the entry reaches it WITHOUT
        # passing through a conditional branch edge (cond-branch refs are
        # kept out of `callees` above); only computations reachable
        # exclusively via conditionals are excluded — one XLA CSE'd
        # between a fallback branch and the steady path still counts
        steady: set = set()
        stack = [entry] if entry else []
        while stack:
            c = stack.pop()
            if c in steady:
                continue
            steady.add(c)
            stack.extend(callees.get(c, []))
        stack = [c for c in cond_roots if c not in steady]
        while stack:
            c = stack.pop()
            if c in excluded or c in steady:
                continue
            excluded.add(c)
            stack.extend(x for x in callees.get(c, []) if x not in steady)
    return sum(b for comp, b in per_comp.items() if comp not in excluded)


# ---------------------------------------------------------------------------
# The program-cost registry
# ---------------------------------------------------------------------------

_reg_lock = threading.RLock()
_COSTS: "OrderedDict[tuple, Dict[str, Any]]" = OrderedDict()
_DEFAULT_REGISTRY_SIZE = 2048


def _registry_cap() -> int:
    return env_int("ALINK_PROFILE_REGISTRY_SIZE", _DEFAULT_REGISTRY_SIZE)


def _sig_array_bytes(sig: tuple) -> int:
    total = 0
    for s in sig:
        if s[0] == "a":
            total += int(np.prod(s[1], dtype=np.int64)) * np.dtype(s[2]).itemsize
    return total


def _tree_bytes(out) -> Optional[int]:
    """Total buffer bytes of a pytree of arrays (shape/dtype only — never
    blocks on async values)."""
    try:
        import jax

        total = 0
        for leaf in jax.tree_util.tree_leaves(out):
            shape = getattr(leaf, "shape", None)
            dtype = getattr(leaf, "dtype", None)
            if shape is not None and dtype is not None:
                total += int(np.prod(shape, dtype=np.int64)) * \
                    np.dtype(dtype).itemsize
        return total
    except Exception:
        return None


def _sig_str(sig: tuple) -> str:
    parts = []
    for s in sig:
        if s[0] == "a":
            parts.append(np.dtype(s[2]).name
                         + "[" + ",".join(str(d) for d in s[1]) + "]")
    return ",".join(parts) or "()"


def _new_record(prog, sig: tuple, treedef=None) -> Dict[str, Any]:
    arg_b = _sig_array_bytes(sig)
    return {
        "kernel": prog.kernel_id,
        "signature": _sig_str(sig),
        "capture": "pending",
        "flops": None,
        "transcendentals": None,
        "bytes_accessed": None,
        "argument_bytes": arg_b,
        "output_bytes": None,
        "temp_bytes": None,
        "peak_hbm_bytes": None,
        "memory_source": "estimate",
        "compile_s": None,
        "persist": None,
        "calls": 0,
        "exec_total_s": 0.0,
        "exec_min_s": None,
        "_sig": sig,
        "_treedef": treedef,
        "_prog_key": prog.key,
    }


def _insert_locked(key: tuple, rec: Dict[str, Any]) -> None:
    _COSTS[key] = rec
    cap = _registry_cap()
    while cap > 0 and len(_COSTS) > cap:
        _COSTS.popitem(last=False)
        metrics.incr("profile.registry_evictions")


def _refresh_peak_estimate(rec: Dict[str, Any]) -> None:
    if rec["memory_source"] == "estimate":
        rec["peak_hbm_bytes"] = (rec.get("argument_bytes") or 0) + \
            (rec.get("output_bytes") or 0)


def note_compiled(prog, sig: tuple, args, out, compile_s: float,
                  persist: Optional[str] = None) -> None:
    """Called by ``CachedProgram`` on the first successful call of a new
    shape signature: enqueue a pending cost record (cheap — a dict insert
    plus the pytree structure of ``args``). ``deep`` mode resolves it
    eagerly, charging the extra lower+compile to the compile event it
    rides on.

    ``persist`` labels where the executable came from: ``"hit"`` (the
    persistent compile cache served it — ``compile_s`` measured trace +
    deserialize, not a backend compile), ``"compile"`` (persistence on,
    compiled fresh), or None (persistence off). Cost capture is identical
    either way: lazy resolution re-lowers from the recorded signature, so
    the static XLA cost survives a persist-hit that skipped the compiler."""
    mode = profiling_mode()
    if mode == "off":
        return
    treedef = None
    try:
        import jax

        treedef = jax.tree_util.tree_structure(args)
    except Exception:
        pass
    key = (prog.key, sig)
    with _reg_lock:
        rec = _COSTS.get(key)
        if rec is None:
            rec = _new_record(prog, sig, treedef)
            _insert_locked(key, rec)
        elif rec.get("_treedef") is None:
            rec["_treedef"] = treedef
        rec["compile_s"] = round(float(compile_s), 6)
        rec["persist"] = persist
        if rec["output_bytes"] is None:
            rec["output_bytes"] = _tree_bytes(out)
            _refresh_peak_estimate(rec)
    if mode == "deep" and rec["capture"] == "pending":
        _resolve_record(rec, prog=prog, deep=True)


def note_exec(prog, sig: tuple, seconds: float, args=None, out=None) -> None:
    """Per-call exec accounting for a warm (already-traced) program call.
    O(1) on the steady path: one dict lookup + three float updates under
    the registry lock. A missing record (the program traced while
    profiling was off, or the record was registry-evicted) is recreated
    here, including the pytree structure lazy resolution needs."""
    key = (prog.key, sig)
    with _reg_lock:
        rec = _COSTS.get(key)
        if rec is None:
            treedef = None
            if args is not None:
                try:
                    import jax

                    treedef = jax.tree_util.tree_structure(args)
                except Exception:
                    pass
            rec = _new_record(prog, sig, treedef)
            if out is not None and rec["output_bytes"] is None:
                rec["output_bytes"] = _tree_bytes(out)
                _refresh_peak_estimate(rec)
            _insert_locked(key, rec)
        rec["calls"] += 1
        rec["exec_total_s"] += seconds
        m = rec["exec_min_s"]
        rec["exec_min_s"] = seconds if m is None or seconds < m else m


# ---------------------------------------------------------------------------
# Lazy resolution
# ---------------------------------------------------------------------------


class _Unresolvable(Exception):
    pass


def _rebuild_args(rec: Dict[str, Any]):
    """Reconstruct a call-compatible argument pytree from the recorded
    signature: array leaves become zeros of the recorded shape/dtype,
    hashable static leaves are replayed verbatim. The repr-fallback leaves
    ``args_signature`` stores for unhashable statics cannot be replayed —
    those records resolve to ``capture="error"``."""
    import jax

    treedef = rec.get("_treedef")
    if treedef is None:
        raise _Unresolvable("no pytree structure recorded")
    leaves: List[Any] = []
    for s in rec["_sig"]:
        if s[0] == "a":
            leaves.append(np.zeros(s[1], np.dtype(s[2])))
        else:
            tname, val = s[1], s[2]
            if isinstance(val, str) and tname != "str":
                raise _Unresolvable(f"unreplayable static leaf ({tname})")
            leaves.append(val)
    return jax.tree_util.tree_unflatten(treedef, leaves)


def _find_program(rec: Dict[str, Any]):
    from .jitcache import programs

    for p in programs(rec["kernel"]):
        if p.key == rec.get("_prog_key"):
            return p
    return None


def _resolve_record(rec: Dict[str, Any], prog=None, deep: bool = False) -> None:
    """Materialize the XLA cost (and, under ``deep``, memory) analysis for
    one pending record. Runs OUTSIDE the registry lock — lowering can take
    milliseconds-to-seconds for large programs and must not block the
    execution hot path's ``note_exec``."""
    if prog is None:
        prog = _find_program(rec)
    if prog is None:
        # the program was LRU-evicted before anyone read the registry; the
        # record (exec stats, memory estimate) survives, the static cost
        # is gone with the executable
        rec["capture"] = "evicted"
        metrics.incr("profile.resolve_evicted")
        return
    t0 = time.perf_counter()
    try:
        args = _rebuild_args(rec)
        lowered = prog.jit_fn.lower(*args)
        cost = xla_cost_analysis(lowered)
        if deep:
            compiled = lowered.compile()
            cost = xla_cost_analysis(compiled) or cost
            _apply_memory_analysis(rec, compiled)
        rec["flops"] = cost.get("flops")
        rec["transcendentals"] = cost.get("transcendentals")
        rec["bytes_accessed"] = cost.get("bytes_accessed")
        rec["capture"] = "deep" if deep else "cost"
        metrics.incr("profile.cost_captured")
    except Exception as e:
        rec["capture"] = "error"
        rec["error"] = f"{type(e).__name__}: {e}"[:160]
        metrics.incr("profile.capture_errors")
    finally:
        metrics.add_time("profile.capture_s", time.perf_counter() - t0)


def _apply_memory_analysis(rec: Dict[str, Any], compiled) -> None:
    try:
        ma = compiled.memory_analysis()
    except Exception:
        return
    if ma is None:
        return

    def grab(attr):
        v = getattr(ma, attr, None) if not isinstance(ma, dict) \
            else ma.get(attr)
        return int(v) if isinstance(v, (int, float)) else 0

    arg_b = grab("argument_size_in_bytes")
    out_b = grab("output_size_in_bytes")
    tmp_b = grab("temp_size_in_bytes")
    alias_b = grab("alias_size_in_bytes")
    if arg_b or out_b or tmp_b:
        rec["argument_bytes"] = arg_b
        rec["output_bytes"] = out_b
        rec["temp_bytes"] = tmp_b
        rec["peak_hbm_bytes"] = max(arg_b + out_b + tmp_b - alias_b, 0)
        rec["memory_source"] = "memory_analysis"


def resolve_pending() -> int:
    """Resolve every pending record (idempotent — each program lowers at
    most once per signature). Costs one ``lower()`` per unresolved record,
    charged to the reader, never to the execution path. No-op with
    profiling off (a readout must not trace while the operator has
    disabled the machinery)."""
    if not profiling_enabled():
        return 0
    with _reg_lock:
        todo = [r for r in _COSTS.values() if r["capture"] == "pending"]
        for r in todo:
            # claim under the lock: a concurrent reader (a /metrics scrape
            # racing a job_report) must not duplicate the lower() work
            r["capture"] = "resolving"
    deep = profiling_mode() == "deep"
    for rec in todo:
        _resolve_record(rec, deep=deep)
    return len(todo)


def clear_profile_registry() -> None:
    """Drop every cost record and reset the HBM watermark (tests)."""
    with _reg_lock:
        _COSTS.clear()
    with _hbm_lock:
        _hbm.update(peak_bytes=0, last_bytes=None, samples=0)


# ---------------------------------------------------------------------------
# Device peaks + roofline
# ---------------------------------------------------------------------------

# (substring of device_kind) -> (peak dense TFLOP/s at bf16, HBM GB/s).
# Public-datasheet ballpark figures — the ridge point they imply is what the
# classification needs, not the 4th digit. Override per deployment with
# ALINK_PEAK_TFLOPS / ALINK_PEAK_HBM_GBS.
_DEVICE_PEAKS: Tuple[Tuple[str, Tuple[float, float]], ...] = (
    ("v6", (918.0, 1640.0)),
    ("trillium", (918.0, 1640.0)),
    ("v5p", (459.0, 2765.0)),
    ("v5", (197.0, 819.0)),
    ("v4", (275.0, 1228.0)),
    ("v3", (123.0, 900.0)),
    ("v2", (45.0, 700.0)),
    # host CPU: one modern server socket's vector throughput + memory
    # bandwidth — keeps the roofline verdict meaningful in CPU containers
    ("cpu", (0.5, 51.2)),
)


def device_peaks() -> Dict[str, Any]:
    """Peak FLOP/s + HBM bandwidth for the local accelerator (table by
    ``device_kind`` substring, env overrides win) and the ridge point
    (FLOP/byte) that splits compute- from bandwidth-bound kernels. Never
    imports jax into a process that has not loaded it."""
    kind = "cpu"
    if "jax" in sys.modules:
        try:
            import jax

            kind = jax.devices()[0].device_kind
        except Exception:
            pass
    peak_t = hbm = None
    source = "unknown"
    for sub, (t, b) in _DEVICE_PEAKS:
        if sub in kind.lower():
            peak_t, hbm, source = t, b, "table"
            break
    env_t = env_float("ALINK_PEAK_TFLOPS", None)
    env_b = env_float("ALINK_PEAK_HBM_GBS", None)
    if env_t:
        peak_t, source = env_t, "env"
    if env_b:
        hbm, source = env_b, "env"
    peak_flops = peak_t * 1e12 if peak_t else None
    bw = hbm * 1e9 if hbm else None
    return {
        "device_kind": kind,
        "peak_flops_per_s": peak_flops,
        "hbm_bytes_per_s": bw,
        "ridge_flops_per_byte":
            round(peak_flops / bw, 3) if peak_flops and bw else None,
        "source": source,
    }


def roofline(flops: Optional[float], bytes_accessed: Optional[float],
             exec_mean_s: Optional[float] = None,
             peaks: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
    """Roofline verdict for one program: arithmetic intensity, the
    compute-/bandwidth-bound classification against the device ridge, the
    attainable ceiling at this intensity, and — when a measured exec time
    is available — achieved FLOP/s and efficiency vs that ceiling."""
    peaks = peaks or device_peaks()
    out: Dict[str, Any] = {
        "arithmetic_intensity": None,
        "bound": None,
        "ceiling_flops_per_s": None,
        "achieved_flops_per_s": None,
        "efficiency": None,
    }
    if flops and bytes_accessed:
        ai = flops / bytes_accessed
        out["arithmetic_intensity"] = round(ai, 4)
        ridge = peaks.get("ridge_flops_per_byte")
        if ridge:
            out["bound"] = ("compute-bound" if ai >= ridge
                            else "bandwidth-bound")
            out["ceiling_flops_per_s"] = round(
                min(peaks["peak_flops_per_s"],
                    ai * peaks["hbm_bytes_per_s"]), 1)
    if flops and exec_mean_s and exec_mean_s > 0:
        out["achieved_flops_per_s"] = round(flops / exec_mean_s, 1)
        if out["ceiling_flops_per_s"]:
            out["efficiency"] = round(
                out["achieved_flops_per_s"] / out["ceiling_flops_per_s"], 4)
    return out


# ---------------------------------------------------------------------------
# Device HBM watermark sampling
# ---------------------------------------------------------------------------

_hbm_lock = threading.Lock()
_hbm: Dict[str, Any] = {"available": None, "peak_bytes": 0,
                        "last_bytes": None, "samples": 0}


def sample_device_memory() -> Optional[int]:
    """Sample ``device.memory_stats()`` across local devices and update the
    process-wide HBM watermark. Returns total bytes in use, or None where
    the backend exposes no stats (CPU) — after the first empty probe the
    sampler latches unavailable and every later call is a cheap no-op."""
    if not profiling_enabled():
        return None
    with _hbm_lock:
        if _hbm["available"] is False:
            return None
    if "jax" not in sys.modules:
        return None
    in_use = peak = 0
    seen = False
    try:
        import jax

        for d in jax.local_devices():
            fn = getattr(d, "memory_stats", None)
            stats = fn() if fn is not None else None
            if not stats:
                continue
            seen = True
            cur = int(stats.get("bytes_in_use", 0))
            in_use += cur
            peak += int(stats.get("peak_bytes_in_use", cur))
    except Exception:
        # a TRANSIENT stats error (runtime hiccup on a live backend) must
        # not permanently latch sampling off — only a clean probe that
        # found no stats at all (CPU) does that
        metrics.incr("profile.hbm_sample_errors")
        return None
    with _hbm_lock:
        if not seen:
            _hbm["available"] = False
            return None
        _hbm["available"] = True
        _hbm["samples"] += 1
        _hbm["last_bytes"] = in_use
        _hbm["peak_bytes"] = max(_hbm["peak_bytes"], peak, in_use)
    return in_use


def hbm_watermark() -> Dict[str, Any]:
    with _hbm_lock:
        d = dict(_hbm)
    return {
        "available": bool(d["available"]),
        "peak_bytes": d["peak_bytes"] or None,
        "bytes_in_use": d["last_bytes"],
        "samples": d["samples"],
    }


# ---------------------------------------------------------------------------
# Readouts
# ---------------------------------------------------------------------------


def _export_record(rec: Dict[str, Any]) -> Dict[str, Any]:
    out = {k: v for k, v in rec.items() if not k.startswith("_")}
    calls = rec["calls"]
    out["exec_total_s"] = round(rec["exec_total_s"], 6)
    out["exec_mean_s"] = round(rec["exec_total_s"] / calls, 9) if calls else None
    if rec.get("flops") and out["exec_mean_s"]:
        out["achieved_flops_per_s"] = round(rec["flops"] / out["exec_mean_s"], 1)
    else:
        out["achieved_flops_per_s"] = None
    return out


def program_costs(kernel_id: Optional[str] = None, *,
                  resolve: bool = True) -> List[Dict[str, Any]]:
    """Cost records (one per program x shape signature), JSON-able. With
    ``resolve`` (default) pending records are materialized first."""
    if resolve:
        resolve_pending()
    with _reg_lock:
        recs = [dict(r) for r in _COSTS.values()]
    return [_export_record(r) for r in recs
            if kernel_id is None or r["kernel"] == kernel_id]


def costs_by_kernel(*, resolve: bool = True) -> Dict[str, Dict[str, Any]]:
    """Dominant (most-called) resolved record per kernel id, trimmed to the
    headline fields — the shape ``compile_summary()`` embeds."""
    out: Dict[str, Dict[str, Any]] = {}
    best_calls: Dict[str, int] = {}
    for r in program_costs(resolve=resolve):
        kid = r["kernel"]
        if r.get("flops") is None:
            continue
        if kid not in out or r["calls"] > best_calls[kid]:
            best_calls[kid] = r["calls"]
            out[kid] = {"flops": r["flops"],
                        "bytes_accessed": r["bytes_accessed"],
                        "peak_hbm_bytes": r["peak_hbm_bytes"],
                        "capture": r["capture"]}
    return out


def _kernel_rows(recs: List[Dict[str, Any]],
                 peaks: Dict[str, Any]) -> List[Dict[str, Any]]:
    """Per-kernel aggregation shared by ``profile_summary`` and
    ``kernel_candidates``: sums calls/wall over a kernel id's programs,
    picks the dominant (most-called) program's static costs, and attaches
    its roofline verdict. Sorted by total wall, busiest first."""
    by_kernel: Dict[str, Dict[str, Any]] = {}
    dominant: Dict[str, Dict[str, Any]] = {}
    for r in recs:
        kid = r["kernel"]
        agg = by_kernel.setdefault(kid, {"kernel": kid, "programs": 0,
                                         "calls": 0, "exec_total_s": 0.0})
        agg["programs"] += 1
        agg["calls"] += r["calls"]
        agg["exec_total_s"] += r["exec_total_s"]
        dom = dominant.get(kid)
        if dom is None or (r["calls"], r["exec_total_s"]) >= \
                (dom["calls"], dom["exec_total_s"]):
            dominant[kid] = r

    rows: List[Dict[str, Any]] = []
    for kid, agg in by_kernel.items():
        dom = _export_record(dominant[kid])
        row = {
            "kernel": kid,
            "programs": agg["programs"],
            "calls": agg["calls"],
            "exec_total_s": round(agg["exec_total_s"], 6),
            "signature": dom["signature"],
            "capture": dom["capture"],
            "flops": dom["flops"],
            "bytes_accessed": dom["bytes_accessed"],
            "argument_bytes": dom["argument_bytes"],
            "output_bytes": dom["output_bytes"],
            "temp_bytes": dom["temp_bytes"],
            "peak_hbm_bytes": dom["peak_hbm_bytes"],
            "memory_source": dom["memory_source"],
            "compile_s": dom["compile_s"],
            "persist": dom.get("persist"),
            "exec_mean_s": dom["exec_mean_s"],
            "achieved_flops_per_s": dom["achieved_flops_per_s"],
        }
        row["roofline"] = roofline(dom["flops"], dom["bytes_accessed"],
                                   dom["exec_mean_s"], peaks)
        rows.append(row)
    rows.sort(key=lambda r: -(r["exec_total_s"] or 0.0))
    return rows


def kernel_candidates(top: Optional[int] = None, *,
                      resolve: bool = True) -> List[Dict[str, Any]]:
    """The roofline worst-offenders table: which program to hand-fuse next.

    Joins each kernel id's measured warm wall time with its roofline
    verdict — ``lost_s = exec_total_s × (1 − efficiency)`` is the seconds
    the program left on the table against its attainable ceiling — and
    cross-references the custom-kernel registry (``native/kernels.py``) so
    every row answers "does this path already have a hand-written kernel,
    and is it switched on". Rows with a measurable efficiency rank first
    by lost seconds, worst offender on top; rows without one (no flops
    capture or no warm timing yet) follow, ordered by wall time.

    Surfaced by ``profile_summary()`` (hence ``job_report()`` and
    ``GET /api/profile``)."""
    from ..native.kernels import covering, kernel_enabled, kernel_spec

    if resolve and profiling_enabled():
        resolve_pending()
    peaks = device_peaks()
    with _reg_lock:
        recs = [dict(r) for r in _COSTS.values()]
    out: List[Dict[str, Any]] = []
    for row in _kernel_rows(recs, peaks):
        eff = row["roofline"].get("efficiency")
        lost = None
        if eff is not None:
            lost = round(
                (row["exec_total_s"] or 0.0) * max(0.0, 1.0 - min(eff, 1.0)),
                6)
        covered = covering(row["kernel"])
        spec = kernel_spec(covered) if covered else None
        out.append({
            "kernel": row["kernel"],
            "programs": row["programs"],
            "calls": row["calls"],
            "exec_total_s": row["exec_total_s"],
            "exec_mean_s": row["exec_mean_s"],
            "bound": row["roofline"].get("bound"),
            "efficiency": eff,
            "lost_s": lost,
            "custom_kernel": covered,
            "knob": spec["knob"] if spec else None,
            "kernel_enabled": kernel_enabled(spec["knob"]) if spec else None,
        })
    out.sort(key=lambda r: (0, -r["lost_s"]) if r["lost_s"] is not None
             else (1, -(r["exec_total_s"] or 0.0)))
    if top is not None:
        out = out[:top]
    return out


def profile_summary(top: Optional[int] = None, *,
                    resolve: bool = True) -> Dict[str, Any]:
    """The one-call performance-observatory readout: device peaks + ridge,
    HBM watermark, a per-kernel table joining static XLA cost with
    measured exec timings into roofline verdicts, and the ranked
    ``candidates`` worst-offenders table. Feeds ``job_report()``,
    ``GET /api/profile`` and the ``alink_profile_*`` Prometheus gauges."""
    if resolve and profiling_enabled():
        resolve_pending()
        sample_device_memory()
    peaks = device_peaks()
    with _reg_lock:
        recs = [dict(r) for r in _COSTS.values()]
    pending = sum(1 for r in recs if r["capture"] == "pending")
    rows = _kernel_rows(recs, peaks)
    if top is not None:
        rows = rows[:top]
    return {
        "enabled": profiling_enabled(),
        "mode": profiling_mode(),
        "device": peaks,
        "hbm": hbm_watermark(),
        "kernels": rows,
        "candidates": kernel_candidates(top=top, resolve=False),
        "registry": {"records": len(recs), "pending": pending},
        "counters": metrics.counters("profile."),
    }


# ---------------------------------------------------------------------------
# Prometheus surface — alink_profile_* gauge families on the global recorder
# ---------------------------------------------------------------------------


def _export_gauges() -> None:
    if not profiling_enabled():
        return
    with _reg_lock:
        empty = not _COSTS
    if empty:
        return
    summ = profile_summary(top=64)
    for row in summ["kernels"]:
        kid = row["kernel"]
        for field, gname in (
                ("flops", "profile.flops"),
                ("bytes_accessed", "profile.bytes_accessed"),
                ("peak_hbm_bytes", "profile.peak_hbm_bytes"),
                ("achieved_flops_per_s", "profile.achieved_flops_per_s")):
            v = row.get(field)
            if v is not None:
                metrics.set_gauge(gname, v, kernel=kid)
        ai = row["roofline"].get("arithmetic_intensity")
        if ai is not None:
            metrics.set_gauge("profile.arithmetic_intensity", ai, kernel=kid)
    hbm = summ["hbm"]
    if hbm.get("peak_bytes"):
        metrics.set_gauge("profile.device_hbm_peak_bytes", hbm["peak_bytes"])


metrics.register_export_hook(_export_gauges)
