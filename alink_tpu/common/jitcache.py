"""Shape-stable execution: process-wide program cache, shape bucketing, AOT warmup.

The platform's dominant cost on short jobs is not compute but compilation
(PERF.md: ``setup_s`` on a checkout's first run). Three mechanisms cut the
compile tax to a once-per-process (or, with the persistent XLA cache,
once-per-machine) event:

1. **ProgramCache** — jitted kernels are registered once under a key of
   (kernel id, static config, mesh fingerprint, wire-precision policy) via
   :func:`cached_jit`. Call sites that used to rebuild ``jax.jit(...)``
   closures per fit/predict (discarding jax's own trace cache each time)
   now fetch one long-lived program and let jax's dispatch cache do its
   job. Loading N copies of the same model compiles once, not N times.

2. **Shape bucketing** — the leading (row) dimension is padded up a bucket
   ladder (:func:`bucket_rows`, env ``ALINK_SHAPE_BUCKETS``) so a
   batch-size sweep or a ragged final stream chunk hits one compiled
   program instead of lowering a fresh program per distinct row count.
   Bucketing is applied ONLY on row-wise kernels (each output row depends
   only on its input row), where zero-padding plus slicing the outputs back
   to the true row count is bit-identical to the unpadded run — no
   cross-row reduction ever sees the padded tail.

3. **AOT warmup** — :func:`warmup` compiles registered kernels for given
   (or profiled, env ``ALINK_SHAPE_PROFILE``) shape signatures ahead of
   time on a background thread, off the serving critical path.

4. **Persistent compile artifacts** — :func:`enable_persistent_cache`
   wires jax's persistent compilation cache under the ProgramCache (at
   ``JAX_COMPILATION_CACHE_DIR`` when the caller set it, else at a fixed
   directory inside the checkout) so executables survive process death: a
   fresh process pays trace + deserialize (``jit.persist_hit``) instead of
   a backend compile, corrupt entries fall back to a fresh compile
   (``jit.persist_error``), and the on-disk footprint is LRU-bounded
   (``ALINK_COMPILE_CACHE_MAX_BYTES``). Paired with
   :func:`save_warmup_specs` / ``warmup(path)``, a replica that has never
   compiled reaches warm-path readiness from disk alone — see
   docs/coldstart.md.

Observability: every first call of a program with a new shape signature is
counted (``jit.trace`` / ``jit.compile``) and timed (global and per-kernel
``jitcache.*.compile_s`` timers, plus a ``compile_s`` phase on the active
executor node trace). :func:`compile_summary` aggregates the lot
(``job_report()`` carries it).

Buffer donation: builders may return programs built with
``jax.jit(..., donate_argnums=...)`` (the DL train/MLM steps do — params
and optimizer state update in place on device). The cache is donation-safe
by construction: the shape signature is computed BEFORE dispatch, the
profiling hooks only ever read leaf metadata (shape/dtype/tree structure,
never buffer contents) from arguments that the call may have consumed, and
:meth:`CachedProgram.ensure_compiled` warms on fresh host zeros that were
never committed device buffers. Callers keep the usual donation contract:
rebind to the returned state and never re-use a donated tree.
"""

from __future__ import annotations

import functools
import itertools
import json
import os
import sys
import threading
import time
from collections import OrderedDict
from typing import Any, Callable, Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

from . import profiling as _profiling
from .env import env_int, env_raw, env_str
from .metrics import add_node_phase, metrics

# ---------------------------------------------------------------------------
# Key construction
# ---------------------------------------------------------------------------

_token_counter = itertools.count(1)


def instance_token(obj) -> int:
    """Unique, GC-safe token for a Python object's lifetime. Used as the
    cache-key component for kernels whose behavior is determined by mutable
    instance state that cannot be content-hashed (model arrays): the same
    instance reuses its program; a new instance gets a fresh entry (unlike
    ``id()``, tokens are never recycled)."""
    tok = getattr(obj, "_jitcache_token", None)
    if tok is None:
        tok = next(_token_counter)
        try:
            obj._jitcache_token = tok
        except AttributeError:  # __slots__ objects: fall back to identity-free
            return tok          # one-shot token (no reuse, still correct)
    return tok


class Unkeyable(TypeError):
    """Raised by :func:`fn_content_key` when a closure captures values that
    cannot be content-hashed (device arrays, open handles). Callers fall
    back to :func:`instance_token` or skip caching."""


def _freeze(v) -> Any:
    """Hashable, content-faithful key component for a config value."""
    import types

    if v is None or isinstance(v, (bool, int, float, str, bytes, type,
                                   types.CodeType)):
        return v
    if isinstance(v, np.generic):
        # numpy scalars (np.float32 etc.) do not subclass Python scalars;
        # without this they would demote the caller to the Unkeyable
        # fallback — a silent per-call rebuild of the whole program
        return ("nps", v.dtype.str, v.item())
    if isinstance(v, np.ndarray):
        a = np.ascontiguousarray(v)
        import hashlib

        h = hashlib.blake2b(digest_size=12)
        h.update(a.view(np.uint8).reshape(-1).data if a.dtype != object
                 else repr(a.tolist()).encode())
        return ("nd", a.shape, a.dtype.str, h.hexdigest())
    if isinstance(v, (tuple, list)):
        return ("seq", tuple(_freeze(x) for x in v))
    if isinstance(v, dict):
        return ("map", tuple(sorted((k, _freeze(x)) for k, x in v.items())))
    if isinstance(v, (frozenset, set)):
        return ("set", tuple(sorted(map(repr, v))))
    if callable(v):
        return fn_content_key(v)
    raise Unkeyable(f"cannot build a cache key from {type(v).__name__}")


def fn_content_key(fn) -> Tuple:
    """Content key for a plain function or closure: code object + defaults +
    captured cell values. Two closures built from the same source with the
    same captured config hash equal — the mechanism that lets per-call
    rebuilt kernels (objective closures, mapper block kernels) share one
    compiled program. Raises :class:`Unkeyable` when a cell holds something
    that cannot be content-hashed."""
    if fn is None:
        return ("fn", None)
    if hasattr(fn, "__wrapped__"):
        fn = fn.__wrapped__
    code = getattr(fn, "__code__", None)
    if code is None:
        # bound method / callable object: key on the class + instance token
        f = getattr(fn, "__func__", None)
        if f is not None:
            return ("bound", fn_content_key(f),
                    instance_token(fn.__self__))
        raise Unkeyable(f"cannot key callable {fn!r}")
    cells: Tuple = ()
    if fn.__closure__:
        vals = []
        for cell in fn.__closure__:
            try:
                vals.append(_freeze(cell.cell_contents))
            except (Unkeyable, ValueError) as e:
                raise Unkeyable(str(e))
        cells = tuple(vals)
    defaults = tuple(_freeze(d) for d in (fn.__defaults__ or ()))
    return ("fn", fn.__qualname__, code, defaults, cells)


# ---------------------------------------------------------------------------
# Mesh fingerprinting (shared registry — one representative mesh per
# structural fingerprint, so equivalent meshes share compiled programs)
# ---------------------------------------------------------------------------

_mesh_lock = threading.Lock()
_MESHES: Dict[tuple, Any] = {}


def mesh_fingerprint(mesh) -> Optional[tuple]:
    """Structural mesh key (axis names, shape, device ids). Registers the
    mesh as the representative for its fingerprint; compiled kernels close
    over the representative, so fresh-mesh-per-job services do not grow the
    program cache unboundedly."""
    if mesh is None:
        return None
    k = (
        tuple(mesh.axis_names),
        tuple(int(s) for s in mesh.devices.shape),
        tuple(getattr(d, "id", i) for i, d in enumerate(mesh.devices.flat)),
    )
    with _mesh_lock:
        _MESHES.setdefault(k, mesh)
    return k


def mesh_for(fingerprint: tuple):
    with _mesh_lock:
        return _MESHES[fingerprint]


# ---------------------------------------------------------------------------
# Shape bucketing
# ---------------------------------------------------------------------------

_BUCKETS_ENV = "ALINK_SHAPE_BUCKETS"
_LINEAR_HEAD = 64       # below this, buckets are multiples of _LINEAR_STEP
_LINEAR_STEP = 8


def _parse_buckets() -> "str | List[int]":
    raw = (env_str(_BUCKETS_ENV, "") or "").strip().lower()
    if raw in ("", "pow2"):
        return "pow2"
    if raw in ("off", "0", "none"):
        return "off"
    try:
        ladder = sorted({int(x) for x in raw.split(",") if x.strip()})
        if ladder and all(s > 0 for s in ladder):
            return ladder
    except ValueError:
        pass
    return "pow2"  # malformed knob must not crash a running job


def bucket_rows(n: int) -> int:
    """Bucketed row count for ``n``: the padded leading dimension every
    kernel compiled through the bucketing helpers sees.

    Default ladder ("pow2 with a linear head"): multiples of 8 up to 64,
    then the next power of two — a batch-size sweep from 1..10k compiles
    ~16 programs instead of one per distinct size. ``ALINK_SHAPE_BUCKETS``
    overrides: ``off`` disables bucketing, or a comma list (``64,512,4096``)
    gives an explicit ladder (sizes beyond the last round up to a multiple
    of the last rung)."""
    n = int(n)
    spec = _parse_buckets()
    if spec == "off" or n < 0:
        return n
    if isinstance(spec, list):
        for s in spec:
            if n <= s:
                return s
        last = spec[-1]
        return ((n + last - 1) // last) * last
    # pow2 with linear head
    if n <= _LINEAR_HEAD:
        return max(_LINEAR_STEP,
                   ((n + _LINEAR_STEP - 1) // _LINEAR_STEP) * _LINEAR_STEP)
    return 1 << (n - 1).bit_length()


def bucketing_enabled() -> bool:
    return _parse_buckets() != "off"


def floor_bucket_rows(n: int) -> int:
    """Largest ladder rung <= ``n`` (``n`` itself when bucketing is off or
    ``n`` sits below the smallest rung). Streaming paths size their full
    micro-batches with this so steady chunks ship with ZERO padding and only
    the ragged tail pads up to a (smaller) bucket."""
    n = int(n)
    spec = _parse_buckets()
    if spec == "off" or n <= 0:
        return n
    if isinstance(spec, list):
        best = None
        for s in spec:
            if s <= n:
                best = s
        return best if best is not None else n
    if n < _LINEAR_STEP:
        return n
    if n <= _LINEAR_HEAD:
        return (n // _LINEAR_STEP) * _LINEAR_STEP
    return 1 << (n.bit_length() - 1)


def pad_rows(arr: np.ndarray, target: int) -> np.ndarray:
    """Zero-pad ``arr`` along dim0 to ``target`` rows (no-op if already
    there). Zeros are the bit-parity-safe filler for row-wise kernels: the
    padded rows produce garbage rows that the caller slices off; real rows
    are untouched."""
    n = arr.shape[0]
    if target == n:
        return arr
    pad_width = [(0, target - n)] + [(0, 0)] * (arr.ndim - 1)
    return np.pad(arr, pad_width)


def device_constants(*arrays):
    """``jax.device_put`` model parameters once at load time. Mappers pass
    these as program ARGUMENTS (so models share one compiled program), but a
    host numpy argument would re-cross the wire on every predict call —
    staging them once keeps the per-call cost at zero, like the baked-in
    constants they replaced."""
    import jax

    return tuple(jax.device_put(np.asarray(a)) for a in arrays)


def call_row_bucketed(prog: Callable, row_args: Sequence[np.ndarray],
                      const_args: Sequence[Any] = ()):
    """Run a ROW-WISE program over bucket-padded inputs and slice every
    output back to the true row count.

    Contract: every ``row_args`` array is row-aligned on dim0 and every
    output of ``prog`` is row-aligned on dim0 (no cross-row reductions).
    Under that contract the result is bit-identical to the unpadded call —
    each output row is a function of its input row alone. ``const_args``
    pass through unpadded (weights, centroids)."""
    n = int(row_args[0].shape[0])
    m = bucket_rows(n)
    if m != n:
        row_args = [pad_rows(np.asarray(a), m) for a in row_args]
    out = prog(*row_args, *const_args)
    if m == n:
        return out

    def trim(x):
        return x[:n] if getattr(x, "ndim", 0) >= 1 and x.shape[0] == m else x

    if isinstance(out, tuple):
        return tuple(trim(o) for o in out)
    if isinstance(out, list):
        return [trim(o) for o in out]
    return trim(out)


# ---------------------------------------------------------------------------
# Shape signatures + profile recording
# ---------------------------------------------------------------------------

def _leaf_sig(x) -> tuple:
    shape = getattr(x, "shape", None)
    dtype = getattr(x, "dtype", None)
    if shape is not None and dtype is not None:
        return ("a", tuple(int(s) for s in shape), np.dtype(dtype).str)
    try:
        hash(x)
        return ("s", type(x).__name__, x)
    except TypeError:
        return ("s", type(x).__name__, repr(x))


def args_signature(args: Sequence[Any]) -> tuple:
    import jax

    return tuple(_leaf_sig(leaf) for leaf in jax.tree_util.tree_leaves(args))


_profile_lock = threading.Lock()


def _record_profile(kernel_id: str, sig: tuple) -> None:
    path = env_str("ALINK_SHAPE_PROFILE")
    if not path:
        return
    arrs = [[list(s[1]), s[2]] for s in sig if s[0] == "a"]
    try:
        with _profile_lock, open(path, "a") as f:
            f.write(json.dumps({"kernel": kernel_id, "args": arrs}) + "\n")
    except OSError:
        metrics.incr("jit.profile_write_errors")


def load_shape_profile(path: Optional[str] = None) -> List[Tuple[str, list]]:
    """Parse an ``ALINK_SHAPE_PROFILE`` jsonl into warmup specs
    ``[(kernel_id, [(shape, dtype), ...]), ...]`` (deduplicated, order
    preserved; malformed lines skipped)."""
    path = path or env_str("ALINK_SHAPE_PROFILE")
    specs: List[Tuple[str, list]] = []
    seen = set()
    if not path or not os.path.exists(path):
        return specs
    with open(path) as f:
        for line in f:
            try:
                rec = json.loads(line)
                args = [(tuple(s), d) for s, d in rec["args"]]
                key = (rec["kernel"], tuple(args))
            except (ValueError, KeyError, TypeError):
                continue
            if key not in seen:
                seen.add(key)
                specs.append((rec["kernel"], args))
    return specs


# ---------------------------------------------------------------------------
# Persistent compile artifacts (cross-process)
# ---------------------------------------------------------------------------
# This module is the ONE sanctioned owner of jax's persistent compilation
# cache configuration (alink-lint ALK006 bans jax_compilation_cache_* config
# writes and raw compilation_cache imports anywhere else). Everything below
# only changes WHERE compiled executables come from — never what they
# compute: a persist hit deserializes the exact executable a previous
# process compiled for the same HLO + compile options, and every failure
# (corrupt entry, unwritable dir, version skew) falls back to a fresh
# backend compile.

_JAX_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"
_PERSIST_CAP_ENV = "ALINK_COMPILE_CACHE_MAX_BYTES"
_DEFAULT_PERSIST_CAP = 2 * 1024 ** 3   # on-disk LRU bound (2 GiB)
# env defaults a pre-jax enable writes so that every compile persists, small
# per-op programs included (jax's own floor is 1 s of compile time)
_JAX_TUNING_ENV = {"JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0.0",
                   "JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES": "-1"}

_persist_lock = threading.Lock()
_persist: Dict[str, Any] = {"enabled": False, "dir": None, "hooked": False,
                            "configured": False, "wrote_env": set()}


def persist_cap_bytes() -> int:
    """On-disk size bound for the persistent cache (env
    ``ALINK_COMPILE_CACHE_MAX_BYTES``, 0 = unbounded), applied by
    :func:`prune_persistent_cache` each time a process enables the cache."""
    return env_int(_PERSIST_CAP_ENV, _DEFAULT_PERSIST_CAP)


def compile_cache_dir() -> Optional[str]:
    """The active persistent-cache directory, or None when persistence is
    off."""
    with _persist_lock:
        return _persist["dir"] if _persist["enabled"] else None


def default_cache_dir() -> str:
    """The in-checkout cache directory: ``<checkout>/.jax_cache``, derived
    from this package's own location so that every process of a checkout —
    fleet workers, a second run of the same command — resolves the same
    path (the path is part of what makes a cache reusable; a temp name, pid
    or time would never hit). Git-ignored."""
    pkg = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return os.path.join(os.path.dirname(pkg), ".jax_cache")


def _resolve_persist_dir(cache_dir: Optional[str]) -> Optional[str]:
    """Where the cache lives, or None for off. The cache is placed from
    outside: ``JAX_COMPILATION_CACHE_DIR``, when set, IS the cache and this
    module configures no other directory (an explicit ``cache_dir`` that
    disagrees is an error, not an override). Unset: the explicit argument
    (tests and drills), else :func:`default_cache_dir` — except under
    ``JAX_PLATFORMS=cpu``, where persistence stays off by default (XLA:CPU
    AOT entries are machine-feature-pinned, and the CPU test suite counts
    traces and compiles)."""
    placed = env_str(_JAX_DIR_ENV)
    if placed is not None:
        placed = placed.strip()
        if cache_dir and os.path.abspath(cache_dir) != os.path.abspath(placed):
            raise ValueError(
                f"{_JAX_DIR_ENV}={placed!r} places the compile cache; "
                f"refusing to configure a second one at {cache_dir!r}")
        return placed
    if cache_dir is not None:
        return cache_dir or None
    if (env_str("JAX_PLATFORMS", "") or "").strip() == "cpu":
        return None
    return default_cache_dir()


def _counted_cache_io(fn):
    """Wrap one jax compilation-cache IO entry point so every read/write
    failure is counted as ``jit.persist_error`` before jax's own fallback
    (warn + fresh compile) takes over. Behavior-preserving: the exception
    re-raises unchanged."""
    @functools.wraps(fn)
    def wrapper(*args, **kwargs):
        try:
            return fn(*args, **kwargs)
        except Exception:
            metrics.incr("jit.persist_error")
            raise
    wrapper._alink_counted = True  # type: ignore[attr-defined]
    return wrapper


def _install_persist_hooks() -> bool:
    """Counter plumbing: ``jit.persist_hit`` / ``jit.persist_miss`` /
    ``jit.persist_saved_s`` / ``jit.persist_load_s`` from jax's monitoring
    events,
    ``jit.persist_error`` from wrapped cache IO (jax 0.9 internals).
    Returns True; callers record that under ``_persist_lock``."""
    if _persist["hooked"]:
        return True
    from jax._src import compilation_cache as _cc
    from jax._src import monitoring

    def _on_event(event: str, **kwargs) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            metrics.incr("jit.persist_hit")
        elif event == "/jax/compilation_cache/cache_misses":
            metrics.incr("jit.persist_miss")

    def _on_duration(event: str, duration: float, **kwargs) -> None:
        # backend-compile seconds each persist hit skipped (jax stores
        # whole seconds, so sub-second CPU compiles read 0)
        if event == "/jax/compilation_cache/compile_time_saved_sec":
            metrics.add_time("jit.persist_saved_s", max(float(duration), 0.0))
        elif event == "/jax/compilation_cache/cache_retrieval_time_sec":
            # what one persist hit spent reading and deserialising its
            # cached executable (jax 0.9.0, _src/compiler.py)
            metrics.observe("jit.persist_load_s", max(float(duration), 0.0))

    monitoring.register_event_listener(_on_event)
    monitoring.register_event_duration_secs_listener(_on_duration)
    for name in ("get_executable_and_time", "put_executable_and_time"):
        fn = getattr(_cc, name)
        if not getattr(fn, "_alink_counted", False):
            setattr(_cc, name, _counted_cache_io(fn))
    return True


def _apply_jax_persist_config(d: str) -> None:
    """Point the imported jax at ``d`` (a no-op when jax already read that
    directory from ``JAX_COMPILATION_CACHE_DIR`` at import) and make every
    compile persist."""
    import jax
    from jax._src import compilation_cache as _cc

    if getattr(jax.config, "jax_compilation_cache_dir", None) != d:
        jax.config.update("jax_compilation_cache_dir", d)
        # jax latches its cache-used decision on the first compile of the
        # task; a process that already compiled before this enable (tests,
        # late re-points) must re-evaluate or the new dir is ignored
        _cc.reset_cache()
    # cache everything: the default 1s floor skips exactly the small
    # per-op programs this framework compiles most often. A user-exported
    # JAX_PERSISTENT_CACHE_* knob wins (jax consumed it at import); the
    # ones this module exported itself hold these same values.
    ours = _persist["wrote_env"]
    for name, value in (
            ("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", 0.0),
            ("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", -1)):
        if name in ours or env_raw(name) is None:
            jax.config.update(name.lower(), value)
    # jax's own eviction (jax_compilation_cache_max_size) stays off: once on,
    # every write scans the directory for each entry's "-atime" companion
    # and fails on the first entry written without one — by a process that
    # compiled before this config landed, or by any other jax that shares a
    # directory placed from outside (seen on the chip: every put of a
    # process raised FileNotFoundError). prune_persistent_cache() bounds the
    # directory at enable time and copes with both kinds of entry.


def enable_persistent_cache(cache_dir: Optional[str] = None) -> Optional[str]:
    """Wire jax's persistent compilation cache underneath the ProgramCache
    so compiled programs survive process death: a fresh process pays trace +
    deserialize instead of trace + backend-compile.

    Called at package import. The directory is resolved by
    :func:`_resolve_persist_dir`: ``JAX_COMPILATION_CACHE_DIR`` if the
    caller set it (then nothing else is ever configured), else the explicit
    ``cache_dir``, else the in-checkout default (off under
    ``JAX_PLATFORMS=cpu``). When jax is not imported yet this only exports
    the directory and two tuning defaults as the ``JAX_*`` env vars jax
    reads at import — which child processes inherit, so workers share the
    cache — and ``import alink_tpu`` stays jax-free; the config + counter
    hooks are finalized lazily on the first ``cached_jit`` miss. Returns
    the active dir, or None when persistence stays off — in which case
    process behavior is byte-for-byte unchanged."""
    d = _resolve_persist_dir(cache_dir)
    if d is None:
        return None
    try:
        os.makedirs(d, exist_ok=True)
    except OSError:  # read-only checkout: run uncached rather than not at all
        metrics.incr("jit.persist_hook_errors")
        return None
    with _persist_lock:
        for name, value in {_JAX_DIR_ENV: d, **_JAX_TUNING_ENV}.items():
            if env_raw(name) is None:
                # recorded so disable can take back exactly what it wrote
                _persist["wrote_env"].add(name)
                os.environ[name] = value
        if "jax" in sys.modules:
            _apply_jax_persist_config(d)
            _persist["hooked"] = _install_persist_hooks()
            _persist["configured"] = True
        else:
            _persist["configured"] = False
        _persist["enabled"] = True
        _persist["dir"] = d
    prune_persistent_cache()
    return d


def disable_persistent_cache() -> None:
    """Turn persistence back off (tests, operators draining a bad disk).
    In-flight executables are unaffected; the next compile goes straight to
    the backend. Env vars an enable wrote are removed again (``JAX_*``
    values the caller exported stay untouched) — otherwise a jax that
    initializes later, or a child process, would read our leftovers and
    silently re-activate the cache this call turned off."""
    with _persist_lock:
        wrote = _persist["wrote_env"]
        _persist.update(enabled=False, dir=None, configured=False,
                        wrote_env=set())
    for name in wrote:
        os.environ.pop(name, None)
    if "jax" in sys.modules:
        import jax
        from jax._src import compilation_cache as _cc

        jax.config.update("jax_compilation_cache_dir", None)
        _cc.reset_cache()


def _ensure_persist_ready() -> None:
    """Finalize the jax-side config + counter hooks on the first
    ``cached_jit`` miss (cheap dict reads once done). Imports jax if the
    enable ran before jax did — the miss path's builder is about to anyway,
    and the config must land BEFORE that first compile so the very first
    program already persists and counts."""
    if not _persist["enabled"] or _persist["configured"]:
        return
    with _persist_lock:
        if _persist["configured"] or not _persist["enabled"]:
            return
        _apply_jax_persist_config(_persist["dir"])
        _persist["hooked"] = _install_persist_hooks()
        _persist["configured"] = True


def _persist_entries(d: str) -> List[Tuple[str, float, int]]:
    """(path, last-use stamp, bytes) per on-disk cache entry. jax's LRUCache
    layout keeps a sibling ``<key>-atime`` file as the last-use marker; its
    mtime (falling back to the entry's own mtime) orders eviction."""
    entries: List[Tuple[str, float, int]] = []
    try:
        names = os.listdir(d)
    except OSError:
        return entries
    for name in names:
        if not name.endswith("-cache"):
            continue
        path = os.path.join(d, name)
        try:
            size = os.path.getsize(path)
            stamp_path = path[:-len("-cache")] + "-atime"
            try:
                stamp = os.path.getmtime(stamp_path)
            except OSError:
                stamp = os.path.getmtime(path)
            entries.append((path, stamp, size))
        except OSError:
            continue
    return entries


def prune_persistent_cache(cache_dir: Optional[str] = None,
                           max_bytes: Optional[int] = None) -> Dict[str, int]:
    """LRU-prune the on-disk cache to ``max_bytes`` (default: the configured
    cap): least-recently-used entries (and their ``-atime`` companions)
    delete first until the directory fits. Safe to run concurrently with
    live processes — a reader that loses an entry re-compiles and re-writes
    it. Returns ``{"entries", "bytes", "removed", "removed_bytes"}``."""
    d = cache_dir or compile_cache_dir()
    cap = persist_cap_bytes() if max_bytes is None else max_bytes
    if not d:
        return {"entries": 0, "bytes": 0, "removed": 0, "removed_bytes": 0}
    entries = _persist_entries(d)
    total = sum(e[2] for e in entries)
    removed = removed_bytes = 0
    if cap > 0 and total > cap:
        for path, _, size in sorted(entries, key=lambda e: e[1]):
            if total <= cap:
                break
            try:
                os.remove(path)
                try:
                    os.remove(path[:-len("-cache")] + "-atime")
                except OSError:
                    pass
            except OSError:
                continue
            total -= size
            removed += 1
            removed_bytes += size
            metrics.incr("jit.persist_evict")
    return {"entries": len(entries) - removed, "bytes": total,
            "removed": removed, "removed_bytes": removed_bytes}


def persist_summary() -> Dict[str, Any]:
    """One-call persistence readout: knob state, on-disk entry count/bytes
    vs the cap, and the ``jit.persist_*`` counters. Embedded in
    :func:`compile_summary` and exported as gauges at ``/metrics``."""
    d = compile_cache_dir()
    out: Dict[str, Any] = {
        "enabled": d is not None,
        "dir": d,
        "max_bytes": persist_cap_bytes(),
        "entries": 0,
        "bytes": 0,
        "counters": metrics.counters("jit.persist"),
    }
    if d:
        entries = _persist_entries(d)
        out["entries"] = len(entries)
        out["bytes"] = sum(e[2] for e in entries)
    saved = metrics.timer_stats("jit.persist_saved_s")
    if saved:
        out["compile_s_saved"] = saved.get("total_s")
    return out


_GAUGE_TTL_S = 60.0
_gauge_stamp: Dict[str, float] = {"t": 0.0}


def _export_persist_gauges() -> None:
    # runs on every /metrics scrape: refresh the on-disk readout (a full
    # directory stat walk) at most once per TTL so a 10s Prometheus scrape
    # interval never turns into thousands of stat() calls per scrape on a
    # network-filesystem cache dir
    if not _persist["enabled"]:
        return
    now = time.monotonic()
    with _persist_lock:
        if now - _gauge_stamp["t"] < _GAUGE_TTL_S:
            return
        _gauge_stamp["t"] = now
    s = persist_summary()
    metrics.set_gauge("jit.persist_cache_entries", s["entries"])
    metrics.set_gauge("jit.persist_cache_bytes", s["bytes"])


metrics.register_export_hook(_export_persist_gauges)


# ---------------------------------------------------------------------------
# The program cache
# ---------------------------------------------------------------------------

class CachedProgram:
    """One long-lived jitted program plus per-shape-signature accounting.

    ``__call__`` delegates to the underlying jitted function; the first call
    with a new signature is counted as a trace+compile event and timed (the
    timing includes the first execution — on a warm persistent XLA cache
    that is dominated by trace + cache load, cold by the backend compile)."""

    __slots__ = ("kernel_id", "key", "jit_fn", "_sigs", "_lock")

    def __init__(self, kernel_id: str, key: tuple, jit_fn: Callable):
        self.kernel_id = kernel_id
        self.key = key
        self.jit_fn = jit_fn
        self._sigs: set = set()
        self._lock = threading.Lock()

    def seen_signatures(self) -> int:
        with self._lock:
            return len(self._sigs)

    def _note_sig(self, sig: tuple) -> bool:
        with self._lock:
            if sig in self._sigs:
                return False
            self._sigs.add(sig)
            return True

    def __call__(self, *args):
        sig = args_signature(args)
        if not self._note_sig(sig):
            metrics.incr("jit.program_calls")
            if not _profiling.profiling_enabled():
                return self.jit_fn(*args)
            # warm-call exec accounting: joins the static XLA cost captured
            # at trace time into achieved-FLOP/s / roofline readouts
            t0 = time.perf_counter()
            out = self.jit_fn(*args)
            if _profiling.sync_enabled():
                import jax

                jax.block_until_ready(out)
            _profiling.note_exec(self, sig, time.perf_counter() - t0,
                                 args, out)
            return out
        metrics.incr("jit.trace")
        metrics.incr("jit.compile")
        _record_profile(self.kernel_id, sig)
        # persist attribution: a jump in the process-wide persist-hit
        # counter across this compile window means the executable came off
        # disk, not from the backend compiler (best-effort under concurrent
        # compiles — cost records stay correct either way, only the
        # hit/compile label could cross-attribute)
        ph0 = metrics.counter("jit.persist_hit") if _persist["enabled"] \
            else None
        t0 = time.perf_counter()
        try:
            out = self.jit_fn(*args)
        finally:
            dt = time.perf_counter() - t0
            metrics.add_time("jitcache.compile_s", dt)
            metrics.add_time(f"jitcache.{self.kernel_id}.compile_s", dt)
            metrics.observe("jit.compile_s", dt)
            metrics.record_bounded("jit.compile_event", 512,
                                   kernel=self.kernel_id,
                                   ms=round(dt * 1e3, 3))
            add_node_phase("compile_s", dt)
        persist = None if ph0 is None else \
            ("hit" if metrics.counter("jit.persist_hit") > ph0 else "compile")
        _profiling.note_compiled(self, sig, args, out, dt, persist=persist)
        return out

    def lower(self, *args):
        return self.jit_fn.lower(*args)

    def ensure_compiled(self, arg_sigs: Iterable[Tuple[tuple, str]]) -> bool:
        """AOT-warm this program for array arguments of the given
        (shape, dtype) list by executing it once on zeros — this populates
        jax's real dispatch cache (an ``.lower().compile()`` would not), so
        the first production call performs zero new traces. Returns True if
        a compile happened, False if the signature was already warm."""
        zeros = [np.zeros(s, np.dtype(d)) for s, d in arg_sigs]
        sig = args_signature(zeros)
        with self._lock:
            if sig in self._sigs:
                return False
        metrics.incr("jit.warmup_compile")
        self(*zeros)
        return True


_lock = threading.RLock()
_PROGRAMS: "OrderedDict[tuple, CachedProgram]" = OrderedDict()
_DEFAULT_MAX_PROGRAMS = 256


def _max_programs() -> int:
    """LRU bound on cached programs (env ``ALINK_PROGRAM_CACHE_SIZE``, 0 =
    unbounded). The cache replaced per-call throwaway jit closures and
    size-bounded lru_caches; without a bound a long-running tuning sweep
    (one optimizer entry per hyper-parameter combination) would pin every
    compiled executable for process lifetime."""
    return env_int("ALINK_PROGRAM_CACHE_SIZE", _DEFAULT_MAX_PROGRAMS)


def _policy_component() -> str:
    # the wire-precision policy decides the dtype staged inputs arrive in;
    # keyed so a mid-process policy flip cannot alias programs traced for a
    # different input dtype contract (the raw policy string — not the probed
    # auto-slow/fast answer — is enough: auto's downcast is restored to the
    # caller dtype before any kernel sees it)
    try:
        from .staging import wire_precision

        return wire_precision()
    except Exception:
        return "auto"


def cached_jit(kernel_id: str, builder: Callable, *static,
               mesh=None, key_extra: Any = None) -> CachedProgram:
    """Fetch-or-build the process-wide program for ``kernel_id`` + config.

    ``builder(*static)`` (or ``builder(mesh, *static)`` when a mesh is
    given) must return the ready-to-call jitted function; it runs only on a
    cache miss. ``static`` values and ``key_extra`` are content-frozen into
    the key (np arrays by digest, closures by code + captured values).
    Raises :class:`Unkeyable` if a component cannot be frozen — callers that
    can tolerate a per-call rebuild should catch it and fall back."""
    key = (kernel_id, tuple(_freeze(s) for s in static),
           _freeze(key_extra), mesh_fingerprint(mesh), _policy_component())
    with _lock:
        prog = _PROGRAMS.get(key)
        if prog is not None:
            _PROGRAMS.move_to_end(key)
            metrics.incr("jit.program_hit")
            return prog
        metrics.incr("jit.program_miss")
        # builders are where jax enters the process: finalize the
        # persistent-cache config + counter hooks before the first compile
        _ensure_persist_ready()
        jit_fn = builder(mesh, *static) if mesh is not None else \
            builder(*static)
        prog = _PROGRAMS[key] = CachedProgram(kernel_id, key, jit_fn)
        cap = _max_programs()
        while cap > 0 and len(_PROGRAMS) > cap:
            _PROGRAMS.popitem(last=False)   # LRU: callers holding a
            metrics.incr("jit.program_evictions")  # reference keep it alive
        return prog


def programs(kernel_id: Optional[str] = None) -> List[CachedProgram]:
    with _lock:
        ps = list(_PROGRAMS.values())
    if kernel_id is not None:
        ps = [p for p in ps if p.kernel_id == kernel_id]
    return ps


def clear_program_cache() -> None:
    """Drop every cached program (tests / hot-reload). The next use rebuilds
    and re-traces; jax-level caches attached to the dropped closures are
    garbage-collected with them."""
    with _lock:
        _PROGRAMS.clear()


def clear_kernel(kernel_id: str) -> int:
    """Drop every cached program registered under ``kernel_id`` (tests that
    rebuild kernels after flipping build-time flags). Returns the number of
    programs dropped."""
    with _lock:
        doomed = [k for k, p in _PROGRAMS.items() if p.kernel_id == kernel_id]
        for k in doomed:
            del _PROGRAMS[k]
        return len(doomed)


def compile_summary() -> Dict[str, Any]:
    """Aggregate compile observability: program counts, jit.* counters, the
    program-cache hit rate, and per-kernel signature counts + compile-time
    stats."""
    with _lock:
        progs = list(_PROGRAMS.values())
    counters = metrics.counters("jit.")
    hits = counters.get("jit.program_hit", 0)
    misses = counters.get("jit.program_miss", 0)
    kernels: Dict[str, Dict[str, Any]] = {}
    for p in progs:
        d = kernels.setdefault(p.kernel_id, {"programs": 0, "signatures": 0})
        d["programs"] += 1
        d["signatures"] += p.seen_signatures()
    for kid, d in kernels.items():
        stats = metrics.timer_stats(f"jitcache.{kid}.compile_s")
        if stats:
            d["compile"] = stats
    try:
        # join the performance observatory's static costs: per-kernel
        # FLOPs / bytes accessed / peak HBM next to the compile stats
        for kid, cost in _profiling.costs_by_kernel().items():
            if kid in kernels:
                kernels[kid]["cost"] = cost
    except Exception:
        pass
    return {
        "programs": len(progs),
        "counters": counters,
        "hit_rate": round(hits / (hits + misses), 4) if hits + misses else None,
        "kernels": kernels,
        "persist": persist_summary(),
    }


# ---------------------------------------------------------------------------
# AOT warmup
# ---------------------------------------------------------------------------

def seen_warmup_specs(kernel_ids: Optional[Iterable[str]] = None
                      ) -> List[Tuple[str, list]]:
    """Warmup specs ``[(kernel_id, [(shape, dtype), ...]), ...]`` for every
    shape signature the process has executed — the array leaves of each
    recorded signature, in the exact shape :func:`warmup` consumes and
    :func:`load_shape_profile` returns. This is the live-process twin of
    the ``ALINK_SHAPE_PROFILE`` file: it lets a replica snapshot what it
    warmed and persist that next to its model artifacts."""
    wanted = set(kernel_ids) if kernel_ids is not None else None
    specs: List[Tuple[str, list]] = []
    seen = set()
    for p in programs():
        if wanted is not None and p.kernel_id not in wanted:
            continue
        with p._lock:
            sigs = list(p._sigs)
        for sig in sigs:
            arrs = [(tuple(s[1]), s[2]) for s in sig if s[0] == "a"]
            if not arrs:
                continue
            key = (p.kernel_id, tuple(arrs))
            if key not in seen:
                seen.add(key)
                specs.append((p.kernel_id, arrs))
    return specs


def save_warmup_specs(path: str,
                      specs: Optional[Iterable] = None) -> int:
    """Write warmup specs to ``path`` in the ``ALINK_SHAPE_PROFILE`` jsonl
    format (what :func:`load_shape_profile` / ``warmup(path)`` read back in
    a process that has never compiled). Atomic replace — a reader never
    sees a half-written profile. Returns the number of specs written."""
    items = list(seen_warmup_specs() if specs is None else specs)
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        for kernel_id, arg_sigs in items:
            f.write(json.dumps({
                "kernel": kernel_id,
                "args": [[list(s), str(d)] for s, d in arg_sigs],
            }) + "\n")
    os.replace(tmp, path)
    return len(items)


def _run_warmup(specs: List[Tuple[str, list]], result: dict) -> None:
    compiled = errors = 0
    for kernel_id, arg_sigs in specs:
        for prog in programs(kernel_id):
            try:
                if prog.ensure_compiled(arg_sigs):
                    compiled += 1
            except Exception:
                errors += 1
                metrics.incr("jit.warmup_errors")
    result.update(compiled=compiled, errors=errors, specs=len(specs))


def warmup(specs: Optional[Iterable] = None, *, block: bool = False):
    """AOT-compile registered kernels ahead of the first real call.

    ``specs``: iterable of ``(kernel_id, [(shape, dtype), ...])``, or a
    path to a profile jsonl written by :func:`save_warmup_specs` /
    ``ALINK_SHAPE_PROFILE`` recording — the disk artifact that lets a
    process that has never compiled AOT-warm (with the persistent compile
    cache, each warm call deserializes the executable a previous process
    compiled). ``None`` loads the profile recorded under
    ``ALINK_SHAPE_PROFILE``. Only kernels already registered in this
    process (their ``cached_jit`` call has run — e.g. a model mapper was
    loaded) are warmable; unknown ids are skipped silently. By default the
    compiles run on a daemon thread (off the serving critical path) and the
    started thread is returned with a ``.result`` dict it fills;
    ``block=True`` runs inline and returns the dict
    ``{"compiled": n, "errors": e, "specs": s}``."""
    if specs is None:
        specs = load_shape_profile()
    elif isinstance(specs, str):
        specs = load_shape_profile(specs)
    norm: List[Tuple[str, list]] = []
    for item in specs:
        kid, sigs = item
        norm.append((kid, [(tuple(s), str(d)) for s, d in sigs]))
    result: dict = {}
    if block:
        _run_warmup(norm, result)
        return result
    th = threading.Thread(target=_run_warmup, args=(norm, result),
                          name="alink-warmup", daemon=True)
    th.result = result  # type: ignore[attr-defined]
    th.start()
    return th
