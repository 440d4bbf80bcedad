"""Device-resident staging cache + wire-precision policy.

Reference analog: the comqueue session cache
(core/src/main/java/com/alibaba/alink/common/comqueue/SessionSharedObjs.java:158
``cachePartitionedData`` — partitioned data staged once and reused across
supersteps within a job). Here the cache is *content-keyed* and spans jobs:
repeated ``execute()``/``link_from`` of the same table does not re-push the
same bytes host->device — the second and later pushes of a block are free.

Wire precision: float32 blocks at or above a size threshold are cast
to bfloat16 on the host (halving wire bytes), shipped, and upcast to float32
on device, so compute keeps fp32 accumulation. Controlled by
``AlinkGlobalConfiguration`` wire-precision policy:

- ``"auto"`` (default): **precision-safe by default** — bf16 wire only for
  float blocks >= threshold (4 MiB) AND a measured-slow host->device link
  (see :func:`wire_is_slow`); on local/PCIe-class links auto is exact fp32.
- ``"bf16"``: always use the bf16 wire for float blocks (explicit opt-in)
- ``"fp32"``: never downcast on the wire

Env overrides: ``ALINK_WIRE_PRECISION``, ``ALINK_STAGING_CACHE_BYTES``
(0 disables the cache), ``ALINK_ASSUME_SLOW_WIRE`` (1/0 forces the
slow-link gate instead of probing).

Cache sizing: the default cap is min(2 GiB, ~12% of detected device HBM)
— see :func:`_device_default_cap` — so the cache never silently pins a
large fraction of a small accelerator's memory.
"""

from __future__ import annotations

import hashlib
import threading
import time
from collections import OrderedDict
from typing import Any, Optional, Tuple

import numpy as np

from .env import env_int, env_raw, env_str
from .metrics import metrics

_WIRE_THRESHOLD_BYTES = 4 * 1024 * 1024
_DEFAULT_MAX_BYTES = 2 * 1024 * 1024 * 1024
_HBM_FRACTION = 0.12
_hbm_cap_lock = threading.Lock()
_hbm_cap: "int | None" = None


def _device_default_cap() -> int:
    """Default cache cap sized to the accelerator: min(2 GiB, ~12% of device
    HBM). A flat 2 GiB silently pins an eighth of a 16 GB v5e — and would be
    a third of an 8 GB part; small devices get a proportionally small cache.
    Falls back to the flat default when the backend exposes no memory stats
    (CPU, older plugins). Probed once; ``ALINK_STAGING_CACHE_BYTES`` and
    ``set_max_bytes`` still override."""
    global _hbm_cap
    cap = _hbm_cap
    if cap is not None:
        return cap
    with _hbm_cap_lock:
        if _hbm_cap is None:
            cap = _DEFAULT_MAX_BYTES
            try:
                import jax

                stats = jax.local_devices()[0].memory_stats()
                limit = (stats or {}).get("bytes_limit")
                if limit:
                    cap = min(cap, int(limit * _HBM_FRACTION))
            except Exception:
                pass
            _hbm_cap = cap
        return _hbm_cap


class _Stats:
    __slots__ = ("hits", "misses", "wire_bytes_sent", "wire_bytes_saved",
                 "evictions")

    def __init__(self):
        self.hits = 0
        self.misses = 0
        self.wire_bytes_sent = 0
        self.wire_bytes_saved = 0
        self.evictions = 0

    def as_dict(self):
        return {
            "hits": self.hits,
            "misses": self.misses,
            "wire_bytes_sent": self.wire_bytes_sent,
            "wire_bytes_saved": self.wire_bytes_saved,
            "evictions": self.evictions,
        }


class StagingCache:
    """LRU cache of device-resident (sharded) arrays keyed by host content.

    The key is a blake2b digest of the host bytes plus the placement
    (mesh devices, partition axis, padding, wire dtype) — two jobs staging
    the same table to the same mesh share one device copy. JAX arrays are
    immutable, so sharing is safe; eviction is LRU by device bytes."""

    def __init__(self, max_bytes: Optional[int] = None):
        self._lock = threading.RLock()
        self._entries: "OrderedDict[Tuple, Any]" = OrderedDict()
        self._bytes = 0
        self._max_bytes = max_bytes
        self.stats = _Stats()

    # -- config ------------------------------------------------------------
    @property
    def max_bytes(self) -> int:
        raw = env_raw("ALINK_STAGING_CACHE_BYTES")
        if raw is not None:
            try:
                return int(raw)  # any <= 0 disables the cache
            except ValueError:
                pass  # malformed tuning knob: fall back, never crash
        return (self._max_bytes if self._max_bytes is not None
                else _device_default_cap())

    def set_max_bytes(self, n: int) -> None:
        with self._lock:
            self._max_bytes = int(n)
            self._evict()

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()
            self._bytes = 0

    # -- core --------------------------------------------------------------
    def get(self, key: Tuple):
        with self._lock:
            if key in self._entries:
                self._entries.move_to_end(key)
                self.stats.hits += 1
                return self._entries[key]
            self.stats.misses += 1
            return None

    def put(self, key: Tuple, value, nbytes: int) -> None:
        if self.max_bytes <= 0:
            return
        with self._lock:
            if key in self._entries:
                return
            self._entries[key] = value
            self._bytes += nbytes
            self._evict()

    def _evict(self) -> None:
        cap = self.max_bytes
        while self._bytes > cap and self._entries:
            _, (val, nbytes) = self._entries.popitem(last=False)
            self._bytes -= nbytes
            self.stats.evictions += 1

    def note_wire(self, sent: int = 0, saved: int = 0) -> None:
        """Record wire traffic under the cache lock — the pipelined executor
        feeds staging from several DAG/transfer threads at once, so unlocked
        ``+=`` on the counters loses updates."""
        with self._lock:
            self.stats.wire_bytes_sent += sent
            self.stats.wire_bytes_saved += saved

    def stats_dict(self):
        with self._lock:
            d = self.stats.as_dict()
            d["resident_bytes"] = self._bytes
            d["resident_entries"] = len(self._entries)
            return d


_cache = StagingCache()


def staging_cache() -> StagingCache:
    return _cache


def staging_cache_stats() -> dict:
    return _cache.stats_dict()


def clear_staging_cache() -> None:
    _cache.clear()
    _cache.stats = _Stats()


# ---------------------------------------------------------------------------
# Wire precision policy + host->device bandwidth probe
# ---------------------------------------------------------------------------

_SLOW_WIRE_MBPS = 64.0
_PROBE_BYTES = 1 * 1024 * 1024
_wire_probe: dict = {"slow": None, "mbps": None}
_probe_lock = threading.Lock()


def measured_wire_mbps() -> Optional[float]:
    """Host→device bandwidth from the one-shot probe (None before it ran)."""
    return _wire_probe["mbps"]


def wire_is_slow() -> bool:
    """Whether the host→device link is a remote-class bottleneck.

    Resolution order: ``ALINK_ASSUME_SLOW_WIRE`` (1/0 forces the answer) >
    a cached one-shot probe (a 1 MiB ``device_put`` with a dependent fetch;
    < ~64 MB/s counts as slow; what the v5e host reads on this probe is in
    PERF.md). The answer gates the ``auto`` bf16 wire policy and
    content-cache use inside streaming."""
    env = env_str("ALINK_ASSUME_SLOW_WIRE")
    if env is not None:
        return env.lower() in ("1", "true", "yes")
    if _wire_probe["slow"] is None:
        # single-flight: concurrent transfer threads must not each run a
        # probe (they would measure a self-contended wire), and callers who
        # resolve the gate before streaming (stream_map does) keep the probe
        # clear of their own traffic
        with _probe_lock:
            if _wire_probe["slow"] is None:
                import time

                try:
                    import jax

                    buf = np.arange(_PROBE_BYTES, dtype=np.uint8)
                    # warm at the probe's own shape: the dependent fetch
                    # compiles per shape, and a compile inside the window
                    # reads as a slow link
                    _ = float(jax.device_put(buf)[0])
                    buf = buf[::-1].copy()
                    t0 = time.perf_counter()
                    _ = float(jax.device_put(buf)[0])  # dependent fetch =
                    dt = max(time.perf_counter() - t0, 1e-9)  # real sync
                    mbps = _PROBE_BYTES / 1e6 / dt
                    _wire_probe["mbps"] = mbps
                    _wire_probe["slow"] = mbps < _SLOW_WIRE_MBPS
                except Exception:
                    # transient (backend not up yet): answer fast-for-now
                    # but do NOT cache — retry on the next call
                    return False
    return _wire_probe["slow"]


def wire_precision() -> str:
    env = env_str("ALINK_WIRE_PRECISION")
    if env:
        return env.lower()
    from .env import AlinkGlobalConfiguration

    return AlinkGlobalConfiguration.get_wire_precision()


def _policy_key() -> str:
    """Cache-key component for the wire policy. Under ``auto`` the effective
    cast depends on the slow-wire gate, so the gate's answer must be part of
    the key — otherwise flipping ALINK_ASSUME_SLOW_WIRE mid-process could
    return a bf16-rounded cached array to a caller expecting exact fp32."""
    pol = wire_precision()
    if pol != "auto":
        return pol
    return "auto-slow" if wire_is_slow() else "auto-fast"


def _wire_cast(arr: np.ndarray) -> Tuple[np.ndarray, bool]:
    """Return (wire_array, downcast?) under the active wire policy.

    Only float32 blocks ride the bf16 wire: float64 stays full-precision
    (quantizing 52 mantissa bits to 7 is not a wire optimization), and the
    upcast on device restores the caller's exact dtype contract. ``auto`` is
    precision-safe by default: it downcasts only when the block is large AND
    the link measured slow (on a local link the bf16 rounding buys
    nothing)."""
    policy = wire_precision()
    if policy == "fp32" or arr.dtype != np.float32:
        return arr, False
    if policy == "bf16" or (
        policy == "auto" and arr.nbytes >= _WIRE_THRESHOLD_BYTES
        and wire_is_slow()
    ):
        import ml_dtypes

        return arr.astype(ml_dtypes.bfloat16), True
    return arr, False


# ---------------------------------------------------------------------------
# Content keys
# ---------------------------------------------------------------------------

def _digest(arr: np.ndarray) -> str:
    a = np.ascontiguousarray(arr)
    h = hashlib.blake2b(digest_size=16)
    h.update(str((a.shape, a.dtype.str)).encode())
    h.update(a.view(np.uint8).reshape(-1).data if a.dtype != object else
             repr(a.tolist()).encode())
    return h.hexdigest()


def _mesh_key(mesh) -> Tuple:
    return (
        tuple(getattr(d, "id", i) for i, d in enumerate(mesh.devices.flat)),
        tuple(mesh.shape.items()),
    )


# ---------------------------------------------------------------------------
# Staging entry points
# ---------------------------------------------------------------------------

def stage_sharded(
    arr: np.ndarray,
    mesh,
    axis: str,
    *,
    with_mask: bool = False,
    pad_rows_to: Optional[int] = None,
):
    """Stage ``arr`` row-sharded over ``mesh[axis]``, via the content cache.

    Pads dim0 to ``pad_rows_to`` (or the axis size multiple) before placing;
    float32 blocks ride the bf16 wire under the active policy and are upcast
    back to float32 on device. Returns the device array, or
    ``(array, mask)`` when ``with_mask`` — mask is 1.0 for real rows."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    arr = np.asarray(arr)
    n_shards = mesh.shape[axis]
    n = arr.shape[0]
    if pad_rows_to is None:
        from ..parallel.mesh import pad_to_multiple

        pad_rows_to = pad_to_multiple(max(n, n_shards), n_shards)
    sharding = NamedSharding(mesh, P(axis))

    key = ("rows", _digest(arr), _mesh_key(mesh), axis, pad_rows_to,
           _policy_key())
    hit = _cache.get(key)
    if hit is not None:
        out, _ = hit
    else:
        t0 = time.perf_counter()
        padded = arr
        if pad_rows_to != n:
            pad_width = [(0, pad_rows_to - n)] + [(0, 0)] * (arr.ndim - 1)
            padded = np.pad(arr, pad_width)
        wire, downcast = _wire_cast(padded)
        dev = jax.device_put(wire, sharding)
        if downcast:
            dev = dev.astype(padded.dtype)  # restore the caller's dtype
        _cache.note_wire(sent=wire.nbytes,
                         saved=padded.nbytes - wire.nbytes if downcast else 0)
        # host-side staging cost (pad + wire cast + transfer dispatch);
        # device_put is async, so the on-wire tail is not in this number
        metrics.observe("staging.transfer_s", time.perf_counter() - t0)
        out = dev
        _cache.put(key, (out, out.nbytes), out.nbytes)

    if not with_mask:
        return out
    mdtype = arr.dtype if arr.dtype.kind == "f" else np.float32
    mkey = ("mask", n, pad_rows_to, str(np.dtype(mdtype)), _mesh_key(mesh), axis)
    mhit = _cache.get(mkey)
    if mhit is not None:
        return out, mhit[0]
    mask = np.zeros(pad_rows_to, dtype=mdtype)
    mask[:n] = 1.0
    mdev = jax.device_put(mask, sharding)
    _cache.note_wire(sent=mask.nbytes)
    _cache.put(mkey, (mdev, mdev.nbytes), mdev.nbytes)
    return out, mdev


def stage_replicated(arr: np.ndarray, mesh=None):
    """Stage ``arr`` replicated (or single-device), via the content cache."""
    import jax

    arr = np.asarray(arr)
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec as P

        sharding = NamedSharding(mesh, P())
        mkey = _mesh_key(mesh)
    else:
        sharding = None
        mkey = ("default", getattr(jax.devices()[0], "id", 0))

    key = ("repl", _digest(arr), mkey, _policy_key())
    hit = _cache.get(key)
    if hit is not None:
        return hit[0]
    t0 = time.perf_counter()
    wire, downcast = _wire_cast(arr)
    dev = jax.device_put(wire, sharding) if sharding is not None else \
        jax.device_put(wire)
    if downcast:
        dev = dev.astype(arr.dtype)  # restore the caller's dtype
    _cache.note_wire(sent=wire.nbytes,
                     saved=arr.nbytes - wire.nbytes if downcast else 0)
    metrics.observe("staging.transfer_s", time.perf_counter() - t0)
    _cache.put(key, (dev, dev.nbytes), dev.nbytes)
    return dev
