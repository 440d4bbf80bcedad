"""Double-buffered host→device streaming.

When batches arrive from the host, a predict pays for the host→device
transfer as well as the compute (how the two compare on the v5e host is
not measured yet: ROADMAP S2). This module turns "transfer, then compute,
then transfer, ..." into a
pipeline: ``device_put`` of micro-batch *k+1* runs on a dedicated transfer
thread while the device computes micro-batch *k*, so end-to-end throughput
approaches ``max(wire, compute)`` instead of their sum. With more than one
transfer stream, several ``device_put`` calls are in flight at once, which
can also lift a single-stream transfer limit.

Knobs (env):

- ``ALINK_STREAM_DEPTH``  — in-flight transfer buffers per stream (default 2:
  classic double buffering; batch *k* computing while *k+1* ships).
- ``ALINK_H2D_STREAMS``   — transfer threads shared process-wide (default 4).

``stream_map(..., split=k)`` additionally splits every batch into *k* row
chunks shipped on *k* parallel streams and reassembled on device before
compute — where one stream cannot fill the link, aggregate bandwidth
scales with the stream count while the compiled program's batch shape is
untouched.

Staging-cache integration: with ``use_cache="auto"`` batches go through
:func:`alink_tpu.common.staging.stage_replicated` (content-keyed device
cache) whenever the wire is measured slow — re-streaming the same table
costs nothing — and bypass the digest overhead on fast local wires.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Callable, Iterable, Iterator, Optional, Sequence, Tuple

from .env import env_int

DEFAULT_DEPTH = 2

_pool_lock = threading.Lock()
_pool: Optional[ThreadPoolExecutor] = None


def stream_depth(default: int = DEFAULT_DEPTH) -> int:
    return max(1, env_int("ALINK_STREAM_DEPTH", default))


def _num_streams() -> int:
    return max(1, env_int("ALINK_H2D_STREAMS", 4))


def transfer_pool() -> ThreadPoolExecutor:
    """Process-wide host→device transfer threads. ``device_put`` releases the
    GIL during the copy, so a small pool genuinely parallelizes the wire."""
    global _pool
    with _pool_lock:
        if _pool is None:
            _pool = ThreadPoolExecutor(
                max_workers=_num_streams(), thread_name_prefix="alink-h2d")
        return _pool


def _default_put(arrays: Sequence[Any], use_cache: bool):
    import jax

    if use_cache:
        from .staging import stage_replicated

        return [stage_replicated(a) for a in arrays]
    devs = [jax.device_put(a) for a in arrays]
    # force the copy to complete inside the transfer thread — that is what
    # makes the overlap real (and the transfer time measurable) instead of
    # deferring the wire wait into the consumer's dispatch
    jax.block_until_ready(devs)
    return devs


def stream_map(
    fn: Callable[..., Any],
    batches: Iterable[Tuple[Any, Sequence[Any]]],
    *,
    depth: Optional[int] = None,
    use_cache: "bool | str" = False,
    put: Optional[Callable[[Sequence[Any]], Sequence[Any]]] = None,
    split: int = 1,
    phases: Optional[dict] = None,
) -> Iterator[Tuple[Any, Any]]:
    """Yield ``(meta, fn(*device_arrays))`` for each ``(meta, host_arrays)``
    in ``batches``, with up to ``depth`` transfers in flight ahead of compute.

    ``use_cache="auto"`` routes transfers through the content-keyed staging
    cache when the wire is slow (see module docstring). ``split=k`` ships
    each batch as *k* parallel row-chunk transfers reassembled on device
    (bit-identical input, k× the wire streams). ``phases`` (optional dict)
    accumulates ``transfer_s`` / ``wait_s`` (consumer stall on the
    in-flight transfer — ~0 when the pipeline overlaps) / ``compute_s`` /
    ``batches``; the same numbers also land on the active executor node
    trace, so the per-node breakdown sees the split without extra
    plumbing.

    Transfers retry under the central
    :class:`~alink_tpu.common.resilience.RetryPolicy` when the failure is
    transient (wire drop, device RESOURCE_EXHAUSTED) — safe because a
    ``device_put`` is idempotent; the ``transfer`` fault-injection point
    fires before every attempt."""
    from .faults import maybe_fail
    from .metrics import add_node_phase, metrics
    from .resilience import with_retries
    from .tracing import attach_context, capture_context

    if use_cache == "auto":
        from .staging import wire_is_slow

        use_cache = wire_is_slow()
    if put is None:
        def put(arrays, _cache=bool(use_cache)):
            return _default_put(arrays, _cache)

    depth = stream_depth(DEFAULT_DEPTH) if depth is None else max(1, depth)
    split = max(1, int(split))
    pool = transfer_pool()
    # transfers run on shared alink-h2d threads: carry the caller's trace
    # context across the handoff so a retried transfer marks the caller's
    # span (the DAG unit / stream op) `retried`, not an orphan
    tctx = capture_context()

    def timed_put(arrays):
        def attempt():
            maybe_fail("transfer")
            return put(arrays)

        t0 = time.perf_counter()
        with attach_context(tctx):
            devs = with_retries(attempt, name="h2d.transfer",
                                counter="resilience.transfer_retries")
        return devs, t0, time.perf_counter()

    def submit(arrays):
        """One future per batch (split=1) or per row chunk (split>1) —
        chunk futures fan across the transfer threads, so one batch's
        bytes ride several wire streams concurrently."""
        if split <= 1 or not len(arrays) or arrays[0].shape[0] < split:
            return pool.submit(timed_put, arrays)
        import numpy as _np

        bounds = _np.linspace(
            0, arrays[0].shape[0], split + 1).astype(int)
        return [
            pool.submit(timed_put, [a[s:e] for a in arrays])
            for s, e in zip(bounds[:-1], bounds[1:]) if e > s
        ]

    def gather(handle):
        """(device arrays, transfer seconds) from a submit() handle. For a
        split batch the chunks transfer concurrently, so the honest transfer
        time is the wall span max(end)-min(start), not the per-chunk sum."""
        if not isinstance(handle, list):
            devs, t0, t1 = handle.result()
            return devs, t1 - t0
        parts, starts, ends = [], [], []
        for f in handle:
            devs, t0, t1 = f.result()
            parts.append(devs)
            starts.append(t0)
            ends.append(t1)
        import jax.numpy as jnp

        return [jnp.concatenate([p[i] for p in parts], axis=0)
                for i in range(len(parts[0]))], max(ends) - min(starts)

    it = iter(batches)
    inflight: deque = deque()

    def pump():
        while len(inflight) < depth:
            try:
                meta, arrays = next(it)
            except StopIteration:
                return
            inflight.append((meta, submit(arrays)))

    pump()
    while inflight:
        meta, handle = inflight.popleft()
        t_wait = time.perf_counter()
        devs, dt_put = gather(handle)
        # the consumer-side stall: how long THIS loop blocked on the
        # in-flight transfer. Near-zero when the pipeline overlaps
        # (transfer finished while compute ran); ~transfer_s when the wire
        # is the bottleneck — the one number that says whether the
        # double-buffering is actually hiding the host
        dt_wait = time.perf_counter() - t_wait
        add_node_phase("transfer_s", dt_put)
        metrics.observe("stream.transfer_s", dt_put)
        metrics.observe("stream.wait_s", dt_wait)
        if phases is not None:
            phases["transfer_s"] = phases.get("transfer_s", 0.0) + dt_put
            phases["wait_s"] = phases.get("wait_s", 0.0) + dt_wait
        t0 = time.perf_counter()
        out = fn(*devs)
        dt_fn = time.perf_counter() - t0
        add_node_phase("compute_s", dt_fn)
        metrics.observe("stream.compute_s", dt_fn)
        if phases is not None:
            phases["compute_s"] = phases.get("compute_s", 0.0) + dt_fn
            phases["batches"] = phases.get("batches", 0) + 1
        pump()  # keep the pipe full before handing control back
        yield meta, out


def iter_row_chunks(arrays: Sequence[Any], chunk_rows: int):
    """Split row-aligned host arrays into ``(n_valid, [chunks])`` micro-batches
    — the generic feeder for :func:`stream_map` over one logical table."""
    n = arrays[0].shape[0]
    for s in range(0, n, chunk_rows):
        part = [a[s:s + chunk_rows] for a in arrays]
        yield part[0].shape[0], part
