"""Job-scoped span tracing — the Dapper-style correlation layer.

Four PRs of runtime work left the platform with strong but *island* signals:
per-node executor phase records, ``jit.*`` compile counters,
``resilience_summary()``, checkpoint epochs. None of them answer the one
question an operator actually asks: *what did THIS job run spend its time
on, and where?* This module adds the missing correlation key — a trace id —
and the span tree under it:

- :func:`trace_span` — context-managed span: trace id / span id / parent id,
  wall time and self time (wall less the children that finished on the same
  thread), per-phase seconds (compile/transfer/compute, fed by the same
  ``node_phase_context`` plumbing the executor already uses), and an outcome
  (``ok`` / ``retried`` / ``failed`` / ``defused``). Spans nest through a
  thread-local; :func:`capture_context` + :func:`attach_context` carry the
  parent across explicit thread handoffs (the ``alink-dag`` executor pool,
  ``alink-h2d`` transfer streams, recovery chain threads), so a span started
  on a worker thread still parents correctly. Each span is also a
  ``jax.profiler.TraceAnnotation`` of its name on the thread that opened it:
  under a profiler session (:func:`~alink_tpu.common.metrics.profile_trace`)
  it lies on that thread's line of the ``/host:CPU`` plane, on the clock of
  the device operations; with no session it costs one flag test.
- :class:`Tracer` — process-wide finished-span sink: a bounded in-memory
  ring (``ALINK_TRACE_RING``, default 4096 spans) plus an optional append-
  only JSONL event log (``ALINK_TRACE_LOG=<path>``; one JSON object per
  finished span, crash-greppable). Every finished span's wall also goes
  into one histogram per name, ``span.<name>_s``, in the metrics registry
  (exact sum and count). Span names are therefore a closed set
  (``docs/observability.md`` lists them; the executor's unit spans are
  named by operator class): a request's, a row's or a model's name is an
  attribute, never part of a span's name.
- :func:`job_report` — one dict per job run: the span tree (one span per
  scheduled DAG unit, fused chains as ONE span with a ``fused`` mark), the
  compile/transfer/compute split, retries absorbed, outcome counts, and the
  program-/staging-cache hit rates active during the run.

Everything is gated behind ``ALINK_TRACING`` (default **on**; ``off``
restores zero-span execution). The gate is read per span open, so a test or
a latency-critical section can flip it at runtime. Tracing NEVER changes
results — the bit-parity contract is CI-pinned in
``tests/test_observability.py``; what tracing costs on the chip is in
PERF.md (PR 26).
"""

from __future__ import annotations

import atexit
import contextlib
import gc
import itertools
import json
import logging
import os
import statistics
import sys
import threading
import time
import uuid
from collections import deque
from typing import Any, Dict, List, Optional, Sequence

from .env import env_flag, env_float, env_int, env_str
from .metrics import metrics

logger = logging.getLogger("alink_tpu.tracing")

_RING_DEFAULT = 4096
_EXPORT_DEFAULT = 512

# A unit (``serving.batch``, ``train.epoch``; a device step in the train
# loop) is slow where it lies over the median of the last _UNIT_HISTORY of
# its kind, at least _SLOW_MIN_EARLIER of them, by more than _SLOW_RATIO of
# that median and by more than _SLOW_FLOOR_S. Read from the chip's pauses
# (PERF.md section 6, PR 36): a Moonlight epoch of +0.29 s on 3.84 s, a
# generator cycle of +0.9 s on 7.45 s and a served cycle of 0.44 s for 0.32 s
# all pass; a cycle's ordinary jitter of a few milliseconds does not.
_UNIT_HISTORY = 32
_SLOW_KEEP = 32
_SLOW_MIN_EARLIER = 4
_SLOW_RATIO = 0.05
_SLOW_FLOOR_S = 0.020
_SLOW_LOG_EVERY_S = 1.0
# a collection this long, or any of the oldest generation, gets a record of
# its own in the ring; the rest are counted and summed
_GC_RECORD_S = 1e-3

# span ids carry a per-process random prefix: cross-process stitching
# (fleet replicas relaying span batches to the supervisor) must never
# alias two processes' counters into one parent link
_SPAN_PREFIX = uuid.uuid4().hex[:6]
_span_ids = itertools.count(1)

# optional process identity (replica id / train rank) — when set, every
# finished span is stamped with it so cross-process readouts
# (job_report / chrome_trace) can lay spans out in real process lanes
_proc_label: Optional[str] = None
_proc_pid: Optional[int] = None


def set_process_identity(label: Optional[str],
                         pid: Optional[int] = None) -> None:
    """Tag every span finished in this process with ``proc=label`` (and
    the OS pid). Called once at worker/rank startup — e.g. a fleet
    replica sets its replica id, a distributed train process its rank.
    ``None`` clears the tag (spans revert to the local, untagged shape
    that keeps single-process readouts byte-stable)."""
    global _proc_label, _proc_pid
    if label is None:
        _proc_label, _proc_pid = None, None
    else:
        _proc_label = str(label)
        _proc_pid = int(pid) if pid is not None else os.getpid()


def process_identity() -> Optional[str]:
    return _proc_label


def tracing_enabled() -> bool:
    """``ALINK_TRACING=off`` disables span recording entirely (the
    histogram/counter layer in ``common/metrics.py`` stays on — it predates
    tracing and other readouts depend on it)."""
    global _enabled_seen
    _enabled_seen = env_flag("ALINK_TRACING", default=True)
    return _enabled_seen


# what ``tracing_enabled`` last returned: every span open, every drain of
# the collector and every reader of the registry refreshes it, and the
# collector's callback, which runs too often to read the environment, goes
# by it
_enabled_seen = env_flag("ALINK_TRACING", default=True)


class Span:
    """One traced unit of work. Mutable while open; callers may set
    ``outcome`` explicitly (``defused``), add ``phases`` seconds, or attach
    ``attrs``; everything else is filled by the tracer."""

    __slots__ = ("trace_id", "span_id", "parent_id", "name", "t_start",
                 "start_perf", "wall_s", "child_s", "phases", "outcome",
                 "retries", "attrs", "thread", "thread_id", "error", "keep",
                 "cpu_start", "cpu_s", "child_cpu_s", "unit")

    def __init__(self, trace_id: str, span_id: str, parent_id: Optional[str],
                 name: str, attrs: Dict[str, Any]):
        self.trace_id = trace_id
        self.span_id = span_id
        self.parent_id = parent_id
        self.name = name
        self.t_start = time.time()
        self.start_perf = time.perf_counter()
        self.wall_s: float = 0.0
        # seconds this span's thread was on a processor inside it (a span
        # opens and finishes on one thread): wall less CPU is the time the
        # thread waited, for the interpreter, the device or a core
        self.cpu_start = time.thread_time()
        self.cpu_s: float = 0.0
        # wall and CPU of the children that finished on this span's own thread
        self.child_s: float = 0.0
        self.child_cpu_s: float = 0.0
        self.phases: Dict[str, float] = {}
        self.outcome: Optional[str] = None
        self.retries = 0
        self.attrs = attrs
        self.thread = threading.current_thread().name
        self.thread_id = threading.get_ident()
        self.error: Optional[str] = None
        # False: the span leaves no record (ring, log, histogram) when it
        # ends — the batcher's last wait, which ends in shutdown
        self.keep = True
        # not None: the span is a unit of the slow-unit record, and this is
        # the key its walls are compared under (a served model's name)
        self.unit: Optional[str] = None

    @property
    def self_s(self) -> float:
        """Wall less the children that finished on the same thread. A
        child on another thread (:func:`attach_context`) runs beside its
        parent, not inside it, and is not subtracted."""
        return max(self.wall_s - self.child_s, 0.0)

    @property
    def self_cpu_s(self) -> float:
        """The same of the thread's CPU seconds."""
        return max(self.cpu_s - self.child_cpu_s, 0.0)

    def to_dict(self) -> Dict[str, Any]:
        d: Dict[str, Any] = {
            "trace_id": self.trace_id,
            "span_id": self.span_id,
            "parent_id": self.parent_id,
            "name": self.name,
            "t_start": round(self.t_start, 6),
            "start_perf": self.start_perf,
            "wall_s": round(self.wall_s, 6),
            "self_s": round(self.self_s, 6),
            "cpu_s": round(self.cpu_s, 6),
            "self_cpu_s": round(self.self_cpu_s, 6),
            "outcome": self.outcome,
            "thread": self.thread,
        }
        if self.phases:
            d["phases"] = {k: round(v, 6) if isinstance(v, float) else v
                           for k, v in self.phases.items()}
        if self.retries:
            d["retries"] = self.retries
        if self.attrs:
            d["attrs"] = self.attrs
        if self.error:
            d["error"] = self.error
        if _proc_label is not None:
            d["proc"] = _proc_label
            d["pid"] = _proc_pid
        return d


_ctx = threading.local()


def current_span() -> Optional[Span]:
    return getattr(_ctx, "span", None)


def capture_context() -> Optional[Span]:
    """The active span — the token a thread handoff carries so work on the
    other thread parents correctly AND feeds the span's retry accounting
    (:func:`note_retry` on a transfer thread must mark the owning span).
    None when no span is open (or tracing is off): attaching None is a
    no-op."""
    return current_span()


@contextlib.contextmanager
def attach_context(token: Optional[Span]):
    """Install a captured span as this thread's span parent for the
    duration (executor pool workers, transfer streams, recovery chains).
    Restores the previous context on exit — pool threads are reused."""
    if token is None:
        yield
        return
    prev = getattr(_ctx, "span", None)
    _ctx.span = token
    try:
        yield
    finally:
        _ctx.span = prev


class _RemoteParent:
    """A wire-adopted parent token: quacks enough like a :class:`Span`
    (trace id, span id, retry counter) for :meth:`Tracer.start` and
    :func:`note_retry` to treat it as the active parent, without being a
    recordable span itself — the real span lives in the origin process."""

    __slots__ = ("trace_id", "span_id", "proc", "retries")

    def __init__(self, trace_id: str, span_id: str, proc: Optional[str]):
        self.trace_id = trace_id
        self.span_id = span_id
        self.proc = proc
        self.retries = 0


_CTX_MAX_ID = 128  # a wire id longer than this is garbage, not a trace


def wire_context() -> Optional[Dict[str, Any]]:
    """The active span as a serializable wire token — trace id, parent
    span id, origin process identity — the thing a frame-protocol request
    carries so the receiving process can parent its spans under the
    caller's. ``None`` when no span is open (or tracing is off): stamping
    ``None`` into a request is the defined old-client shape and adopting
    it is a no-op."""
    sp = current_span()
    if sp is None:
        return None
    ctx: Dict[str, Any] = {"trace_id": sp.trace_id, "span_id": sp.span_id}
    origin = _proc_label or getattr(sp, "proc", None)
    if origin is not None:
        ctx["proc"] = origin
    return ctx


@contextlib.contextmanager
def adopt_context(ctx: Optional[Dict[str, Any]]):
    """Install a :func:`wire_context` token received over the wire as
    this thread's span parent for the duration — the receive-side half of
    the cross-process contract. ``None`` (old client / tracing off at the
    origin) and malformed tokens are tolerated: the block runs untraced-
    parented (its spans become local roots — the orphan-span fallback a
    rolling-restart mix relies on), with garbage counted in
    ``trace.bad_wire_context``."""
    if ctx is None or not tracing_enabled():
        yield
        return
    tid = ctx.get("trace_id") if isinstance(ctx, dict) else None
    sid = ctx.get("span_id") if isinstance(ctx, dict) else None
    if not (isinstance(tid, str) and 0 < len(tid) <= _CTX_MAX_ID
            and isinstance(sid, str) and 0 < len(sid) <= _CTX_MAX_ID):
        metrics.incr("trace.bad_wire_context")
        yield
        return
    proc = ctx.get("proc")
    token = _RemoteParent(tid, sid,
                          str(proc) if isinstance(proc, str) else None)
    prev = getattr(_ctx, "span", None)
    _ctx.span = token
    try:
        yield
    finally:
        _ctx.span = prev


def slow_against(earlier: Sequence[float], value: float) -> Optional[float]:
    """The median of ``earlier`` where ``value`` lies over it by more than
    5% and by more than 20 ms and ``earlier`` holds at least four; else
    None. The one rule for a slow unit and a slow device step."""
    if len(earlier) < _SLOW_MIN_EARLIER:
        return None
    usual = statistics.median(earlier)
    return usual if value - usual > max(_SLOW_RATIO * usual,
                                        _SLOW_FLOOR_S) else None


_BY_NAME = ("wall_s", "cpu_s", "self_s", "self_cpu_s")


def _rounded(by_name: Dict[str, List[float]]) -> Dict[str, Dict[str, float]]:
    return {n: {k: round(v, 6) for k, v in zip(_BY_NAME, row)}
            for n, row in by_name.items()}


def _slow_line(rec: Dict[str, Any]) -> str:
    """A slow unit as one line of the operator's slow log::

        serving.batch key=model 2.051 s for a usual 0.471: host.gc gen2
        0.094 s on MainThread; dl.predict.apply +1.480 s, cpu +0.002;
        bert.tokenize +0.012 s, cpu +0.011; slowest step of lm.decode 17:
        0.912 s

    After the collections, the three span names whose own seconds (wall less
    same-thread children) grew most over the last ordinary unit's, each
    with the growth of its own CPU seconds: a wall that grew without its
    CPU waited, for the device, the interpreter or a core."""
    parts = [f"host.gc gen{c['generation']} {c['wall_s']:.3f} s on "
             f"{c['thread']}" for c in rec["collections"]]
    usual = rec["usual_by_name"]
    grown = []
    for name, row in rec["by_name"].items():
        if name == "host.gc":
            continue
        was = usual.get(name) or {}
        grown.append((row["self_s"] - was.get("self_s", 0.0),
                      row["self_cpu_s"] - was.get("self_cpu_s", 0.0), name))
    for wall, cpu, name in sorted(grown, reverse=True)[:3]:
        if wall > 0.001:
            parts.append(f"{name} +{wall:.3f} s, cpu {cpu:+.3f}")
    parts += [f"slowest step of {s['span']} {s['slowest_step']}: "
              f"{s['slowest_step_s']:.3f} s" for s in rec["steps"]]
    return (f"{rec['unit']} key={rec['key']} {rec['wall_s']:.3f} s for a "
            f"usual {rec['usual_s']:.3f}: " + "; ".join(parts))


# ---------------------------------------------------------------------------
# The collector as a span: host.gc
# ---------------------------------------------------------------------------
# ``gc.callbacks`` calls ``_on_gc`` on whichever thread an allocation trips
# the collector's threshold, which may be inside ``metrics.observe`` under
# the registry's lock, or inside ``Tracer.finish`` under the ring's. So the
# callback takes no lock and allocates next to nothing: it writes plain
# module-level totals, which only it writes (the interpreter lock is held
# and collections do not nest, so two callbacks never interleave), and a
# bounded deque. ``_drain_gc`` turns them into histogram entries, counters
# and ring records, from ``Tracer.finish`` where a recorded collection waits
# and from whoever reads the registry (``metrics`` calls it through its
# read hooks).

# Every collection's record lies in this trace, not in its parent span's: a
# job's report and tree hold what the job ran, the same from run to run,
# and the collector falls where the heap sends it. ``parent_id`` still
# names the span that was open on the collection's thread.
_GC_TRACE = "host.gc"
_gc_t0 = 0.0                # 0.0: the collection under way is not timed
_gc_annotation = None
# the collections that get a record of their own, and the totals of the rest:
# seconds, objects collected, collections of generation 0, 1, 2
_gc_pending: deque = deque(maxlen=256)
_gc_short = [0.0, 0, 0, 0, 0]
# what the last drain had seen of those totals; under _gc_drain_lock
_gc_drained = [0.0, 0, 0, 0, 0]
_gc_drain_lock = threading.Lock()


def _on_gc(phase: str, info: Dict[str, Any], _now=time.perf_counter) -> None:
    # paid at every collection of any generation, 62 times a served BERT
    # batch (PERF.md section 6, PR 36): the gate is the value
    # ``tracing_enabled`` last returned, not a read of the environment
    global _gc_t0, _gc_annotation
    if phase == "start":
        if _enabled_seen:
            if info["generation"]:
                # a young collection is too short and too frequent for an
                # event of its own on the profiler's host plane
                profiler = _jax_profiler()
                if profiler is not None:
                    _gc_annotation = profiler.TraceAnnotation("host.gc")
                    _gc_annotation.__enter__()
            _gc_t0 = _now()
        else:
            _gc_t0 = 0.0
        return
    if not _gc_t0:
        return
    wall = _now() - _gc_t0
    generation = info["generation"]
    if not generation and wall < _GC_RECORD_S:
        _gc_short[0] += wall
        _gc_short[1] += info["collected"]
        _gc_short[2] += 1
        return
    if _gc_annotation is not None:
        _gc_annotation.__exit__(None, None, None)
        _gc_annotation = None
    if generation < 2 and wall < _GC_RECORD_S:
        _gc_short[0] += wall
        _gc_short[1] += info["collected"]
        _gc_short[2 + generation] += 1
        return
    parent = getattr(_ctx, "span", None)
    if type(parent) is Span:
        # a collection is its thread's work: it lies inside the span open
        # there, as a child whose CPU seconds are taken to be its wall
        parent.child_s += wall
        parent.child_cpu_s += wall
    _gc_pending.append((_gc_t0, wall, generation, info["collected"],
                        threading.current_thread().name, parent))


def _drain_gc() -> List[Dict[str, Any]]:
    """What the collector did since the last drain: its seconds into
    ``span.host.gc_s`` (a recorded collection by itself, the short ones as
    so many entries of their mean: sum and count stay exact), the counters,
    and the recorded collections as ring records, oldest first. Each total
    is read once and only ever grows, so a drain that falls between two of
    the callback's additions leaves the rest to the next drain."""
    if not _gc_pending and _gc_short[0] == _gc_drained[0]:
        return []
    with _gc_drain_lock:
        seen = list(_gc_short)
        seconds, collected, *by_gen = (a - b for a, b
                                       in zip(seen, _gc_drained))
        _gc_drained[:] = seen
        picked = []
        while _gc_pending:
            picked.append(_gc_pending.popleft())
    records: List[Dict[str, Any]] = []
    for t0, wall, generation, n, thread, parent in picked:
        metrics.observe("span.host.gc_s", wall)
        by_gen[generation] += 1
        collected += n
        rec: Dict[str, Any] = {
            "trace_id": _GC_TRACE,
            "span_id": f"{_SPAN_PREFIX}-{next(_span_ids):x}",
            "parent_id": None if parent is None else parent.span_id,
            "name": "host.gc",
            "t_start": round(time.time() - (time.perf_counter() - t0), 6),
            "start_perf": t0,
            "wall_s": round(wall, 6), "self_s": round(wall, 6),
            "cpu_s": round(wall, 6), "self_cpu_s": round(wall, 6),
            "outcome": "ok", "thread": thread,
            "attrs": {"generation": generation, "collected": n},
        }
        if _proc_label is not None:
            rec["proc"], rec["pid"] = _proc_label, _proc_pid
        records.append(rec)
    short = sum(by_gen) - len(picked)
    if short > 0:
        metrics.observe_mean("span.host.gc_s", max(seconds, 0.0) / short,
                             short)
    for generation, n in enumerate(by_gen):
        if n:
            metrics.incr(f"host.gc_collections.gen{generation}", n)
    if collected:
        metrics.incr("host.gc_collected", collected)
    return records


class Tracer:
    """Process-wide finished-span sink: bounded ring + optional JSONL log."""

    def __init__(self):
        self._lock = threading.Lock()
        self._ring: deque = deque(maxlen=max(16, env_int(
            "ALINK_TRACE_RING", _RING_DEFAULT)))
        self._log_lock = threading.Lock()
        self._log_path: Optional[str] = None
        self._log_file = None
        self._log_bytes = 0
        self._log_rotated = False
        self._export: Optional[deque] = None
        # spans' CPU seconds by name, [sum, count], since the last reader
        self._cpu: Dict[str, List[Any]] = {}
        # the slow-unit record: per (unit name, key) the last walls and the
        # last ordinary unit's record; the slow units kept
        self._units: Dict[tuple, Dict[str, Any]] = {}
        self._slow: deque = deque(maxlen=_SLOW_KEEP)
        self._slow_logged = 0.0

    # -- span lifecycle ------------------------------------------------------
    def start(self, name: str, **attrs) -> Span:
        parent = current_span()
        if parent is None:
            trace_id = uuid.uuid4().hex[:16]
            parent_id = None
        else:
            trace_id = parent.trace_id
            parent_id = parent.span_id
        span_id = f"{_SPAN_PREFIX}-{next(_span_ids):x}"
        return Span(trace_id, span_id, parent_id, name,
                    {k: v for k, v in attrs.items() if v is not None})

    def finish(self, span: Span) -> None:
        span.wall_s = time.perf_counter() - span.start_perf
        span.cpu_s = time.thread_time() - span.cpu_start
        if span.outcome is None:
            span.outcome = "retried" if span.retries else "ok"
        if not span.keep:
            return
        metrics.observe(f"span.{span.name}_s", span.wall_s)
        d = span.to_dict()
        # the recorded collections go into the ring in their place; the
        # short ones' totals wait for a reader of the registry
        records = _drain_gc() + [d] if _gc_pending else [d]
        slow = None
        with self._lock:
            self._admit(records)
            # CPU seconds by name are summed here, under the lock the ring
            # takes anyway, and reach ``cpu.<name>_s`` when somebody reads
            # the registry (``drain_collector``): no second histogram entry
            # on a span's path
            row = self._cpu.get(span.name)
            if row is None:
                row = self._cpu[span.name] = [0.0, 0]
            row[0] += span.cpu_s
            row[1] += 1
            if span.unit is not None:
                slow = self._unit_locked(span, d)
        self._log(d)
        if slow is not None:
            self._unit_was_slow(span, d, *slow)

    def _admit(self, records: List[Dict[str, Any]]) -> None:
        """Finished records into the ring and, where armed, the export
        buffer; under ``_lock``."""
        self._ring.extend(records)
        if self._export is not None:
            for d in records:
                e = dict(d)
                e.pop("start_perf", None)  # process-local; dead on the wire
                self._export.append(e)

    def drain_collector(self) -> None:
        """Hand what the collector's callbacks have counted to the registry
        and the ring now (``finish`` does it where a recorded collection
        waits; the registry calls this before it answers a reader), with the
        spans' CPU seconds summed by name since the last reader (so many
        entries of their mean in ``cpu.<name>_s``: sum and count exact), and
        look at ``ALINK_TRACING`` on the callbacks' behalf."""
        tracing_enabled()
        records = _drain_gc()
        with self._lock:
            if records:
                self._admit(records)
            cpu, self._cpu = self._cpu, {}
        for name, (seconds, n) in cpu.items():
            metrics.observe_mean(f"cpu.{name}_s", seconds / n, n)
        for d in records:
            self._log(d)

    # -- the slow-unit record ------------------------------------------------
    def _unit_locked(self, span: Span, unit: Dict[str, Any]):
        """A unit's wall against the median of the last of its key, under
        ``_lock``: an ordinary unit pays this and no more (its record is
        kept as the last ordinary one, to be summed only if a slow unit
        asks); for a slow one, the ring's records that met it and those
        that met the last ordinary unit."""
        key = (span.name, span.unit)
        kept = self._units.get(key)
        if kept is None:
            kept = self._units[key] = {
                "walls": deque(maxlen=_UNIT_HISTORY), "usual": None}
        usual_s = slow_against(kept["walls"], span.wall_s)
        kept["walls"].append(span.wall_s)
        if usual_s is None:
            kept["usual"] = unit
            return None
        usual = kept["usual"]
        return (usual_s, self._met_locked(unit), usual,
                [] if usual is None else self._met_locked(usual))

    def _met_locked(self, unit: Dict[str, Any]) -> List[Dict[str, Any]]:
        """The ring's records, of any thread, that finished before ``unit``
        did and whose interval meets the unit's. The ring is in order of
        finish, so the walk back passes the records that finished after the
        unit and ends at the first that finished before it began; a unit
        that has rolled out of the ring meets nothing."""
        start = unit["start_perf"]
        met: List[Dict[str, Any]] = []
        found = False
        for s in reversed(self._ring):
            if not found:
                found = s is unit
                continue
            t0 = s.get("start_perf")
            if t0 is None:      # relayed from another process
                continue
            if t0 + s["wall_s"] < start:
                break
            met.append(s)
        return met

    @staticmethod
    def _sums(unit: Dict[str, Any], met: List[Dict[str, Any]]):
        """Of a unit and the records that met it: ``_BY_NAME``'s four sums
        by span name, the collections inside it and the slowest steps its
        spans named."""
        start = unit["start_perf"]
        end = start + unit["wall_s"]
        by_name: Dict[str, List[float]] = {}
        collections, steps = [], []
        for s in [unit] + met:
            row = by_name.get(s["name"])
            if row is None:
                row = by_name[s["name"]] = [0.0, 0.0, 0.0, 0.0]
            # seconds inside the unit: a record that reaches over an edge
            # counts by the share of its wall that lies inside
            t0, w = s["start_perf"], s["wall_s"]
            inside = min(t0 + w, end) - max(t0, start)
            share = 1.0 if inside >= w or w <= 0 else max(inside, 0.0) / w
            for i, k in enumerate(_BY_NAME):
                row[i] += share * s.get(k, 0.0)
            attrs = s.get("attrs")
            if not attrs:
                continue
            if s["name"] == "host.gc":
                collections.append({"generation": attrs.get("generation"),
                                    "wall_s": w, "thread": s["thread"]})
            if "slowest_step_s" in attrs:
                steps.append({"span": s["name"],
                              "slowest_step": attrs.get("slowest_step"),
                              "slowest_step_s": attrs["slowest_step_s"]})
        return by_name, collections, steps

    def _unit_was_slow(self, span: Span, unit: Dict[str, Any], usual_s: float,
                       met: List[Dict[str, Any]],
                       usual: Optional[Dict[str, Any]],
                       usual_met: List[Dict[str, Any]]) -> None:
        wall = span.wall_s
        by_name, collections, steps = self._sums(unit, met)
        usual_by_name = {} if usual is None else self._sums(usual, usual_met)[0]
        record = {
            "unit": span.name, "key": span.unit, "t_start": unit["t_start"],
            "start_perf": unit["start_perf"], "wall_s": round(wall, 6),
            "usual_s": round(usual_s, 6), "excess_s": round(wall - usual_s, 6),
            "by_name": _rounded(by_name), "usual_by_name": _rounded(usual_by_name),
            "collections": collections, "steps": steps,
        }
        record["line"] = _slow_line(record)
        metrics.incr(f"slow.{span.name}")
        metrics.observe(f"slow.{span.name}_excess_s", wall - usual_s)
        now = time.perf_counter()
        with self._lock:
            self._slow.append(record)
            say = now - self._slow_logged >= _SLOW_LOG_EVERY_S
            if say:
                self._slow_logged = now
        if say:
            logger.warning("%s", record["line"])

    def slow_units(self) -> List[Dict[str, Any]]:
        """The last slow units (at most 32), oldest first: each with its
        wall beside the usual one, its seconds by span name beside the last
        ordinary unit's, the collections inside it and the slowest steps
        its spans named, and the line that was logged for it."""
        with self._lock:
            return list(self._slow)

    # -- cross-process relay -------------------------------------------------
    def enable_export(self, maxlen: int = _EXPORT_DEFAULT) -> None:
        """Arm the export buffer: every finished span is ALSO queued
        (bounded, oldest dropped) for :meth:`drain_export` — the replica
        side of the heartbeat span relay. Off by default: a single-process
        session pays nothing."""
        with self._lock:
            self._export = deque(maxlen=max(16, int(maxlen)))

    def drain_export(self) -> List[Dict[str, Any]]:
        """Take (and clear) the finished spans queued since the last
        drain. Empty list when export was never enabled."""
        with self._lock:
            if not self._export:
                return []
            out = list(self._export)
            self._export.clear()
        return out

    def ingest(self, span_dicts: Any, proc: Optional[str] = None,
               pid: Optional[int] = None) -> int:
        """Merge a relayed span batch (dicts from another process's
        :meth:`drain_export`) into this ring, stamped with the sender's
        process identity. Validates EVERY entry before admitting ANY —
        raises ``ValueError`` on garbage so the caller can count and drop
        the whole payload loudly; a half-ingested batch would corrupt the
        stitched tree silently."""
        if not isinstance(span_dicts, (list, tuple)):
            raise ValueError("span batch is not a list")
        accepted: List[Dict[str, Any]] = []
        for s in span_dicts:
            if not isinstance(s, dict):
                raise ValueError("span batch entry is not a dict")
            if not all(isinstance(s.get(k), str) and s.get(k)
                       for k in ("trace_id", "span_id", "name")):
                raise ValueError("span entry missing trace_id/span_id/name")
            d = {k: v for k, v in s.items() if k != "start_perf"}
            try:
                d["t_start"] = float(d.get("t_start", 0.0))
                d["wall_s"] = float(d.get("wall_s", 0.0))
            except (TypeError, ValueError):
                raise ValueError("span entry times are not numeric")
            pid_in = d.get("parent_id")
            if pid_in is not None and not isinstance(pid_in, str):
                raise ValueError("span entry parent_id is not a string")
            d.setdefault("parent_id", None)
            d.setdefault("outcome", "ok")
            if proc is not None:
                d["proc"] = str(proc)
                if pid is not None:
                    d["pid"] = int(pid)
            accepted.append(d)
        with self._lock:
            self._ring.extend(accepted)
        return len(accepted)

    @staticmethod
    def _max_log_bytes() -> int:
        """``ALINK_TRACE_LOG_MAX_MB`` caps the JSONL event log. 0 / unset =
        unbounded (the pre-cap behavior)."""
        mb = env_float("ALINK_TRACE_LOG_MAX_MB", 0.0) or 0.0
        return int(mb * 1024 * 1024) if mb > 0 else 0

    def _log(self, d: Dict[str, Any]) -> None:
        path = env_str("ALINK_TRACE_LOG")
        if not path:
            return
        rec = dict(d)
        rec.pop("start_perf", None)  # process-local; meaningless in a file
        line = json.dumps(rec, default=str) + "\n"
        nbytes = len(line.encode("utf-8"))
        try:
            with self._log_lock:
                if self._log_file is None or self._log_path != path:
                    if self._log_file is not None:
                        self._log_file.close()
                    self._log_file = open(path, "a")
                    self._log_path = path
                    self._log_rotated = False
                    try:
                        self._log_bytes = os.path.getsize(path)
                    except OSError:
                        self._log_bytes = 0
                cap = self._max_log_bytes()
                if cap and self._log_bytes + nbytes > cap:
                    # rotate ONCE per path: keep a .1 of the filled log and
                    # start fresh; when the fresh file fills too, drop (and
                    # count) further events — a long-lived serving process
                    # must never grow the log without bound
                    if self._log_rotated:
                        metrics.incr("trace.log_dropped")
                        return
                    self._log_file.close()
                    os.replace(path, path + ".1")
                    self._log_file = open(path, "w")
                    self._log_bytes = 0
                    self._log_rotated = True
                    metrics.incr("trace.log_rotated")
                self._log_file.write(line)
                self._log_file.flush()
                self._log_bytes += nbytes
        except OSError:
            metrics.incr("trace.log_errors")

    # -- readouts ------------------------------------------------------------
    def spans(self, trace_id: Optional[str] = None, *,
              collector: bool = False) -> List[Dict[str, Any]]:
        """Finished spans (dicts), oldest first; filtered to one trace when
        ``trace_id`` is given. The collector's records (trace ``host.gc``)
        are left out of the unfiltered list unless ``collector`` asks for
        them: what a job ran is the same from run to run, where the
        collector falls is not."""
        with self._lock:
            out = list(self._ring)
        if trace_id is not None:
            out = [s for s in out if s["trace_id"] == trace_id]
        elif not collector:
            out = [s for s in out if s["trace_id"] != _GC_TRACE]
        return out

    def last_trace_id(self) -> Optional[str]:
        """Trace id of the most recently finished ROOT span (a root is a
        span with no parent — one per job run)."""
        with self._lock:
            for s in reversed(self._ring):
                if s["parent_id"] is None and s["trace_id"] != _GC_TRACE:
                    return s["trace_id"]
        return None

    def traces(self, limit: int = 50) -> List[Dict[str, Any]]:
        """Most-recent-first summaries of the traces still in the ring:
        trace id, root span name, wall, span count, worst outcome."""
        with self._lock:
            spans = list(self._ring)
        by_trace: Dict[str, List[Dict[str, Any]]] = {}
        order: List[str] = []
        for s in spans:
            if s["trace_id"] == _GC_TRACE:
                continue
            if s["trace_id"] not in by_trace:
                order.append(s["trace_id"])
            by_trace.setdefault(s["trace_id"], []).append(s)
        out = []
        for tid in reversed(order):
            ss = by_trace[tid]
            root = next((s for s in ss if s["parent_id"] is None), None)
            bad = next((s["outcome"] for s in ss
                        if s["outcome"] == "failed"), None)
            out.append({
                "trace_id": tid,
                "root": root["name"] if root else ss[0]["name"],
                "t_start": (root or ss[0])["t_start"],
                "wall_s": (root or ss[0])["wall_s"],
                "spans": len(ss),
                "outcome": bad or (root["outcome"] if root else "ok"),
            })
            if len(out) >= limit:
                break
        return out

    def clear(self) -> None:
        with self._lock:
            self._ring = deque(maxlen=max(16, env_int(
                "ALINK_TRACE_RING", _RING_DEFAULT)))
            if self._export is not None:
                self._export.clear()
            self._units.clear()
            self._slow.clear()
            self._slow_logged = 0.0
        with self._log_lock:
            if self._log_file is not None:
                self._log_file.close()
                self._log_file = None
                self._log_path = None
            self._log_bytes = 0
            self._log_rotated = False


tracer = Tracer()
metrics.register_read_hook(tracer.drain_collector)
gc.callbacks.append(_on_gc)


@atexit.register
def _unhook_collector() -> None:
    # the interpreter's last collections run while modules are taken apart
    if _on_gc in gc.callbacks:
        gc.callbacks.remove(_on_gc)


def _jax_profiler():
    """``jax.profiler`` where jax is already imported, else None: a process
    that never imported jax has no profiler session to write to, and a
    fleet parent must stay off jax."""
    return getattr(sys.modules.get("jax"), "profiler", None)


@contextlib.contextmanager
def trace_span(name: str, *, unit: Optional[str] = None, **attrs):
    """Open a span around a block::

        with trace_span("kmeans.fit", rows=n) as sp:
            ...

    ``unit`` marks the span as a unit of the slow-unit record (one of the
    spans a served cycle or a training run is made of: ``serving.batch``,
    ``train.epoch``) and is the key its walls are compared under.

    Yields the open :class:`Span` (set ``sp.outcome``/``sp.phases``/
    ``sp.attrs`` freely) or ``None`` when tracing is off — callers must
    guard attribute access with ``if sp is not None``. An exception marks
    the span ``failed`` (error type + message recorded) and propagates
    unchanged. Spans opened on the same thread nest automatically; use
    :func:`capture_context`/:func:`attach_context` across threads."""
    if not tracing_enabled():
        yield None
        return
    span = tracer.start(name, **attrs)
    span.unit = unit
    prev = getattr(_ctx, "span", None)
    _ctx.span = span
    profiler = _jax_profiler()
    try:
        with profiler.TraceAnnotation(name) if profiler \
                else contextlib.nullcontext():
            yield span
    except BaseException as e:
        span.outcome = "failed"
        span.error = f"{type(e).__name__}: {e}"[:200]
        raise
    finally:
        _ctx.span = prev
        tracer.finish(span)
        if isinstance(prev, Span) and prev.thread_id == span.thread_id:
            prev.child_s += span.wall_s
            prev.child_cpu_s += span.cpu_s


def step_annotation(name: str, step: int):
    """``jax.profiler.StepTraceAnnotation(name, step_num=step)``: a step
    boundary of the program's own in a profiler trace (the train loop opens
    one per optimizer step). It is no span — the ring and the registry
    already count steps (``train.step_s``) — and, like a span's annotation,
    a null context when tracing is off or jax was never imported."""
    profiler = _jax_profiler()
    if profiler is None or not tracing_enabled():
        return contextlib.nullcontext()
    return profiler.StepTraceAnnotation(name, step_num=step)


def note_retry() -> None:
    """Called by the resilience layer on every retry sleep: bumps the
    active span's retry count so the span's outcome reads ``retried`` even
    though the call ultimately succeeded. No-op outside a span."""
    sp = current_span()
    if sp is not None:
        sp.retries += 1


# ---------------------------------------------------------------------------
# Job report
# ---------------------------------------------------------------------------


def _span_tree(spans: List[Dict[str, Any]]) -> List[Dict[str, Any]]:
    by_id = {s["span_id"]: dict(s, children=[]) for s in spans}
    roots: List[Dict[str, Any]] = []
    for s in by_id.values():
        parent = by_id.get(s["parent_id"]) if s["parent_id"] else None
        if parent is not None:
            parent["children"].append(s)
        else:
            roots.append(s)
    # rel time base: perf_counter within one process (sub-µs, immune to
    # clock steps); ingested cross-process spans have no start_perf, so a
    # stitched tree falls back to the wall-clock epoch every process shares
    key = "start_perf" if all(
        "start_perf" in s for s in by_id.values()) else "t_start"
    base = min((s[key] for s in by_id.values()), default=0.0)
    for s in by_id.values():
        s["rel_start_s"] = round(s.get(key, base) - base, 6)
        s.pop("start_perf", None)
    # second pass: a remote child can sit AFTER its parent in ring order
    # (it arrived by heartbeat relay long after the parent finished), so
    # children only sort once every span has its rel_start_s
    for s in by_id.values():
        s["children"].sort(key=lambda c: c["rel_start_s"])
    roots.sort(key=lambda c: c["rel_start_s"])
    return roots


def _train_block() -> Optional[Dict[str, Any]]:
    """The DL training loop's hot-path readout (None when no train ran
    this process): the ``train.step_s`` / ``train.feed_wait_s`` /
    ``train.accum_flush_s`` histograms plus every ``train.*`` counter —
    the observatory sees the training loop like every other hot path.
    Built from the metrics recorder directly so ``job_report`` never
    imports the dl stack."""
    from .metrics import metrics

    out: Dict[str, Any] = {}
    for name in ("train.step_s", "train.feed_wait_s",
                 "train.accum_flush_s"):
        st = metrics.histogram(name)
        if st is not None:
            out[name.split(".", 1)[1]] = st
    counters = metrics.counters("train.")
    if counters:
        out["counters"] = counters
    return out or None


def job_report(trace_id: Optional[str] = None) -> Dict[str, Any]:
    """One dict per job run: the DAG-shaped span tree plus the aggregate
    split an operator wants first.

    ``trace_id=None`` reports the most recently finished root span's trace.
    Returns ``{"error": ...}`` when the trace is unknown (or tracing was
    off), never raises — this feeds an HTTP endpoint."""
    if trace_id is None:
        trace_id = tracer.last_trace_id()
        if trace_id is None:
            return {"error": "no traces recorded "
                             "(is ALINK_TRACING off?)"}
    spans = tracer.spans(trace_id)
    if not spans:
        return {"error": f"unknown trace {trace_id!r}"}
    totals: Dict[str, float] = {}
    outcomes: Dict[str, int] = {}
    retries = 0
    for s in spans:
        outcomes[s["outcome"]] = outcomes.get(s["outcome"], 0) + 1
        retries += s.get("retries", 0)
        for k, v in (s.get("phases") or {}).items():
            if k.endswith("_s") and isinstance(v, (int, float)):
                totals[k] = round(totals.get(k, 0.0) + v, 6)
    tree = _span_tree(spans)
    root = tree[0] if tree else None
    caches: Dict[str, Any] = {}
    try:
        from .jitcache import compile_summary

        cs = compile_summary()
        caches["programs"] = {"hit_rate": cs["hit_rate"],
                              "cached": cs["programs"]}
    except Exception:
        pass
    try:
        from .staging import staging_cache_stats

        st = staging_cache_stats()
        hits, misses = st.get("hits", 0), st.get("misses", 0)
        caches["staging"] = {
            "hit_rate": round(hits / (hits + misses), 4)
            if hits + misses else None,
            "wire_bytes_sent": st.get("wire_bytes_sent"),
        }
    except Exception:
        pass
    profile: Dict[str, Any] = {}
    try:
        # the performance observatory's per-kernel cost/roofline table —
        # the static "what should this have cost" side of the span tree
        from .profiling import profile_summary

        profile = profile_summary(top=12)
    except Exception:
        pass
    try:
        # last pre-flight plan-validation report (None when the validator
        # never ran — ALINK_VALIDATE_PLAN=off)
        from ..analysis import last_plan_report

        analysis: Optional[Dict[str, Any]] = last_plan_report()
    except Exception:
        analysis = None
    return {
        "trace_id": trace_id,
        "profile": profile,
        "train": _train_block(),
        "slow_units": tracer.slow_units(),
        "analysis": analysis,
        "root": None if root is None else
        {"name": root["name"], "wall_s": root["wall_s"],
         "outcome": root["outcome"]},
        "spans": [{k: v for k, v in s.items() if k != "start_perf"}
                  for s in spans],
        "tree": tree,
        "totals": totals,
        "retries": retries,
        "outcomes": outcomes,
        "caches": caches,
    }


def chrome_trace(trace_id: Optional[str] = None) -> Dict[str, Any]:
    """The span ring as a chrome://tracing / Perfetto JSON object (trace
    event format). ``trace_id=None`` exports every finished span in the
    ring — one waterfall across jobs; pass an id to cut one job out.

    Each span becomes one complete ("X") event with its phases, attrs,
    outcome, and span/parent ids under ``args``; threads map to stable
    integer tids with thread_name metadata so the waterfall groups by the
    pool/transfer/driver thread that ran the work. Spans relayed from
    other processes (fleet replicas, train ranks — tagged ``proc``/
    ``pid`` by :meth:`Tracer.ingest`) get their OWN process lane: one
    Perfetto track group per replica, named by its process identity, so
    a stitched fleet trace reads frontdoor-over-here, batcher-over-there.
    Local spans stay on the canonical ``pid: 1`` lane — single-process
    output is byte-stable. Load the file via ui.perfetto.dev or
    chrome://tracing; :func:`write_chrome_trace` writes it to a
    path."""
    spans = tracer.spans(trace_id, collector=True)
    events: List[Dict[str, Any]] = [{
        "ph": "M", "pid": 1, "tid": 0, "name": "process_name",
        "args": {"name": "alink_tpu"},
    }]
    lanes: Dict[str, int] = {}

    def _lane(s: Dict[str, Any]) -> int:
        proc = s.get("proc")
        if proc is None:
            return 1
        lane = lanes.get(proc)
        if lane is None:
            pid = s.get("pid")
            lane = pid if isinstance(pid, int) and pid > 1 \
                and pid not in lanes.values() else 10_000 + len(lanes)
            lanes[proc] = lane
            events.append({"ph": "M", "pid": lane, "tid": 0,
                           "name": "process_name",
                           "args": {"name": str(proc)}})
        return lane

    tids: Dict[Any, int] = {}
    per_lane: Dict[int, int] = {}
    for s in spans:
        lane = _lane(s)
        thread = s.get("thread") or "?"
        tid = tids.get((lane, thread))
        if tid is None:
            per_lane[lane] = tid = per_lane.get(lane, 0) + 1
            tids[(lane, thread)] = tid
            events.append({"ph": "M", "pid": lane, "tid": tid,
                           "name": "thread_name",
                           "args": {"name": thread}})
        args: Dict[str, Any] = {
            "trace_id": s["trace_id"], "span_id": s["span_id"],
            "parent_id": s.get("parent_id"), "outcome": s.get("outcome"),
        }
        for key in ("phases", "attrs", "retries", "error", "proc"):
            if s.get(key):
                args[key] = s[key]
        events.append({
            "ph": "X", "pid": lane, "tid": tid,
            "name": s["name"],
            "cat": s.get("outcome") or "ok",
            "ts": round(s["t_start"] * 1e6, 3),
            "dur": round(max(s.get("wall_s") or 0.0, 0.0) * 1e6, 3),
            "args": args,
        })
    return {"traceEvents": events, "displayTimeUnit": "ms"}


def write_chrome_trace(path: str, trace_id: Optional[str] = None) -> int:
    """Write :func:`chrome_trace` to ``path``; returns the span count."""
    blob = chrome_trace(trace_id)
    with open(path, "w", encoding="utf-8") as f:
        json.dump(blob, f)
        f.write("\n")
    # metadata events (process + one per thread) don't count as spans
    return sum(1 for e in blob["traceEvents"] if e["ph"] == "X")
