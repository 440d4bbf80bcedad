"""Elastic exactly-once streaming — keyed-state repartitioning and
backpressure-driven rescaling on the epoch runtime.

PR 3's :class:`~alink_tpu.common.recovery.CheckpointCoordinator` snapshots
per-operator state at quiescent epoch barriers — exactly the mechanism a
running stream job needs to *rescale*, not just restart (the same
checkpoint-and-redistribute design as Flink's savepoint rescaling). This
module adds the missing pieces:

- **Key groups** — the key space is hashed into ``num_key_groups`` fixed
  buckets (:func:`key_group`); a parallelism *P* owns contiguous ranges of
  them (:func:`partition_ranges`, Flink's key-group design). The key group
  is the atom of state redistribution: a group's rows always reach exactly
  one partition, in source order, so per-group results — and therefore the
  canonically merged job output — are invariant to the parallelism that
  happens to host them. Bit-identical scale-out/scale-in falls out of the
  design instead of being an aspiration.
- :class:`ElasticStreamJob` — one replayable source fanning out to logical
  chains, each replicated across partitions. *Keyed* chains (every op
  reports :meth:`~StreamOperator.elastic_keyed` for the job's ``key_col``)
  shard rows by hash; *global* chains (FTRL/OnlineFm accumulators, eval
  counters) pin their whole sub-stream — and their state — to one key
  group, the degenerate but exact case of hash-range redistribution.
- :class:`ElasticCoordinator` — drives the job under epoch snapshotting
  and changes parallelism at a quiescent barrier: ``state_partition`` the
  old instances across the new ranges, write the epoch snapshot (the
  manifest commit IS the rescale commit point — a crash before it simply
  never rescaled; after it, restart resumes at the new parallelism),
  rebuild the chain set with ``state_merge``, resume. Crash drills inject
  at the ``rescale`` fault point (``pre_redistribute`` /
  ``mid_redistribute`` / ``pre_resume``).
- :class:`BackpressureController` — watches the per-epoch
  ``stream.chunk_s`` signal (seconds per chunk vs the declared target
  arrival rate), exports the ``stream.lag_s`` gauge, and decides
  scale-out under sustained lag / scale-in when idle, with a hysteresis
  band, per-rescale cooldown, and a flap breaker that degrades the job to
  fixed parallelism (``recovery.rescale_aborted``) instead of thrashing.

Output determinism: partition runners tag every emission with
``(chunk index, key group, seq)``; the coordinator merges all partitions'
staged outputs in that order at each barrier before staging into the
transactional sinks, so the committed sink sequence is identical at any
parallelism — CI-pinned in ``tests/test_elastic.py``.
"""

from __future__ import annotations

import threading
import time
import zlib
from typing import (Any, Callable, Dict, Iterator, List, Optional, Sequence,
                    Tuple)

import numpy as np

from .exceptions import (AkIllegalArgumentException, AkIllegalStateException)
from .faults import maybe_fail
from .metrics import metrics
from .mtable import MTable
from .recovery import (_END, CheckpointCoordinator, SnapshotStore,
                       TransactionalSink, _RescaleInterrupt,
                       _SharedSourceReader, logger)
from .tracing import attach_context, capture_context, trace_span

DEFAULT_KEY_GROUPS = 128

# chunk-index tag for end-of-stream flush emissions: sorts after every
# real chunk, sub-ordered by the flushing partition's first owned key
# group (ops flush key groups in ascending order, so the concatenation of
# partition flushes in range order equals a single instance's flush)
_FLUSH = 1 << 62


def key_group(value: Any, num_key_groups: int) -> int:
    """Stable hash of a key value into ``[0, num_key_groups)``. crc32 of
    ``str(value)`` — stable across processes and restarts (unlike
    ``hash()``), and identical for a value however the chunk stores it
    as long as its string form is stable (ints, strings)."""
    return zlib.crc32(str(value).encode("utf-8")) % int(num_key_groups)


def partition_ranges(num_key_groups: int,
                     parallelism: int) -> List[Tuple[int, int]]:
    """Contiguous ``[lo, hi)`` key-group ranges, one per partition —
    Flink's key-group assignment: every group owned by exactly one
    partition, ranges covering ``[0, num_key_groups)`` exactly."""
    g, p = int(num_key_groups), int(parallelism)
    if p < 1 or p > g:
        raise AkIllegalArgumentException(
            f"parallelism must be in [1, num_key_groups={g}], got {p}")
    return [(g * i // p, g * (i + 1) // p) for i in range(p)]


def owner_of(kg: int, ranges: Sequence[Tuple[int, int]]) -> int:
    for i, (lo, hi) in enumerate(ranges):
        if lo <= kg < hi:
            return i
    raise AkIllegalStateException(
        f"key group {kg} is outside every partition range {list(ranges)}")


def _take_rows(chunk: MTable, idxs: List[int]) -> MTable:
    """Row subset preserving dtypes and schema (numpy fancy indexing per
    column — never a string round trip)."""
    return MTable({n: np.asarray(chunk.col(n))[idxs] for n in chunk.names},
                  chunk.schema)


def _chunk_key_groups(chunk: MTable, key_col: str,
                      num_key_groups: int) -> List[int]:
    """Per-row key groups of a source chunk, hashed ONCE per chunk and
    cached on the chunk object — every keyed partition runner (and every
    keyed op downstream, via the sub-chunk stamp) reads the same array
    instead of re-hashing rows O(parallelism) times."""
    cached = getattr(chunk, "_elastic_kgs", None)
    if cached is None:
        cached = [key_group(v, num_key_groups) for v in chunk.col(key_col)]
        chunk._elastic_kgs = cached
    return cached


def _split_chunk(chunk: MTable, key_col: str, num_key_groups: int,
                 lo: int, hi: int) -> List[Tuple[int, MTable]]:
    """This partition's rows of ``chunk``, as (key group, sub-chunk) pairs
    in ascending key-group order, source row order preserved within each
    group. Sub-chunks are stamped with their key group
    (``_elastic_kg``) so keyed ops can skip re-hashing the rows."""
    kgs = _chunk_key_groups(chunk, key_col, num_key_groups)
    by_kg: Dict[int, List[int]] = {}
    for i, kg in enumerate(kgs):
        if lo <= kg < hi:
            by_kg.setdefault(kg, []).append(i)
    out = []
    for kg in sorted(by_kg):
        sub = _take_rows(chunk, by_kg[kg])
        sub._elastic_kg = kg
        out.append((kg, sub))
    return out


def _has_snapshot_hooks(op) -> bool:
    from ..operator.stream.base import StreamOperator

    return type(op).state_snapshot is not StreamOperator.state_snapshot


# ---------------------------------------------------------------------------
# Backpressure controller
# ---------------------------------------------------------------------------


class BackpressureController:
    """Turns the epoch-level backpressure signal into rescale decisions.

    Signal: seconds-per-chunk this epoch vs ``target_chunk_s`` — the
    arrival interval the stream must keep up with (a live source's poll
    period; for drills, a calibrated baseline). The derived
    ``stream.lag_s`` gauge (seconds fallen behind per epoch) exports at
    ``GET /metrics``.

    Decision rules, in order:

    - hysteresis band: ratio in ``(low, high)`` resets both streaks — no
      decision. ``ratio >= high`` for ``patience`` consecutive epochs →
      scale OUT (×``scale_factor``); ``ratio <= low`` for ``patience``
      epochs → scale IN (÷``scale_factor``).
    - cooldown: no new decision within ``cooldown_epochs`` of the last one
      (a rescale changes the signal; judging the new parallelism on
      pre-rescale epochs would thrash).
    - flap breaker: more than ``max_flips`` direction reversals inside
      ``flap_window`` epochs opens the breaker for the rest of the run —
      the job degrades to fixed parallelism (each suppressed decision
      counts ``recovery.rescale_aborted``) instead of oscillating.

    ``lag_fn(stats)`` overrides the wall-clock signal with an external
    one — a real deployment's queue depth, or a scripted schedule in
    deterministic tests.
    """

    def __init__(self, target_chunk_s: float, *, high: float = 1.5,
                 low: float = 0.5, patience: int = 2,
                 cooldown_epochs: int = 2, scale_factor: int = 2,
                 flap_window: int = 16, max_flips: int = 4,
                 lag_fn: Optional[Callable[[Dict[str, Any]], float]] = None):
        if not (0 <= low < high):
            raise AkIllegalArgumentException(
                f"need 0 <= low < high, got low={low} high={high}")
        self.target_chunk_s = float(target_chunk_s)
        self.high, self.low = float(high), float(low)
        self.patience = max(1, int(patience))
        self.cooldown_epochs = max(0, int(cooldown_epochs))
        self.scale_factor = max(2, int(scale_factor))
        self.flap_window = max(1, int(flap_window))
        self.max_flips = max(1, int(max_flips))
        self.lag_fn = lag_fn
        self.breaker_open = False
        self._hot = 0
        self._cold = 0
        self._last_decision_epoch: Optional[int] = None
        self._decisions: List[Tuple[int, int]] = []  # (epoch, direction)

    def lag_seconds(self, stats: Dict[str, Any]) -> float:
        if self.lag_fn is not None:
            return float(self.lag_fn(stats))
        chunks = max(1, int(stats.get("chunks") or 1))
        return max(0.0, float(stats["wall_s"])
                   - self.target_chunk_s * chunks)

    def observe(self, stats: Dict[str, Any]) -> Optional[int]:
        """Feed one epoch's stats ({epoch, wall_s, chunks, parallelism});
        returns a target parallelism, or None for no change."""
        epoch = int(stats["epoch"])
        p = int(stats["parallelism"])
        lag = self.lag_seconds(stats)
        metrics.set_gauge("stream.lag_s", lag)
        chunks = max(1, int(stats.get("chunks") or 1))
        if self.lag_fn is not None:
            # an injected signal expresses pressure directly as lag
            ratio = 1.0 + lag / max(self.target_chunk_s * chunks, 1e-9) \
                if lag > 0 else 0.0
        else:
            per_chunk = float(stats["wall_s"]) / chunks
            ratio = per_chunk / self.target_chunk_s \
                if self.target_chunk_s > 0 else 0.0
        if ratio >= self.high:
            self._hot += 1
            self._cold = 0
        elif ratio <= self.low:
            self._cold += 1
            self._hot = 0
        else:
            self._hot = self._cold = 0
        direction = 0
        if self._hot >= self.patience:
            direction = 1
        elif self._cold >= self.patience:
            direction = -1
        if direction == 0:
            return None
        if self.breaker_open:
            metrics.incr("recovery.rescale_aborted")
            return None
        if (self._last_decision_epoch is not None
                and epoch - self._last_decision_epoch
                < self.cooldown_epochs):
            return None  # cooldown: streaks keep counting, decision waits
        target = p * self.scale_factor if direction > 0 \
            else max(1, p // self.scale_factor)
        # respect the job's parallelism bounds (the coordinator passes
        # them in the stats) BEFORE recording anything: a decision the
        # bounds reduce to a no-op must not pollute the flap history
        lo = int(stats.get("min_parallelism") or 1)
        hi = int(stats.get("max_parallelism") or (1 << 30))
        target = min(max(target, lo), hi)
        if target == p:
            # already at the floor: a no-op "decision" must not feed the
            # flap history or the aborted counter — an idle job parked at
            # min parallelism is healthy, not thrashing
            self._hot = self._cold = 0
            return None
        recent = [d for e, d in self._decisions
                  if epoch - e <= self.flap_window] + [direction]
        flips = sum(1 for a, b in zip(recent, recent[1:]) if a != b)
        if flips >= self.max_flips:
            self.breaker_open = True
            metrics.incr("recovery.rescale_aborted")
            logger.warning(
                "backpressure breaker OPEN: %d direction flips within %d "
                "epochs — degrading to fixed parallelism %d",
                flips, self.flap_window, p)
            return None
        self._decisions.append((epoch, direction))
        # only the flap window's suffix is ever read — a long-lived job
        # must not grow the history without bound
        if len(self._decisions) > 4 * self.max_flips:
            del self._decisions[:-4 * self.max_flips]
        self._last_decision_epoch = epoch
        self._hot = self._cold = 0
        return target


# ---------------------------------------------------------------------------
# Job topology
# ---------------------------------------------------------------------------


class _ChainSpec:
    __slots__ = ("factory", "sinks", "keyed", "pin", "op_sig")

    def __init__(self, factory, sinks, keyed, pin, op_sig):
        self.factory = factory
        self.sinks: List[TransactionalSink] = sinks
        self.keyed = bool(keyed)
        self.pin = int(pin)
        self.op_sig: List[str] = op_sig  # op type names, topology fence


class ElasticStreamJob:
    """An elastically-parallel recoverable topology: ONE replayable source
    fanning out to logical chains, each built FRESH per partition by a
    factory::

        job = ElasticStreamJob(
            source=TableSourceStreamOp(t, chunkSize=32),
            chains=[
                (lambda: [TumbleTimeWindowStreamOp(
                     timeCol="ts", windowTime=30.0, groupCols=["user"],
                     clause="sum(v) as sv")], [kafka_sink]),
                (lambda: [FtrlTrainStreamOp(...)], [datahub_sink]),
            ],
            checkpoint_dir="/jobs/ck/my-job", key_col="user",
            parallelism=2, epoch_chunks=4,
            rescale_at={3: 4},                  # or a controller, or both
            controller=BackpressureController(target_chunk_s=0.05))

    A chain whose every op is keyed by ``key_col`` shards rows by hash
    across all partitions; any other chain pins to one key group (its
    whole sub-stream runs on that group's owner partition, moving on
    rescale). ``rescale_at`` maps epoch → target parallelism (a
    deterministic schedule, replayed identically across crash restarts);
    the controller decides from live backpressure; and
    ``ElasticCoordinator.request_rescale`` triggers imperatively.
    """

    def __init__(self, source, chains: Sequence[Tuple[Callable[[], list],
                                                      Sequence[Any]]],
                 checkpoint_dir: str, *, key_col: Optional[str] = None,
                 parallelism: int = 2,
                 num_key_groups: int = DEFAULT_KEY_GROUPS,
                 epoch_chunks: int = 1, keep_snapshots: int = 3,
                 min_parallelism: int = 1,
                 max_parallelism: Optional[int] = None,
                 rescale_at: Optional[Dict[int, int]] = None,
                 controller: Optional[BackpressureController] = None,
                 publishers: Sequence[Any] = ()):
        if not chains:
            raise AkIllegalArgumentException("job needs >= 1 chain")
        if getattr(source, "_max_inputs", None) != 0:
            raise AkIllegalArgumentException(
                f"{type(source).__name__} is not a source op (it takes "
                "inputs); an elastic job starts from one replayable source")
        self.source = source
        self.checkpoint_dir = checkpoint_dir
        self.key_col = key_col
        self.num_key_groups = int(num_key_groups)
        if self.num_key_groups < 1:
            raise AkIllegalArgumentException("num_key_groups must be >= 1")
        self.epoch_chunks = max(1, int(epoch_chunks))
        self.keep_snapshots = keep_snapshots
        self.min_parallelism = max(1, int(min_parallelism))
        self.max_parallelism = min(
            int(max_parallelism) if max_parallelism else self.num_key_groups,
            self.num_key_groups)
        if self.min_parallelism > self.max_parallelism:
            raise AkIllegalArgumentException(
                f"min_parallelism={self.min_parallelism} > "
                f"max_parallelism={self.max_parallelism}")
        self.parallelism = int(parallelism)
        if not (self.min_parallelism <= self.parallelism
                <= self.max_parallelism):
            raise AkIllegalArgumentException(
                f"parallelism={self.parallelism} outside "
                f"[{self.min_parallelism}, {self.max_parallelism}]")
        self.rescale_at = {int(k): int(v)
                           for k, v in (rescale_at or {}).items()}
        self.controller = controller

        self.chain_specs: List[_ChainSpec] = []
        seen_sinks: set = set()
        probe_ops_all: List[Any] = []
        probe_by_chain: List[List[Any]] = []
        for ci, (factory, sinks) in enumerate(chains):
            if not callable(factory):
                raise AkIllegalArgumentException(
                    "each chain needs an ops FACTORY (fresh operator "
                    "instances per partition/generation), not instances")
            ops = list(factory())
            again = list(factory())
            if {id(o) for o in ops} & {id(o) for o in again}:
                raise AkIllegalArgumentException(
                    "the chain factory returned the same operator "
                    "instances twice; it must build FRESH ops per call "
                    "(generators are one-shot and partitions must not "
                    "share state)")
            for op in ops:
                self._check_op(op)
            probe_ops_all.extend(ops)
            probe_by_chain.append(ops)
            keyed = key_col is not None and \
                all(op.elastic_keyed(key_col) for op in ops)
            if not sinks:
                raise AkIllegalArgumentException("each chain needs >= 1 sink")
            tsinks = [s if isinstance(s, TransactionalSink)
                      else TransactionalSink(s, scope=self.checkpoint_dir)
                      for s in sinks]
            for s in tsinks:
                if not s.scope:
                    s.scope = self.checkpoint_dir
                if s.sink_id in seen_sinks:
                    raise AkIllegalArgumentException(
                        f"duplicate sink {s.sink_id!r}; every sink needs a "
                        "distinct target")
                seen_sinks.add(s.sink_id)
            self.chain_specs.append(_ChainSpec(
                factory, tsinks, keyed,
                key_group(f"chain{ci}", self.num_key_groups),
                [type(op).__name__ for op in ops]))
        if key_col is not None and \
                not any(s.keyed for s in self.chain_specs):
            # a typo'd key_col (or groupCols missing it) silently degrades
            # every chain to pinned-global: the job runs, but never shards
            # and a scale-out is a throughput no-op. Loud, counted warning.
            metrics.incr("elastic.no_keyed_chains")
            logger.warning(
                "key_col=%r matched NO chain (windows shard only when the "
                "key column is in their groupCols); every chain is pinned "
                "to one partition and rescaling will not add throughput. "
                "Check for a typo, or drop key_col for an all-global job.",
                key_col)
        # modelstream publishers: bind each to its chain's op (the probe
        # instances stand in for per-generation ops at validation time —
        # stamping them feeds the ALK109 pre-flight rule below). Keyed
        # chains are refused: their model state is split across partitions
        # at the barrier, so there is no one op to publish from.
        self.publishers = list(publishers or [])
        for pub in self.publishers:
            if not (0 <= pub.chain < len(probe_by_chain)) or \
                    not (0 <= pub.op_index < len(probe_by_chain[pub.chain])):
                raise AkIllegalArgumentException(
                    f"publisher {pub.name!r} binds chain {pub.chain} op "
                    f"{pub.op_index}, which this job does not have")
            pub.validate_target(probe_by_chain[pub.chain][pub.op_index],
                                keyed=self.chain_specs[pub.chain].keyed)
        # opt-in pre-flight: under ALINK_VALIDATE_PLAN the elastic rules
        # run too — ALK107 (stateful op without partition hooks) escalates
        # to error alongside ALK104, landing a structured report before
        # the bare per-op refusals above would
        from ..analysis import preflight

        preflight([source] + probe_ops_all, where="elastic.build",
                  recovery=True, elastic=True)

    @staticmethod
    def _check_op(op) -> None:
        if getattr(op, "_min_inputs", None) != 1 or \
                getattr(op, "_max_inputs", None) != 1:
            raise AkIllegalArgumentException(
                f"{type(op).__name__} is not a single-input stream op; "
                "elastic chains are linear (fan out via multiple "
                "chains/sinks instead)")
        if getattr(op, "_stateful_unhooked", False):
            raise AkIllegalArgumentException(
                f"{type(op).__name__} keeps cross-chunk state without "
                "state_snapshot/state_restore hooks; restoring it as "
                "stateless would silently break exactly-once.")
        if _has_snapshot_hooks(op) and not getattr(op, "_elastic_hooks",
                                                   False):
            raise AkIllegalArgumentException(
                f"{type(op).__name__} has snapshot hooks but no keyed-"
                "state hooks (state_partition/state_merge); an elastic "
                "job cannot redistribute its state across parallelism "
                "changes (rule ALK107). Implement the hooks or use "
                "GlobalElasticStateMixin for unkeyed accumulators.")

    def all_sinks(self) -> List[TransactionalSink]:
        return [s for spec in self.chain_specs for s in spec.sinks]


# ---------------------------------------------------------------------------
# Partition runners
# ---------------------------------------------------------------------------


class _ChainRunner:
    """One partition's instance-chain of one logical chain: pulls source
    chunks from the shared reader, routes its rows (keyed: per-key-group
    sub-chunks in ascending order; global: whole chunks), and buffers
    tagged outputs for the coordinator's canonical merge."""

    def __init__(self, ci: int, spec: _ChainSpec, part: int,
                 ranges: Sequence[Tuple[int, int]], cid: int,
                 ops: List[Any], job: ElasticStreamJob):
        self.ci = ci
        self.spec = spec
        self.part = part
        self.lo, self.hi = ranges[part]
        self.cid = cid
        self.ops = ops
        self.job = job
        self.outputs: List[Tuple[int, int, int, MTable]] = []
        self._tag: List[Tuple[int, int]] = [(-1, -1)]
        self._seq = 0

    def _consume(self, reader: _SharedSourceReader,
                 start: int) -> Iterator[MTable]:
        idx = start
        keyed = self.spec.keyed
        key_col, g = self.job.key_col, self.job.num_key_groups
        while True:
            chunk = reader.get(self.cid, idx)
            if chunk is _END:
                # flush emissions sort after all chunks, sub-ordered by
                # this partition's range start (ops flush key groups
                # ascending, so partition order == key-group order)
                self._tag[0] = (_FLUSH, self.lo if keyed else self.spec.pin)
                return
            maybe_fail("recovery", label=f"chunk{idx}")
            if keyed:
                for kg, sub in _split_chunk(chunk, key_col, g,
                                            self.lo, self.hi):
                    self._tag[0] = (idx, kg)
                    yield sub
            else:
                self._tag[0] = (idx, self.spec.pin)
                yield chunk
            idx += 1

    def chain_iter(self, reader: _SharedSourceReader,
                   start: int) -> Iterator[MTable]:
        it: Iterator[MTable] = self._consume(reader, start)
        for op in self.ops:
            it = op._stream_impl(it)
        return it

    def run(self, reader: _SharedSourceReader, it: Iterator[MTable],
            ctx=None) -> None:
        try:
            with attach_context(ctx):
                with trace_span(f"recovery.chain{self.ci}.p{self.part}") \
                        as sp:
                    for out in it:
                        c, kg = self._tag[0]
                        self.outputs.append((c, kg, self._seq, out))
                        self._seq += 1
                    if sp is not None:
                        sp.attrs["chunks_out"] = self._seq
        except _RescaleInterrupt:
            pass  # generation torn down at a quiescent barrier
        except BaseException as exc:
            reader.fail(exc)
        finally:
            reader.mark_done(self.cid)


# ---------------------------------------------------------------------------
# Coordinator
# ---------------------------------------------------------------------------


class ElasticCoordinator(CheckpointCoordinator):
    """Drives an :class:`ElasticStreamJob` under epoch snapshotting, and
    changes its parallelism at quiescent epoch barriers — manually
    (:meth:`request_rescale`), by schedule (``job.rescale_at``), or from
    backpressure (``job.controller``). The epoch manifest records the
    parallelism it was cut at plus key-range-partitioned state parts, so
    a crash anywhere around a rescale restarts on the committed side of
    it: before the manifest → the rescale never happened; after → the
    job resumes at the new parallelism."""

    def __init__(self, job: ElasticStreamJob,
                 store: Optional[SnapshotStore] = None):
        super().__init__(job, store)
        self.parallelism: int = job.parallelism
        self.ranges: List[Tuple[int, int]] = []
        self.runners: List[_ChainRunner] = []
        self._threads: List[threading.Thread] = []
        self._restored_parts: Optional[Dict[str, Any]] = None
        self._pending_parallelism: Optional[int] = None
        self._req_lock = threading.Lock()
        self._requested: Optional[int] = None

    # -- rescale triggers ----------------------------------------------------
    def request_rescale(self, parallelism: int) -> None:
        """Ask for a parallelism change at the next epoch barrier (thread-
        safe; the last request before the barrier wins)."""
        with self._req_lock:
            self._requested = int(parallelism)

    def _decide(self, stats: Dict[str, Any]) -> Optional[int]:
        with self._req_lock:
            target, self._requested = self._requested, None
        if target is None:
            target = self.job.rescale_at.get(int(stats["epoch"]))
        if target is None and self.job.controller is not None:
            target = self.job.controller.observe(stats)
        if target is None:
            return None
        clamped = max(self.job.min_parallelism,
                      min(int(target), self.job.max_parallelism))
        if clamped != int(target):
            logger.warning("rescale target %s clamped to %d", target,
                           clamped)
        if clamped == self.parallelism:
            metrics.incr("recovery.rescale_aborted")
            return None
        return clamped

    # -- restore hooks -------------------------------------------------------
    def _fence_manifest(self, manifest: Dict[str, Any]) -> None:
        super()._fence_manifest(manifest)
        job = self.job
        for field, have in (("num_key_groups", job.num_key_groups),
                            ("key_col", job.key_col)):
            if manifest.get(field) != have:
                raise AkIllegalStateException(
                    f"snapshot was cut with {field}="
                    f"{manifest.get(field)!r} but the job was rebuilt "
                    f"with {field}={have!r}; the key space must stay "
                    "fixed for the job's whole life")
        self.parallelism = int(manifest.get("parallelism",
                                            job.parallelism))

    def _apply_operator_states(self, blob: Dict[str, Any]) -> None:
        # instances don't exist yet — the generation build merges each
        # partition's parts into fresh ops
        self._restored_parts = blob.get("operators", {})

    # -- snapshot hooks ------------------------------------------------------
    def _manifest_extra(self) -> Dict[str, Any]:
        return {
            "parallelism": self._pending_parallelism or self.parallelism,
            "num_key_groups": self.job.num_key_groups,
            "key_col": self.job.key_col,
        }

    def _live_op(self, chain: int, op_index: int):
        """Publisher target in the CURRENT generation: a non-keyed chain
        (the only kind a publisher may bind — enforced at build) runs as
        exactly one pinned runner, so the instance is unambiguous."""
        for r in self.runners:
            if r.ci == chain:
                return r.ops[op_index]
        raise AkIllegalStateException(
            f"no live runner for publisher chain {chain}")

    def _logical_ops(self) -> Dict[str, List[Tuple[int, Any]]]:
        out: Dict[str, List[Tuple[int, Any]]] = {}
        for r in self.runners:
            for oi, op in enumerate(r.ops):
                key = f"chain{r.ci}.op{oi}.{type(op).__name__}"
                out.setdefault(key, []).append((r.part, op))
        return out

    def _gather_op_states(self) -> Dict[str, Any]:
        """Steady-epoch snapshot: each instance's full state filed under
        its own partition slot (ranges == current ranges)."""
        out: Dict[str, Any] = {}
        for key, instances in self._logical_ops().items():
            parts: List[List[Any]] = [[] for _ in self.ranges]
            stateful = False
            for part, op in instances:
                if not _has_snapshot_hooks(op):
                    continue
                snap = op.state_snapshot()
                if snap is not None:
                    parts[part].append(snap)
                    stateful = True
            if stateful:
                out[key] = {"ranges": [list(r) for r in self.ranges],
                            "parts": parts}
        return out

    def _partition_states(self, new_ranges: Sequence[Tuple[int, int]]
                          ) -> Dict[str, Any]:
        """Rescale redistribution: every live instance splits its state
        across the NEW ranges; parts destined for the same new partition
        collect into one merge list."""
        out: Dict[str, Any] = {}
        for key, instances in self._logical_ops().items():
            parts: List[List[Any]] = [[] for _ in new_ranges]
            stateful = False
            for _, op in instances:
                if not _has_snapshot_hooks(op):
                    continue
                blobs = op.state_partition(new_ranges)
                if len(blobs) != len(new_ranges):
                    raise AkIllegalStateException(
                        f"{type(op).__name__}.state_partition returned "
                        f"{len(blobs)} blobs for {len(new_ranges)} ranges")
                for j, b in enumerate(blobs):
                    if b is not None:
                        parts[j].append(b)
                        stateful = True
            if stateful:
                out[key] = {"ranges": [list(r) for r in new_ranges],
                            "parts": parts}
        return out

    # -- generation management -----------------------------------------------
    def _build_generation(self, ranges: Sequence[Tuple[int, int]],
                          parts: Optional[Dict[str, Any]]
                          ) -> List[_ChainRunner]:
        job = self.job
        runners: List[_ChainRunner] = []
        seen_keys: set = set()
        cid = 0
        for ci, spec in enumerate(job.chain_specs):
            part_ids = range(len(ranges)) if spec.keyed \
                else [owner_of(spec.pin, ranges)]
            for part in part_ids:
                ops = list(spec.factory())
                if [type(o).__name__ for o in ops] != spec.op_sig:
                    raise AkIllegalStateException(
                        f"chain {ci} factory changed its topology "
                        f"({spec.op_sig} -> "
                        f"{[type(o).__name__ for o in ops]})")
                for oi, op in enumerate(ops):
                    key = f"chain{ci}.op{oi}.{type(op).__name__}"
                    seen_keys.add(key)
                    op.set_key_context(
                        job.key_col if spec.keyed else None,
                        job.num_key_groups, pin_group=spec.pin)
                    if not parts:
                        continue
                    rec = parts.get(key)
                    if rec is None:
                        continue
                    if [tuple(r) for r in rec["ranges"]] != \
                            [tuple(r) for r in ranges]:
                        raise AkIllegalStateException(
                            f"stored state ranges for {key!r} do not "
                            "match the generation's partition ranges")
                    blobs = rec["parts"][part] if spec.keyed else \
                        [b for lst in rec["parts"] for b in lst]
                    if blobs:
                        op.state_merge(blobs)
                runners.append(_ChainRunner(ci, spec, part, ranges, cid,
                                            ops, job))
                cid += 1
        if parts:
            orphans = set(parts) - seen_keys
            if orphans:
                raise AkIllegalStateException(
                    f"snapshot state for {sorted(orphans)} has no "
                    "matching operator; restart needs the same job "
                    "topology")
        return runners

    def _start_threads(self, reader: _SharedSourceReader,
                       start: int) -> List[threading.Thread]:
        ctx = capture_context()
        threads = []
        for r in self.runners:
            it = r.chain_iter(reader, start)
            t = threading.Thread(
                target=r.run, args=(reader, it, ctx),
                name=f"alink-elastic-c{r.ci}p{r.part}", daemon=True)
            threads.append(t)
        for t in threads:
            t.start()
        self._threads = threads
        return threads

    def _stage_outputs(self) -> None:
        """Merge every partition's buffered emissions in canonical
        (chunk, key group, seq) order and stage them into the chain's
        transactional sinks — the order is invariant to parallelism, so
        the committed sink sequence is too."""
        for ci, spec in enumerate(self.job.chain_specs):
            entries: List[Tuple[int, int, int, MTable]] = []
            for r in self.runners:
                if r.ci == ci and r.outputs:
                    entries.extend(r.outputs)
                    r.outputs = []
            entries.sort(key=lambda e: (e[0], e[1], e[2]))
            for _, _, _, out in entries:
                for s in spec.sinks:
                    s.stage(out)

    # -- rescale -------------------------------------------------------------
    def _rescale(self, epoch: int, next_offset: int, target: int,
                 summary: Dict[str, Any],
                 reader: _SharedSourceReader) -> None:
        old_p = self.parallelism
        t0 = time.perf_counter()
        with trace_span("recovery.rescale", epoch=epoch,
                        from_parallelism=old_p, to_parallelism=target) as sp:
            maybe_fail("rescale", label=f"epoch{epoch}.pre_redistribute")
            new_ranges = partition_ranges(self.job.num_key_groups, target)
            parts = self._partition_states(new_ranges)
            maybe_fail("rescale", label=f"epoch{epoch}.mid_redistribute")
            # the epoch manifest (cut at the new parallelism, with the
            # already-partitioned parts) is the rescale's atomic commit
            # point: a crash before it restarts at the old parallelism
            # with the previous snapshot; after it, at the new one
            self._pending_parallelism = target
            try:
                self._cut_epoch(epoch, next_offset, False, op_states=parts)
            finally:
                self._pending_parallelism = None
            maybe_fail("rescale", label=f"epoch{epoch}.pre_resume")
            # tear down the old generation (parked at the barrier; the
            # interrupt unwinds chains WITHOUT their end-of-stream flush)
            reader.interrupt()
            for t in self._threads:
                t.join(timeout=60)
            self.parallelism = target
            self.ranges = list(new_ranges)
            self.runners = self._build_generation(new_ranges, parts)
            reader.resize(len(self.runners), next_offset)
            self._start_threads(reader, next_offset)
            if sp is not None:
                sp.attrs["partitions"] = len(new_ranges)
        dt = time.perf_counter() - t0
        metrics.incr("recovery.rescale_out" if target > old_p
                     else "recovery.rescale_in")
        metrics.add_time("recovery.rescale_s", dt)
        metrics.observe("recovery.rescale_epoch_s", dt)
        summary["rescales"].append({"epoch": epoch, "from": old_p,
                                    "to": target,
                                    "latency_s": round(dt, 6)})
        logger.info("rescaled %d -> %d at epoch %d barrier (%.1f ms)",
                    old_p, target, epoch, dt * 1e3)

    # -- run -----------------------------------------------------------------
    def _run_inner(self) -> Dict[str, Any]:
        job = self.job
        summary: Dict[str, Any] = {
            "complete": False, "restored": False, "epochs": 0,
            "sink_replays": 0, "replayed_chunks": 0,
            "rescales": [], "epoch_stats": [], "parallelism": None,
        }
        start_epoch, start_offset = self._restore(summary)
        self._resume_publishers()
        if summary["complete"]:
            summary["parallelism"] = self.parallelism
            return summary
        k = job.epoch_chunks
        self.ranges = partition_ranges(job.num_key_groups, self.parallelism)
        self.runners = self._build_generation(self.ranges,
                                              self._restored_parts)
        self._restored_parts = None
        reader = _SharedSourceReader(job.source._stream_impl(),
                                     n_consumers=len(self.runners),
                                     skip_before=start_offset)
        self._start_threads(reader, start_offset)
        epoch = start_epoch
        prev_offset = start_offset
        try:
            while True:
                t_ep = time.perf_counter()
                budget = (epoch + 1) * k
                reader.set_budget(budget)
                reader.wait_barrier(budget)
                final = reader.end is not None and reader.all_done()
                next_offset = budget if reader.end is None \
                    else min(budget, reader.end)
                self._stage_outputs()
                wall = time.perf_counter() - t_ep
                chunks = max(0, next_offset - prev_offset)
                if chunks:
                    metrics.observe("stream.chunk_s", wall / chunks)
                stats = {"epoch": epoch, "wall_s": wall, "chunks": chunks,
                         "parallelism": self.parallelism,
                         "min_parallelism": job.min_parallelism,
                         "max_parallelism": job.max_parallelism}
                summary["epoch_stats"].append(
                    {"epoch": epoch, "wall_s": round(wall, 6),
                     "chunks": chunks, "parallelism": self.parallelism})
                if len(summary["epoch_stats"]) > 1024:  # long-lived jobs:
                    del summary["epoch_stats"][:-1024]  # keep the tail
                target = None if final else self._decide(stats)
                # model publish rides the SAME parked barrier as the epoch
                # cut (and precedes a rescale's state redistribution, so
                # the op still holds this epoch's undisturbed state)
                self._publish_epoch(epoch, final)
                if target is not None:
                    self._rescale(epoch, next_offset, target, summary,
                                  reader)
                else:
                    self._cut_epoch(epoch, next_offset, final)
                self._swap_published(epoch, t_ep)
                summary["epochs"] += 1
                prev_offset = next_offset
                epoch += 1
                if final:
                    break
        except BaseException as exc:
            reader.fail(exc)  # unblock parked chains so threads exit
            raise
        finally:
            for t in self._threads:
                t.join(timeout=60)
            summary["replayed_chunks"] = reader.replayed
        summary["complete"] = True
        summary["source_chunks"] = reader.end
        summary["final_epoch"] = epoch - 1
        summary["parallelism"] = self.parallelism
        return summary


ElasticStreamJob._coordinator_cls = ElasticCoordinator


def elastic_summary() -> Dict[str, Any]:
    """One-call readout of the elastic-streaming counters (the WebUI
    recovery line reads it): rescale events and latency, plus the current
    backpressure lag gauge."""
    out: Dict[str, Any] = {
        "rescale_out": metrics.counter("recovery.rescale_out"),
        "rescale_in": metrics.counter("recovery.rescale_in"),
        "rescale_aborted": metrics.counter("recovery.rescale_aborted"),
        "lag_s": metrics.gauge("stream.lag_s"),
    }
    stats = metrics.timer_stats("recovery.rescale_s")
    if stats:
        out["rescale_s"] = stats
    return out
