"""APS analog: model-axis sharded embedding tables with O(B·D) pull/push.

Capability parity with the reference's Alink Parameter Server (reference:
core/src/main/java/com/alibaba/alink/operator/common/aps/ApsEnv.java:39-370 —
mini-batch pull→train→push with the model partitioned by key across tasks;
ApsFuncIndex4Pull / ApsFuncTrain / ApsFuncUpdateModel; used by
operator/batch/huge/impl/Word2VecImpl.java:82-91 and the DeepWalk/Node2Vec/
MetaPath2Vec embedding family).

TPU-first re-design: there are no PS processes. The embedding table is a
``jax.Array`` row-sharded over the ``model`` mesh axis (each device owns
V/M contiguous rows — the APS key partition). Inside ``shard_map``, pull and
push route ids to the shard that OWNS them, so per-device wire bytes stay
~``slack·B·D`` no matter how many shards the table spans (the reference's
point-to-point pull/push RPCs, expressed as fixed-shape ``all_to_all``):

- **pull(ids)**: dedup the id batch, bucket unique ids by owning shard into
  fixed-capacity buckets of ``ceil(slack·B/M)`` rows, one ``all_to_all``
  (ids out), a local gather on each owner, one ``all_to_all`` back (rows
  home). This is the reference's ApsFuncIndex4Pull/pull RPC.
- **push(ids, grads)**: bucket (id, grad) rows by owner — ids ride the same
  ``all_to_all`` payload bitcast into a trailing lane — then each owner
  scatter-adds exactly the updates for its rows. Only the touched (B, D)
  grads move; the table itself never rides a collective.
- **Overflow**: the installed JAX has no ragged ``all_to_all``, so buckets
  are fixed-capacity. Ids past capacity (a pathologically skewed batch) are
  counted in the ``aps.bucket_overflows`` metric and served by the legacy
  all-gather path (:func:`pull_allgather`/:func:`push_allgather`) — inside
  a mesh-agreed ``lax.cond`` so the steady state never pays for it. Pull
  patches up the overflow remainder only; push re-applies the whole batch
  from the pre-push table (a remainder patch-up would split a duplicated
  row's contributions across two scatters and reassociate the float adds).
  Capacity slack is the ``ALINK_APS_BUCKET_SLACK`` knob (default 2.0).

Both routed paths are bit-identical to the all-gather reference: pull is
pure data movement, and push pre-combines duplicates with the identical
dedup computation and replays the reference's source-device scatter-add
order on each owner.

Memory per device is V/M rows — vocabularies larger than one chip's HBM
train fine, which is the whole point of the reference's "huge" family.
"""

from __future__ import annotations

import math
from typing import Callable, Optional

import numpy as np

from .mesh import AXIS_MODEL, default_mesh, make_mesh, pad_to_multiple
from .shardmap import axis_size


def model_mesh(n_devices: Optional[int] = None):
    """1-D mesh over the ``model`` axis — APS workers are both data and
    model holders (reference: ApsEnv runs pull/train/push on the same tasks)."""
    import jax

    devices = jax.devices() if n_devices is None else jax.devices()[:n_devices]
    return make_mesh([(AXIS_MODEL, len(devices))], devices)


def shard_table(mesh, table: np.ndarray, axis: str = AXIS_MODEL):
    """Place (V, D) onto the mesh row-sharded over ``axis``; pads V to a
    multiple of the axis size. Returns (sharded_array, padded_rows)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    m = mesh.shape[axis]
    v_pad = pad_to_multiple(table.shape[0], m)
    if v_pad != table.shape[0]:
        table = np.concatenate(
            [table, np.zeros((v_pad - table.shape[0],) + table.shape[1:],
                             table.dtype)])
    return jax.device_put(table, NamedSharding(mesh, P(axis))), v_pad


def bucket_slack(override: Optional[float] = None) -> float:
    """Bucket over-provisioning factor (``ALINK_APS_BUCKET_SLACK``, ≥ 1)."""
    if override is not None:
        return max(1.0, float(override))
    from ..common.env import env_float

    return max(1.0, env_float("ALINK_APS_BUCKET_SLACK", 2.0))


def bucket_capacity(batch: int, num_shards: int,
                    slack: Optional[float] = None) -> int:
    """Fixed per-owner bucket capacity: ``ceil(slack·B/M)`` rows."""
    return max(1, int(math.ceil(bucket_slack(slack) * batch / num_shards)))


def _note_overflow(n, dev) -> None:
    # fires only when the fallback branch actually executes; count once per
    # step (device 0 speaks for the psum-agreed total)
    if int(dev) == 0:
        from ..common.metrics import metrics

        metrics.incr("aps.bucket_overflows", int(n))


def _bucket_positions(owner_c):
    """Per-element arrival rank within its owner bucket, preserving batch
    order (stable) so routed scatter-adds replay the legacy accumulation
    order."""
    import jax.numpy as jnp

    n = owner_c.shape[0]
    order = jnp.argsort(owner_c)                    # jax sorts are stable
    sorted_owner = owner_c[order]
    pos_sorted = jnp.arange(n, dtype=jnp.int32) - jnp.searchsorted(
        sorted_owner, sorted_owner).astype(jnp.int32)
    return jnp.zeros(n, jnp.int32).at[order].set(pos_sorted)


def pull_allgather(table_l, ids, axis: str, rows_per_shard: int):
    """Legacy O(M·B·D) pull: ``all_gather`` every device's ids + masked
    local gather + ``psum``. Kept as the bit-exactness reference and as the
    bucket-overflow fallback path."""
    import jax
    import jax.numpy as jnp

    m = jax.lax.axis_index(axis)
    ids_all = jax.lax.all_gather(ids, axis)               # (M, B)
    local_idx = jnp.clip(ids_all - m * rows_per_shard, 0, rows_per_shard - 1)
    owned = (ids_all // rows_per_shard) == m              # (M, B)
    contrib = table_l[local_idx] * owned[..., None]       # (M, B, D)
    full = jax.lax.psum(contrib, axis)                    # (M, B, D)
    return jax.lax.dynamic_index_in_dim(full, m, axis=0, keepdims=False)


def _dedup_batch(ids, grads, fill):
    """Per-device dedup: combine duplicate ids' grads onto the (sorted)
    unique id list. Both push paths run this identical computation, so the
    duplicate-combination bits agree between them by construction."""
    import jax.numpy as jnp

    b = ids.shape[0]
    uid, inv = jnp.unique(ids, return_inverse=True, size=b,
                          fill_value=jnp.int32(fill))
    g = jnp.zeros((b,) + grads.shape[1:], grads.dtype).at[inv].add(grads)
    return uid, g


def _push_gathered(table_l, uid, grads, axis: str, rows_per_shard: int,
                   scale: float):
    """all_gather + local scatter-add of an already-deduped batch."""
    import jax
    import jax.numpy as jnp

    m = jax.lax.axis_index(axis)
    ids_all = jax.lax.all_gather(uid, axis).reshape(-1)          # (M*B,)
    grads_all = jax.lax.all_gather(grads, axis)                  # (M, B, D)
    grads_all = grads_all.reshape(-1, grads.shape[-1])
    local_idx = ids_all - m * rows_per_shard
    owned = (local_idx >= 0) & (local_idx < rows_per_shard)
    # foreign rows are parked at the OOB index and dropped, so each owned
    # row's scatter-add reduction group holds exactly its true
    # contributions in source-device order — masked-zero updates would
    # perturb XLA's reduction grouping at the ulp level
    lidx = jnp.where(owned, local_idx, rows_per_shard)
    return table_l.at[lidx].add(-scale * grads_all, mode="drop")


def push_allgather(table_l, ids, grads, axis: str, rows_per_shard: int,
                   scale: float = 1.0):
    """Legacy O(M·B·D) push: per-device dedup, then ``all_gather`` of
    (ids, grads) + masked local scatter-add. Reference/fallback twin of
    :func:`push`."""
    M = axis_size(axis)
    uid, g = _dedup_batch(ids, grads, M * rows_per_shard)
    return _push_gathered(table_l, uid, g, axis, rows_per_shard, scale)


def apply_gathered_replicated(table, ids, grads, axis: str, num_rows: int,
                              scale):
    """Replicated-table twin of :func:`push` — the "host engine" update.

    Per-device dedup, then ``all_gather`` of (uid, grads) + a full-table
    scatter-add applied identically on every device. Each row's scatter-add
    reduction group holds exactly its true contributions in source-device
    order — the same per-row add sequence the routed/all-gather sharded
    pushes replay — so a replicated table driven through this function
    evolves bit-identically to a model-sharded one driven through
    :func:`push` on an equal-size mesh. That is the parity contract the
    huge-embedding engines (``ALINK_HUGE_ENGINE=sharded|host``) are pinned
    against. Ids outside ``[0, num_rows)`` (dedup padding) park at the OOB
    row and drop."""
    import jax
    import jax.numpy as jnp

    uid, g = _dedup_batch(ids, grads, num_rows)
    ids_all = jax.lax.all_gather(uid, axis).reshape(-1)
    g_all = jax.lax.all_gather(g, axis).reshape(-1, g.shape[-1])
    lidx = jnp.where((ids_all >= 0) & (ids_all < num_rows), ids_all, num_rows)
    return table.at[lidx].add(-scale * g_all, mode="drop")


def aps_summary() -> dict:
    """One-call health readout of the APS exchange + hot-key cache counters
    (the block the WebUI profile panel reads)."""
    from ..common.metrics import metrics

    hits = metrics.counter("aps.cache_hits")
    misses = metrics.counter("aps.cache_misses")
    return {
        "cache_hits": hits,
        "cache_misses": misses,
        "cache_evictions": metrics.counter("aps.cache_evictions"),
        "cache_hit_rate": round(hits / (hits + misses), 4)
        if hits + misses else None,
        "bucket_overflows": metrics.counter("aps.bucket_overflows"),
    }


def _export_aps_gauges() -> None:
    # labeled gauges alongside the raw *_total counters: one family per
    # surface (cache events / exchange health), refreshed at scrape time
    from ..common.metrics import metrics

    for event in ("hits", "misses", "evictions"):
        metrics.set_gauge("aps.cache_events",
                          metrics.counter(f"aps.cache_{event}"), event=event)
    metrics.set_gauge("aps.health", metrics.counter("aps.bucket_overflows"),
                      event="bucket_overflows")


def _register_gauges() -> None:
    from ..common.metrics import metrics

    metrics.register_export_hook(_export_aps_gauges)


_register_gauges()


def pull(table_l, ids, axis: str, rows_per_shard: int, *,
         slack: Optional[float] = None, cap: Optional[int] = None):
    """Inside shard_map: fetch rows for this device's ``ids`` from whichever
    shard owns them. ``table_l``: (V/M, D) local shard; ``ids``: (B,) global
    row ids. Returns (B, D).

    Owner-routed: per-device comm is ~``slack·B·D`` regardless of the model
    axis size (see module docstring); ids whose bucket overflows fall back
    to :func:`pull_allgather` under a mesh-agreed ``cond``. ``cap`` overrides
    the per-owner bucket capacity (the hot-key cache sizes the cold
    remainder's buckets from the empirical tail mass — see
    ``parallel/hotcache.py``); out-of-range ids (e.g. the cache's parked
    sentinel ``M·rows``) are dropped and read back as zero rows.
    """
    import jax
    import jax.numpy as jnp

    M = axis_size(axis)
    B = int(ids.shape[0])
    rows = rows_per_shard
    cap = bucket_capacity(B, M, slack) if cap is None else max(1, int(cap))
    m = jax.lax.axis_index(axis)
    ids = ids.astype(jnp.int32)

    # dedup: a batch usually touches far fewer unique rows than B
    uid, inv = jnp.unique(ids, return_inverse=True, size=B,
                          fill_value=jnp.int32(M * rows))
    owner = uid // rows
    valid = (owner >= 0) & (owner < M)
    owner_c = jnp.where(valid, owner, M)        # parked at OOB row M → drop
    pos = _bucket_positions(owner_c)
    in_bucket = valid & (pos < cap)
    ovf = valid & (pos >= cap)

    send = jnp.zeros((M, cap), jnp.int32).at[owner_c, pos].set(
        uid, mode="drop")
    recv = jax.lax.all_to_all(send, axis, 0, 0, tiled=True)   # ids asked of me
    served = table_l[jnp.clip(recv - m * rows, 0, rows - 1)]  # (M, cap, D)
    home = jax.lax.all_to_all(served, axis, 0, 0, tiled=True)
    vals = home[jnp.clip(owner_c, 0, M - 1), jnp.clip(pos, 0, cap - 1)]
    vals = jnp.where(in_bucket[:, None], vals, jnp.zeros_like(vals))

    if cap >= B:            # overflow statically impossible
        return vals[inv]

    n_ovf = jax.lax.psum(ovf.sum(), axis)

    def _fallback(_):
        jax.debug.callback(_note_overflow, n_ovf, m)
        return pull_allgather(table_l, ids, axis, rows)

    fb = jax.lax.cond(
        n_ovf > 0, _fallback,
        lambda _: jnp.zeros((B,) + table_l.shape[1:], table_l.dtype), None)
    return jnp.where(ovf[inv][:, None], fb, vals[inv])


def push(table_l, ids, grads, axis: str, rows_per_shard: int,
         scale: float = 1.0, *, slack: Optional[float] = None):
    """Inside shard_map: apply ``-scale * grads`` for ``ids`` to the owning
    shards — per-device dedup, then owner-routed (combined grads ride one
    ``all_to_all`` with their id bitcast into a trailing lane; each owner
    scatter-adds its rows).

    Bit-identical to :func:`push_allgather`: duplicates are pre-combined by
    the same dedup computation, and routed rows land on each owner in
    source-device order, replaying the reference's scatter-add accumulation
    order. On bucket overflow the fallback ``cond`` re-applies the WHOLE
    batch from the pre-push table via the all-gather path (discarding the
    routed result) — a remainder-only patch-up would interleave a
    duplicated row's contributions across two scatters and break
    bit-exactness. Steady state never takes that branch.
    """
    import jax
    import jax.numpy as jnp

    M = axis_size(axis)
    B = int(ids.shape[0])
    D = int(grads.shape[-1])
    rows = rows_per_shard
    cap = bucket_capacity(B, M, slack)
    m = jax.lax.axis_index(axis)
    ids = ids.astype(jnp.int32)

    uid, g = _dedup_batch(ids, grads, M * rows)
    owner = uid // rows
    valid = (owner >= 0) & (owner < M)
    owner_c = jnp.where(valid, owner, M)
    pos = _bucket_positions(owner_c)
    ovf = valid & (pos >= cap)

    # bucket padding carries id M·rows (owned by nobody → dropped on the
    # receiving side) and zero grads
    send_ids = jnp.full((M, cap), jnp.int32(M * rows)).at[owner_c, pos].set(
        uid, mode="drop")
    send_g = jnp.zeros((M, cap, D), g.dtype).at[owner_c, pos].set(
        g, mode="drop")
    if g.dtype == jnp.float32:
        payload = jnp.concatenate(
            [send_g,
             jax.lax.bitcast_convert_type(send_ids, jnp.float32)[..., None]],
            axis=-1)
        rec = jax.lax.all_to_all(payload, axis, 0, 0, tiled=True)
        rg = rec[..., :D].reshape(M * cap, D)
        rid = jax.lax.bitcast_convert_type(
            rec[..., D], jnp.int32).reshape(M * cap)
    else:                   # non-32-bit grads: ids ride their own collective
        rid = jax.lax.all_to_all(
            send_ids, axis, 0, 0, tiled=True).reshape(M * cap)
        rg = jax.lax.all_to_all(
            send_g, axis, 0, 0, tiled=True).reshape(M * cap, D)

    local = rid - m * rows
    owned = (local >= 0) & (local < rows)
    # same OOB-park-and-drop trick as _push_gathered: a row's reduction
    # group must contain exactly its true contributions, in the same order
    routed = table_l.at[jnp.where(owned, local, rows)].add(
        -scale * rg, mode="drop")

    if cap >= B:            # overflow statically impossible
        return routed

    n_ovf = jax.lax.psum(ovf.sum(), axis)

    def _fallback(args):
        t0, _ = args
        jax.debug.callback(_note_overflow, n_ovf, m)
        return _push_gathered(t0, uid, g, axis, rows, scale)

    return jax.lax.cond(n_ovf > 0, _fallback, lambda args: args[1],
                        (table_l, routed))


class ShardedEmbedding:
    """Host-side handle for a model-sharded (V, D) table.

    The table lives device-resident between training calls (the reference
    keeps the APS model in task memory across iteration blocks,
    ApsEnv.java:198-327); ``to_numpy()`` is the final persist
    (persistentModel:328)."""

    def __init__(self, mesh, vocab_size: int, dim: int,
                 init: Optional[Callable[[np.random.Generator], np.ndarray]] = None,
                 seed: int = 0, axis: str = AXIS_MODEL):
        self.mesh = mesh
        self.axis = axis
        self.vocab_size = vocab_size
        self.dim = dim
        rng = np.random.default_rng(seed)
        table = (init(rng) if init is not None
                 else ((rng.random((vocab_size, dim)) - 0.5) / dim)
                 .astype(np.float32))
        self.array, self.padded_rows = shard_table(mesh, table, axis)
        self.rows_per_shard = self.padded_rows // mesh.shape[axis]

    def to_numpy(self) -> np.ndarray:
        import jax

        return np.asarray(jax.device_get(self.array))[:self.vocab_size]

    def shard_shapes(self):
        return [tuple(s.data.shape) for s in self.array.addressable_shards]

    def save(self, path: str):
        """Persist the table as a .ak model file (the APS persistentModel
        analog, reference: ApsEnv.java:328-366)."""
        from ..common.model import model_to_table
        from ..io.ak import write_ak

        meta = {"modelName": "ShardedEmbedding",
                "vocabSize": self.vocab_size, "dim": self.dim}
        write_ak(path, model_to_table(meta, {"table": self.to_numpy()}))

    @staticmethod
    def load(mesh, path: str, axis: str = AXIS_MODEL) -> "ShardedEmbedding":
        """Restore a saved table back onto the mesh, re-sharded."""
        from ..common.model import table_to_model
        from ..io.ak import read_ak

        meta, arrays = table_to_model(read_ak(path))
        handle = ShardedEmbedding(mesh, meta["vocabSize"], meta["dim"],
                                  init=lambda rng: arrays["table"]
                                  .astype(np.float32), axis=axis)
        return handle
