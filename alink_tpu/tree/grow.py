"""Level-wise histogram tree growth + GBDT / RandomForest training loops.

(reference: operator/common/tree/parallelcart/BaseGbdtTrainBatchOp.java:408 —
the boosting ICQ program; ConstructLocalHistogram.java — per-worker histogram;
CalcFeatureGain.java — split search; communication/ReduceScatter.java —
histogram exchange; BaseRandomForestTrainBatchOp.java:221 — forest BSP.)

The per-level kernel is one jit+shard_map program: local ``segment_sum``
histograms -> one ``psum`` (the ReduceScatter/AllReduceT analog) -> vectorized
cumsum gain -> split argmax -> sample routing. It compiles once per tree level
and is reused across every tree, boosting iteration, and class.

Trees are perfect binary trees of fixed depth (static shapes): internal nodes
in heap layout (2^D - 1), leaves 2^D. A node that doesn't split stores
feature -1 — samples route left and both children inherit its statistics.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import numpy as np

from ..parallel.mesh import AXIS_DATA, default_mesh
from ..parallel.shardmap import shard_map
from .binning import apply_bins, quantile_bins


# ---------------------------------------------------------------------------
# per-level split kernel
# ---------------------------------------------------------------------------


def _split_search(hg, hh, hc, fmask, l2, min_samples, min_gain):
    """Histograms (L, d, B) -> (feat (L,), thr (L,)). THE split contract:
    cumsum left/right gains, min-child-count + last-bin masks, flat argmax;
    feat -1 = no split. Shared by the per-level kernel (forest) and the
    fused GBDT program so the semantics cannot drift."""
    import jax.numpy as jnp

    L, d, B = hg.shape
    GL = jnp.cumsum(hg, axis=-1)
    HL = jnp.cumsum(hh, axis=-1)
    CL = jnp.cumsum(hc, axis=-1)
    G, H, C = GL[..., -1:], HL[..., -1:], CL[..., -1:]
    GR, HR, CR = G - GL, H - HL, C - CL
    gain = (GL * GL / (HL + l2) + GR * GR / (HR + l2) - G * G / (H + l2))
    ok = (CL >= min_samples) & (CR >= min_samples)
    # last bin position means "everything left" — not a split
    ok = ok & (jnp.arange(B)[None, None, :] < B - 1)
    gain = jnp.where(ok & (fmask[None, :, None] > 0), gain, -jnp.inf)
    flat = gain.reshape(L, d * B)
    best = jnp.argmax(flat, axis=1)
    best_gain = jnp.take_along_axis(flat, best[:, None], 1)[:, 0]
    feat = jnp.where(best_gain > min_gain, best // B, -1).astype(jnp.int32)
    thr = jnp.where(best_gain > min_gain, best % B, B - 1).astype(jnp.int32)
    return feat, thr


def _route(bins, node, feat, thr):
    """Send each sample to its child: f<0 routes left (no split)."""
    import jax.numpy as jnp

    f_s = feat[node]
    t_s = thr[node]
    safe_f = jnp.maximum(f_s, 0)
    x_bin = jnp.take_along_axis(bins, safe_f[:, None], 1)[:, 0]
    go_left = (f_s < 0) | (x_bin <= t_s)
    return node * 2 + (1 - go_left.astype(jnp.int32))


def _build_level_fn(mesh, num_nodes: int, num_bins: int, l2: float,
                    min_samples: float, min_gain: float,
                    pallas_on: bool, interp: bool):
    """Build the jitted level kernel for a given node count."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from .pallas_hist import pallas_histogram

    axis = AXIS_DATA
    L, B = num_nodes, num_bins

    def body(bins, g, h, c, node, fmask):
        bins = bins.astype(jnp.int32)  # may arrive uint8 (_compact_bins)
        d = bins.shape[1]
        ids = node[:, None] * B + bins  # (n, d) in [0, L*B)

        if pallas_on:
            def seg(vals):  # pallas VMEM-resident histogram (pallas_hist.py)
                flat = pallas_histogram(ids, vals, num_segments=L * B,
                                        interpret=interp)   # (L*B, d)
                return flat.reshape(L, B, d).transpose(0, 2, 1)
        else:
            def seg(vals):  # (n,) -> (d, L*B) -> (L, d, B)
                out = jax.vmap(
                    lambda col: jax.ops.segment_sum(
                        vals, col, num_segments=L * B),
                    in_axes=1,
                )(ids)
                return out.reshape(d, L, B).transpose(1, 0, 2)

        hg = jax.lax.psum(seg(g), axis)
        hh = jax.lax.psum(seg(h), axis)
        hc = jax.lax.psum(seg(c), axis)
        feat, thr = _split_search(hg, hh, hc, fmask, l2, min_samples,
                                  min_gain)
        return feat, thr, _route(bins, node, feat, thr)

    return jax.jit(
        shard_map(
            body,
            mesh=mesh,
            in_specs=(P(axis), P(axis), P(axis), P(axis), P(axis), P()),
            out_specs=(P(), P(), P(axis)),
            check_vma=False,
        )
    )


def _level_fn(mesh, num_nodes: int, num_bins: int, l2: float,
              min_samples: float, min_gain: float):
    """Process-wide cached level kernel (common/jitcache.py). The pallas
    flags enter the key, so flipping them builds a distinct program rather
    than reusing a kernel that captured the old flag at build time."""
    from ..common.jitcache import cached_jit
    from .pallas_hist import interpret_mode, use_pallas_hist

    return cached_jit("tree.level", _build_level_fn,
                      int(num_nodes), int(num_bins), float(l2),
                      float(min_samples), float(min_gain),
                      bool(use_pallas_hist()), bool(interpret_mode()),
                      mesh=mesh)


def _clear_level_cache():
    from ..common.jitcache import clear_kernel

    clear_kernel("tree.level")


_level_fn.cache_clear = _clear_level_cache  # back-compat with the lru era


def _build_leaf_fn(mesh, num_leaves: int, l2: float):
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    axis = AXIS_DATA

    def body(g, h, node):
        sg = jax.lax.psum(
            jax.ops.segment_sum(g, node, num_segments=num_leaves), axis
        )
        sh = jax.lax.psum(
            jax.ops.segment_sum(h, node, num_segments=num_leaves), axis
        )
        return -sg / (sh + l2)

    return jax.jit(
        shard_map(
            body, mesh=mesh, in_specs=(P(axis), P(axis), P(axis)),
            out_specs=P(), check_vma=False,
        )
    )


def _leaf_fn(mesh, num_leaves: int, l2: float):
    from ..common.jitcache import cached_jit

    return cached_jit("tree.leaf", _build_leaf_fn,
                      int(num_leaves), float(l2), mesh=mesh)


# Kernels are cached by a structural mesh fingerprint (axes, shape, device
# ids) so equivalent meshes share compiles and fresh-mesh-per-job services
# don't grow the cache unboundedly — the registry now lives in
# common/jitcache.py (one representative mesh per fingerprint, shared by
# every kernel family in the process). ``_mesh_key`` stays as an alias.
def _mesh_key(mesh) -> tuple:
    from ..common.jitcache import mesh_fingerprint

    return mesh_fingerprint(mesh)


def _build_predict_fn(depth: int):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def run(X, feats, thrs, leaves, base_score):
        n = X.shape[0]

        def one_tree(f, t, lv):
            node = jnp.zeros(n, jnp.int32)
            pos = jnp.zeros(n, jnp.int32)  # heap index of current node
            for _ in range(depth):
                fs = f[pos]
                ts = t[pos]
                safe = jnp.maximum(fs, 0)
                x = jnp.take_along_axis(X, safe[:, None], 1)[:, 0]
                left = (fs < 0) | (x <= ts)
                node = node * 2 + (1 - left.astype(jnp.int32))
                pos = 2 * pos + 1 + (1 - left.astype(jnp.int32))
            return lv[:, node]  # (K, n)

        scores = jax.vmap(one_tree)(feats, thrs, leaves)  # (T, K, n)
        return scores.sum(0).T + base_score[None, :]

    return run


def _predict_fn(depth: int):
    from ..common.jitcache import cached_jit

    return cached_jit("tree.predict", _build_predict_fn, int(depth))


# ---------------------------------------------------------------------------
# ensemble container
# ---------------------------------------------------------------------------


@dataclass
class TreeEnsemble:
    """Perfect-depth trees in heap layout. feats/thrs: (T, 2^D - 1);
    leaves: (T, K, 2^D) — K output dims (1 for binary/regression)."""

    depth: int
    feats: np.ndarray
    thrs: np.ndarray  # raw-value thresholds (x <= thr goes left)
    leaves: np.ndarray
    base_score: np.ndarray  # (K,)
    task: str  # "regression" | "binary" | "multiclass"
    labels: Optional[list] = None
    feature_cols: Optional[list] = None
    vector_col: Optional[str] = None

    def raw_predict(self, X: np.ndarray, precision=None) -> np.ndarray:
        """(n, K) raw scores — sum of leaf values + base. The jitted traversal
        takes the tree arrays as arguments (not constants) and is cached per
        depth, so repeat predicts and different ensembles share one compile;
        rows are bucket-padded (tree routing is row-wise, so the sliced
        result is bit-identical) so batch-size sweeps reuse one program.

        ``precision`` is the serving quantization policy: int8 runs the
        weight-only leaf-table twin (features/thresholds stay f32 so split
        routing is bit-identical); bf16 rounds the leaf values and reuses
        the fp32 program. Each variant stages its own device arrays, so
        mixed-precision serving of one ensemble never cross-contaminates."""
        from ..common import quant
        from ..common.jitcache import call_row_bucketed, device_constants

        if precision == quant.INT8:
            run = quant.int8_tree_program(self.depth)
            dev = getattr(self, "_dev_arrays_q", None)
            if dev is None:
                lq, ls = quant.quantize_last_axis(self.leaves)
                dev = self._dev_arrays_q = device_constants(
                    self.feats, self.thrs, lq, ls, self.base_score)
            return np.asarray(call_row_bucketed(
                run, (np.asarray(X, np.float32),), dev))
        run = _predict_fn(self.depth)
        if precision == quant.BF16:
            dev = getattr(self, "_dev_arrays_b", None)
            if dev is None:
                dev = self._dev_arrays_b = device_constants(
                    self.feats, self.thrs, quant.bf16_round(self.leaves),
                    quant.bf16_round(self.base_score))
        else:
            dev = getattr(self, "_dev_arrays", None)
            if dev is None:  # staged once per ensemble, not per call
                dev = self._dev_arrays = device_constants(
                    self.feats, self.thrs, self.leaves, self.base_score)
        return np.asarray(call_row_bucketed(
            run, (np.asarray(X, np.float32),), dev))

    def to_arrays(self) -> Dict[str, np.ndarray]:
        return {
            "feats": self.feats,
            "thrs": self.thrs,
            "leaves": self.leaves,
            "base_score": self.base_score,
        }

    @staticmethod
    def from_arrays(meta: dict, arrays: Dict[str, np.ndarray]) -> "TreeEnsemble":
        return TreeEnsemble(
            depth=int(meta["depth"]),
            feats=np.asarray(arrays["feats"], np.int32),
            thrs=np.asarray(arrays["thrs"], np.float32),
            leaves=np.asarray(arrays["leaves"], np.float32),
            base_score=np.asarray(arrays["base_score"], np.float32),
            task=meta["task"],
            labels=meta.get("labels"),
            feature_cols=meta.get("featureCols"),
            vector_col=meta.get("vectorCol"),
        )


# ---------------------------------------------------------------------------
# single-tree growth (shared by GBDT and forest)
# ---------------------------------------------------------------------------


_MAX_DEPTH = 14  # 2^D heap nodes x num_bins histogram rows: beyond this the
# static perfect-depth layout (L*B segment space) outgrows HBM — the same
# bound the reference's TreeObj memory planning enforces


def _check_depth(depth: int):
    from ..common.exceptions import AkIllegalArgumentException

    if depth > _MAX_DEPTH:
        raise AkIllegalArgumentException(
            f"tree depth {depth} > {_MAX_DEPTH}: the perfect-depth heap "
            f"layout allocates 2^depth x num_bins histogram slots; use more "
            f"trees instead of deeper ones")


def _grow_tree(bins_s, g_s, h_s, c_s, mesh, edges, depth, num_bins, l2,
               min_samples, min_gain, fmask, n_local) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Grow one tree; returns (feat_heap (2^D-1,), thr_heap raw (2^D-1,),
    leaf_node_ids (n,) device array of final leaf per sample)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    node = jax.device_put(
        np.zeros(n_local, np.int32), NamedSharding(mesh, P(AXIS_DATA))
    )
    feat_heap = np.full(2 ** depth - 1, -1, np.int32)
    thr_heap = np.zeros(2 ** depth - 1, np.float32)
    fmask_j = jnp.asarray(fmask, jnp.float32)

    for level in range(depth):
        L = 2 ** level
        fn = _level_fn(mesh, L, num_bins, float(l2), float(min_samples),
                       float(min_gain))
        feat, thr, node = fn(bins_s, g_s, h_s, c_s, node, fmask_j)
        feat = np.asarray(feat)
        thr = np.asarray(thr)
        base = 2 ** level - 1
        feat_heap[base:base + L] = feat
        thr_heap[base:base + L] = _bins_to_thresholds(edges, feat, thr)
    return feat_heap, thr_heap, node


def _bins_to_thresholds(edges: np.ndarray, feat: np.ndarray,
                        thr: np.ndarray) -> np.ndarray:
    """bin index -> raw threshold; edges[f, t] is the UPPER boundary of bin
    t, and a non-splitting node (feat < 0) gets +inf so everything routes
    left. The one place encoding this contract (GBDT + forest)."""
    return np.where(
        feat >= 0,
        edges[np.maximum(feat, 0), np.minimum(thr, edges.shape[1] - 1)],
        np.inf)


def _shard(mesh, arr):
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    return jax.device_put(arr, NamedSharding(mesh, P(AXIS_DATA)))


def _shard_cached(mesh, arr):
    """Staging-cache variant of ``_shard`` for train-constant blocks (binned
    features, labels, masks). Per-round arrays (gradients) stay on the direct
    path — their content changes every boosting round."""
    from ..common.staging import stage_sharded

    arr = np.asarray(arr)
    return stage_sharded(arr, mesh, AXIS_DATA, pad_rows_to=arr.shape[0])



def _compact_bins(bins_pad: np.ndarray, num_bins: int) -> np.ndarray:
    """uint8 the bins rectangle when codes fit: a 4x smaller host->device
    transfer and staging-cache footprint; EVERY jitted consumer widens back
    to int32 at body entry (the paired invariant)."""
    if num_bins <= 256:
        return bins_pad.astype(np.uint8)
    return bins_pad


def _pad_rows(arr, dp):
    n = arr.shape[0]
    pad = (-n) % dp
    if pad:
        pad_width = [(0, pad)] + [(0, 0)] * (arr.ndim - 1)
        arr = np.pad(arr, pad_width)
    return arr


# ---------------------------------------------------------------------------
# GBDT — whole-run fused program
# ---------------------------------------------------------------------------


# one-hot histogram operand budget per shard (bf16 elements): above this the
# fused program streams row chunks through the matmul instead of holding the
# whole (n_local, d*B) one-hot in HBM
_HIST_ONEHOT_BUDGET_ELEMS = 128 * 1024 * 1024


def _build_gbdt_train_fn(mesh, task: str, num_trees: int, depth: int,
                         num_bins: int, K: int, subsample_on: bool,
                         colsample_on: bool, d: int, num_chunks: int):
    """ONE compiled program for the whole boosting run: a ``lax.fori_loop``
    over trees inside one ``shard_map`` — gradients, histograms (+psum),
    split search, sample routing, leaf values and score updates all stay on
    device. The host dispatches once and fetches three small arrays, versus
    the previous one-dispatch-per-level design (trees x depth host
    round-trips)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    axis = AXIS_DATA
    B = num_bins
    HEAP = 2 ** depth - 1
    LEAF = 2 ** depth

    def body(bins, y_enc, valid, base, key, hp):
        # hp: (lr, l2, min_samples, min_gain, subsample, colsample) as
        # runtime scalars, so tuning sweeps reuse ONE compiled program
        bins = bins.astype(jnp.int32)  # staged as uint8 (_compact_bins):
        # bins ship 4x smaller and widen on device
        lr, l2, min_samples, min_gain, subsample, colsample = hp
        n_local = bins.shape[0]
        F0 = jnp.tile(base[None, :], (n_local, 1))
        feats0 = jnp.full((num_trees, K, HEAP), -1, jnp.int32)
        thrs0 = jnp.full((num_trees, K, HEAP), B - 1, jnp.int32)
        leaves0 = jnp.zeros((num_trees, K, LEAF), jnp.float32)
        shard_id = jax.lax.axis_index(axis)

        # Histograms as MXU matmuls: every level's (g, h, count) histograms
        # are ONE (3L, n) @ (n, d*B) contraction against the bins one-hot
        # with f32 accumulation — the systolic array does the scatter, not
        # the VPU. one-hot entries are exact in bf16; g/h round to bf16
        # (~0.4% per element), well inside histogram-split tolerance
        # (LightGBM quantizes harder). When the full one-hot would blow the
        # HBM budget (num_chunks > 1), row chunks stream through the same
        # matmul under lax.scan and only a (chunk, d*B) slab materializes.
        def _onehot_bins(b):
            return (b[:, :, None] == jnp.arange(B, dtype=b.dtype)
                    ).astype(jnp.bfloat16).reshape(b.shape[0], d * B)

        def _vmat(node_c, g_c, h_c, w_c, L):
            N = (node_c[:, None]
                 == jnp.arange(L, dtype=node_c.dtype)[None, :]
                 ).astype(jnp.bfloat16)
            return jnp.concatenate(
                [N * g_c.astype(jnp.bfloat16)[:, None],
                 N * h_c.astype(jnp.bfloat16)[:, None],
                 N * w_c.astype(jnp.bfloat16)[:, None]], axis=1)

        if num_chunks == 1:
            O = _onehot_bins(bins)

            def hists(node, g, h, w, L):
                hist = jax.lax.dot_general(
                    _vmat(node, g, h, w, L), O, (((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)  # (3L, d*B)
                hist = hist.reshape(3, L, d, B)
                return hist[0], hist[1], hist[2]
        else:
            chunk = n_local // num_chunks
            bins_c = bins.reshape(num_chunks, chunk, d)

            def hists(node, g, h, w, L):
                def step(acc, xs):
                    nc, gc, hc_, wc, bc = xs
                    part = jax.lax.dot_general(
                        _vmat(nc, gc, hc_, wc, L), _onehot_bins(bc),
                        (((0,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32)
                    return acc + part, None

                hist0 = jnp.zeros((3 * L, d * B), jnp.float32)
                hist, _ = jax.lax.scan(
                    step, hist0,
                    (node.reshape(num_chunks, chunk),
                     g.reshape(num_chunks, chunk),
                     h.reshape(num_chunks, chunk),
                     w.reshape(num_chunks, chunk),
                     bins_c))
                hist = hist.reshape(3, L, d, B)
                return hist[0], hist[1], hist[2]

        def tree_body(it, carry):
            F, feats_acc, thrs_acc, leaves_acc = carry
            kit = jax.random.fold_in(key, it)
            if task == "regression":
                g_all = F - y_enc
                h_all = jnp.ones_like(F)
            elif task == "binary":
                p = jax.nn.sigmoid(F)
                g_all = p - y_enc
                h_all = jnp.maximum(p * (1 - p), 1e-6)
            else:
                p = jax.nn.softmax(F, axis=1)
                g_all = p - y_enc
                h_all = jnp.maximum(p * (1 - p), 1e-6)

            if subsample_on:
                ks = jax.random.fold_in(kit, shard_id)
                w = valid * jax.random.bernoulli(
                    ks, subsample, (n_local,)).astype(jnp.float32)
            else:
                w = valid
            if colsample_on:
                kc = jax.random.fold_in(kit, -1)  # same key on every shard
                fmask = jax.random.bernoulli(
                    kc, colsample, (d,)).astype(jnp.float32)
                # an all-zero draw falls back to ONE random feature (not
                # all), preserving the subsampling regularization
                one_hot = jax.nn.one_hot(
                    jax.random.randint(kc, (), 0, d), d)
                fmask = jnp.where(fmask.sum() > 0, fmask, one_hot)
            else:
                fmask = jnp.ones((d,), jnp.float32)

            for kcls in range(K):
                g = g_all[:, kcls] * w
                h = h_all[:, kcls] * w
                node = jnp.zeros(n_local, jnp.int32)
                for level in range(depth):
                    L = 2 ** level
                    hg, hh, hc = hists(node, g, h, w, L)
                    hg = jax.lax.psum(hg, axis)
                    hh = jax.lax.psum(hh, axis)
                    hc = jax.lax.psum(hc, axis)
                    feat, thr = _split_search(hg, hh, hc, fmask, l2,
                                              min_samples, min_gain)

                    hbase = 2 ** level - 1  # static heap offset
                    feats_acc = jax.lax.dynamic_update_slice(
                        feats_acc, feat[None, None, :], (it, kcls, hbase))
                    thrs_acc = jax.lax.dynamic_update_slice(
                        thrs_acc, thr[None, None, :], (it, kcls, hbase))
                    node = _route(bins, node, feat, thr)

                # leaf sums ride the MXU too: (LEAF, n) @ (n, 2)
                NL = (node[:, None]
                      == jnp.arange(LEAF, dtype=node.dtype)[None, :]
                      ).astype(jnp.bfloat16)
                gh = jnp.stack([g, h], axis=1).astype(jnp.bfloat16)
                sums = jax.lax.psum(
                    jax.lax.dot_general(
                        NL, gh, (((0,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32), axis)
                sg, sh = sums[:, 0], sums[:, 1]
                leaf_vals = (-sg / (sh + l2)) * lr
                leaves_acc = jax.lax.dynamic_update_slice(
                    leaves_acc, leaf_vals[None, None, :], (it, kcls, 0))
                F = F.at[:, kcls].add(leaf_vals[node])
            return F, feats_acc, thrs_acc, leaves_acc

        _, feats, thrs, leaves = jax.lax.fori_loop(
            0, num_trees, tree_body, (F0, feats0, thrs0, leaves0))
        return feats, thrs, leaves

    return jax.jit(
        shard_map(
            body, mesh=mesh,
            in_specs=(P(AXIS_DATA), P(AXIS_DATA), P(AXIS_DATA), P(), P(),
                      P()),
            out_specs=(P(), P(), P()),
            check_vma=False,
        )
    )


def _gbdt_train_fn(mesh, task: str, num_trees: int, depth: int,
                   num_bins: int, K: int, subsample_on: bool,
                   colsample_on: bool, d: int, num_chunks: int):
    from ..common.jitcache import cached_jit

    return cached_jit("gbdt.train", _build_gbdt_train_fn,
                      task, int(num_trees), int(depth), int(num_bins),
                      int(K), bool(subsample_on), bool(colsample_on),
                      int(d), int(num_chunks), mesh=mesh)


def train_gbdt(
    X: np.ndarray,
    y: np.ndarray,
    *,
    task: str,
    num_trees: int = 100,
    depth: int = 5,
    learning_rate: float = 0.1,
    num_bins: int = 64,
    l2: float = 1.0,
    min_samples: float = 5.0,
    min_gain: float = 0.0,
    subsample: float = 1.0,
    colsample: float = 1.0,
    num_classes: int = 2,
    seed: int = 0,
    mesh=None,
    phase_metrics: Optional[dict] = None,
) -> TreeEnsemble:
    """Histogram gradient boosting. task: regression | binary | multiclass.

    The whole boosting run is ONE device dispatch (:func:`_gbdt_train_fn`);
    the host bins the data, ships it once, and fetches the tree arrays once.
    Pass ``phase_metrics={}`` to receive a per-phase wall-clock breakdown
    (binning / data staging / device run / fetch / postprocess). On a COLD
    call XLA compilation is folded into ``device_run_s``; run twice (or rely
    on the persistent compilation cache) for pure execution numbers."""
    _check_depth(depth)
    import time as _time

    import jax
    import jax.numpy as jnp

    t_start = _time.perf_counter()
    mesh = mesh or default_mesh()
    dp = mesh.shape[AXIS_DATA]
    n, d = X.shape
    X32 = np.asarray(X, np.float32)

    edges = quantile_bins(X32, num_bins)
    bins = apply_bins(X32, edges)
    t_binned = _time.perf_counter()

    # row-chunk the one-hot histogram operand when it would blow HBM; pad
    # rows so every shard splits evenly into chunks
    per_shard = -(-n // dp)
    num_chunks = max(1, -(-(per_shard * d * num_bins)
                          // _HIST_ONEHOT_BUDGET_ELEMS))
    bins_pad = _compact_bins(_pad_rows(bins, dp * num_chunks), num_bins)
    n_pad = bins_pad.shape[0]
    valid = np.zeros(n_pad, np.float32)
    valid[:n] = 1.0

    K = num_classes if task == "multiclass" else 1
    if task == "regression":
        base = np.asarray([float(np.mean(y))], np.float32)
    elif task == "binary":
        p = float(np.clip(np.mean(y), 1e-6, 1 - 1e-6))
        base = np.asarray([np.log(p / (1 - p))], np.float32)
    else:
        probs = np.bincount(y.astype(int), minlength=K) / n
        base = np.log(np.clip(probs, 1e-6, None)).astype(np.float32)

    if task == "multiclass":
        y_enc = np.eye(K, dtype=np.float32)[np.asarray(y, int)]
    else:
        y_enc = np.asarray(y, np.float32)[:, None]
    y_pad = _pad_rows(y_enc, dp * num_chunks)

    # train-constant blocks ride the content-keyed staging cache: re-training
    # on the same table (warm bench runs, tuning sweeps) skips the re-push
    bins_s = _shard_cached(mesh, bins_pad)
    y_s = _shard_cached(mesh, y_pad)
    valid_s = _shard_cached(mesh, valid)
    jax.block_until_ready((bins_s, y_s, valid_s))
    t_staged = _time.perf_counter()

    fn = _gbdt_train_fn(
        mesh, task, int(num_trees), int(depth), int(num_bins),
        K, subsample < 1.0, colsample < 1.0, d, int(num_chunks))
    key = jax.random.PRNGKey(seed)
    hp = jnp.asarray([learning_rate, l2, min_samples, min_gain,
                      subsample, colsample], jnp.float32)
    # first call compiles (cached across runs via the persistent XLA cache)
    feats_j, thrs_j, leaves_j = fn(bins_s, y_s, valid_s,
                                   jnp.asarray(base), key, hp)
    jax.block_until_ready((feats_j, thrs_j, leaves_j))
    t_ran = _time.perf_counter()

    # ONE batched device_get: three separate np.asarray calls cost three
    # device->host round trips for KB-sized arrays
    feats_b, thrs_b, leaves_np = (
        np.asarray(a) for a in jax.device_get((feats_j, thrs_j, leaves_j)))
    t_fetched = _time.perf_counter()

    # bin index -> raw threshold (edges[f, t] is the upper bin boundary);
    # flatten (iter, K) into T = num_trees*K trees each holding only its
    # class slot, keeping predict a plain sum
    leaf_count = 2 ** depth
    T = num_trees * K
    feats = np.zeros((T, 2 ** depth - 1), np.int32)
    thrs = np.zeros((T, 2 ** depth - 1), np.float32)
    leaves = np.zeros((T, K, leaf_count), np.float32)
    t = 0
    for it in range(num_trees):
        for kcls in range(K):
            fh = feats_b[it, kcls]
            feats[t] = fh
            thrs[t] = _bins_to_thresholds(edges, fh, thrs_b[it, kcls])
            leaves[t, kcls] = leaves_np[it, kcls]
            t += 1

    if phase_metrics is not None:
        phase_metrics.update({
            "binning_s": round(t_binned - t_start, 4),
            "stage_data_s": round(t_staged - t_binned, 4),
            "device_run_s": round(t_ran - t_staged, 4),
            "fetch_s": round(t_fetched - t_ran, 4),
            "postprocess_s": round(_time.perf_counter() - t_fetched, 4),
        })
    return TreeEnsemble(depth, feats, thrs, leaves, base, task)


# ---------------------------------------------------------------------------
# impurity-criterion single trees (C45 / Cart / Id3)
# ---------------------------------------------------------------------------


def _split_search_impurity(hk, fmask, min_samples, min_gain, criterion):
    """Per-class count histograms (L, d, B, K) -> (feat (L,), thr (L,)).

    Classic impurity split criteria over the SAME binned layout the
    gradient kernels use (reference: the Gini / InfoGain / InfoGainRatio
    arms of operator/common/tree/seriescalc — Cart=gini, Id3=infoGain,
    C45=infoGainRatio):

    - ``gini``: parent Gini minus weighted child Gini
    - ``infoGain``: parent entropy minus weighted child entropy
    - ``infoGainRatio``: infoGain / split-entropy (C4.5's normalization)
    """
    import jax.numpy as jnp

    L, d, B, K = hk.shape
    CLk = jnp.cumsum(hk, axis=2)                # left class counts
    Ck = CLk[:, :, -1:, :]                      # node class totals
    CRk = Ck - CLk
    nL = CLk.sum(-1)                            # (L, d, B)
    nR = CRk.sum(-1)
    ntot = Ck.sum(-1)                           # (L, d, 1)

    def impurity(counts, total):
        p = counts / jnp.maximum(total[..., None], 1.0)
        if criterion == "gini":
            return 1.0 - (p * p).sum(-1)
        return -(p * jnp.where(p > 0, jnp.log2(jnp.maximum(p, 1e-12)),
                               0.0)).sum(-1)

    imp_parent = impurity(Ck, ntot)             # (L, d, 1)
    imp_L = impurity(CLk, nL)
    imp_R = impurity(CRk, nR)
    n_safe = jnp.maximum(ntot, 1.0)
    gain = imp_parent - (nL / n_safe) * imp_L - (nR / n_safe) * imp_R
    if criterion == "infoGainRatio":
        pL = nL / n_safe
        pR = nR / n_safe
        split_info = -(
            jnp.where(pL > 0, pL * jnp.log2(jnp.maximum(pL, 1e-12)), 0.0)
            + jnp.where(pR > 0, pR * jnp.log2(jnp.maximum(pR, 1e-12)), 0.0))
        gain = gain / jnp.maximum(split_info, 1e-6)

    ok = (nL >= min_samples) & (nR >= min_samples)
    ok = ok & (jnp.arange(B)[None, None, :] < B - 1)
    gain = jnp.where(ok & (fmask[None, :, None] > 0), gain, -jnp.inf)
    flat = gain.reshape(L, d * B)
    best = jnp.argmax(flat, axis=1)
    best_gain = jnp.take_along_axis(flat, best[:, None], 1)[:, 0]
    feat = jnp.where(best_gain > min_gain, best // B, -1).astype(jnp.int32)
    thr = jnp.where(best_gain > min_gain, best % B, B - 1).astype(jnp.int32)
    return feat, thr


def _build_impurity_tree_fn(mesh, depth: int, num_bins: int, K: int, d: int,
                            criterion: str, num_chunks: int):
    """ONE compiled program growing a whole impurity-criterion tree:
    per-class count histograms as MXU matmuls (one-hot node x one-hot class
    against the bins one-hot), psum across the data axis, impurity split
    search, routing — every level unrolled inside one shard_map. Like the
    fused GBDT program, row chunks stream through the matmul under
    ``lax.scan`` when the full one-hot would blow the HBM budget."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    axis = AXIS_DATA
    B = num_bins
    HEAP = 2 ** depth - 1
    LEAF = 2 ** depth

    def _onehot_bins(b):
        return (b[:, :, None] == jnp.arange(B, dtype=b.dtype)
                ).astype(jnp.bfloat16).reshape(b.shape[0], d * B)

    def body(bins, W, fmask, hp):
        # W: (n, K) per-class row weights (one-hot label x sample weight)
        bins = bins.astype(jnp.int32)  # may arrive uint8 (_compact_bins)
        min_samples, min_gain = hp
        n_local = bins.shape[0]
        Wb = W.astype(jnp.bfloat16)

        def _vm(node_c, W_c, L):
            N = (node_c[:, None]
                 == jnp.arange(L, dtype=node_c.dtype)[None, :]
                 ).astype(jnp.bfloat16)          # (chunk, L)
            return (N[:, :, None] * W_c[:, None, :]
                    ).reshape(node_c.shape[0], L * K)

        if num_chunks == 1:
            O = _onehot_bins(bins)

            def class_hists(node, L):
                return jax.lax.dot_general(
                    _vm(node, Wb, L), O, (((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)  # (L*K, d*B)
        else:
            chunk = n_local // num_chunks
            bins_c = bins.reshape(num_chunks, chunk, d)
            Wb_c = Wb.reshape(num_chunks, chunk, K)

            def class_hists(node, L):
                def step(acc, xs):
                    nc, wc, bc = xs
                    part = jax.lax.dot_general(
                        _vm(nc, wc, L), _onehot_bins(bc),
                        (((0,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32)
                    return acc + part, None

                hist0 = jnp.zeros((L * K, d * B), jnp.float32)
                hist, _ = jax.lax.scan(
                    step, hist0,
                    (node.reshape(num_chunks, chunk), Wb_c, bins_c))
                return hist

        feats_acc = jnp.full((HEAP,), -1, jnp.int32)
        thrs_acc = jnp.full((HEAP,), B - 1, jnp.int32)
        node = jnp.zeros(n_local, jnp.int32)
        for level in range(depth):
            L = 2 ** level
            hist = class_hists(node, L)
            hk = jax.lax.psum(
                hist.reshape(L, K, d, B).transpose(0, 2, 3, 1), axis)
            feat, thr = _split_search_impurity(
                hk, fmask, min_samples, min_gain, criterion)
            hbase = 2 ** level - 1
            feats_acc = jax.lax.dynamic_update_slice(feats_acc, feat,
                                                     (hbase,))
            thrs_acc = jax.lax.dynamic_update_slice(thrs_acc, thr, (hbase,))
            node = _route(bins, node, feat, thr)

        NL = (node[:, None] == jnp.arange(LEAF, dtype=node.dtype)[None, :]
              ).astype(jnp.bfloat16)
        counts = jax.lax.psum(
            jax.lax.dot_general(
                NL, Wb, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32), axis)  # (LEAF, K)
        probs = counts / jnp.maximum(counts.sum(-1, keepdims=True), 1.0)
        return feats_acc, thrs_acc, probs

    return jax.jit(
        shard_map(
            body, mesh=mesh,
            in_specs=(P(AXIS_DATA), P(AXIS_DATA), P(), P()),
            out_specs=(P(), P(), P()),
            check_vma=False,
        )
    )


def _impurity_tree_fn(mesh, depth: int, num_bins: int, K: int, d: int,
                      criterion: str, num_chunks: int):
    from ..common.jitcache import cached_jit

    return cached_jit("tree.impurity", _build_impurity_tree_fn,
                      int(depth), int(num_bins), int(K), int(d),
                      criterion, int(num_chunks), mesh=mesh)


def _clear_impurity_cache():
    from ..common.jitcache import clear_kernel

    clear_kernel("tree.impurity")


_impurity_tree_fn.cache_clear = _clear_impurity_cache


def train_tree_impurity(
    X: np.ndarray,
    y: np.ndarray,
    *,
    criterion: str,  # gini | infoGain | infoGainRatio
    num_classes: int,
    depth: int = 5,
    num_bins: int = 64,
    min_samples: float = 2.0,
    min_gain: float = 0.0,
    subsample: float = 1.0,
    feature_fraction: float = 1.0,
    seed: int = 0,
    mesh=None,
) -> TreeEnsemble:
    """Single classification tree with a classic impurity criterion
    (reference: C45TrainBatchOp.java / CartTrainBatchOp.java /
    Id3TrainBatchOp.java — the three named tree types). Leaves hold class
    probabilities; for K=2 they collapse to one p(positive) channel so the
    shared forest predict contract applies unchanged."""
    if criterion not in ("gini", "infoGain", "infoGainRatio"):
        from ..common.exceptions import AkIllegalArgumentException

        raise AkIllegalArgumentException(
            f"criterion must be gini|infoGain|infoGainRatio, got {criterion}")
    _check_depth(depth)
    import jax.numpy as jnp

    mesh = mesh or default_mesh()
    dp = mesh.shape[AXIS_DATA]
    n, d = X.shape
    K = int(num_classes)
    rng = np.random.default_rng(seed)
    X32 = np.asarray(X, np.float32)
    edges = quantile_bins(X32, num_bins)
    bins = apply_bins(X32, edges)

    per_shard = -(-n // dp)
    num_chunks = max(1, -(-(per_shard * d * num_bins)
                          // _HIST_ONEHOT_BUDGET_ELEMS))
    bins_pad = _compact_bins(_pad_rows(bins, dp * num_chunks), num_bins)
    w = np.ones(n, np.float32)
    if subsample < 1.0:
        w *= (rng.random(n) < subsample).astype(np.float32)
    w_pad = _pad_rows(w, dp * num_chunks)  # padded rows get weight 0
    fmask = np.ones(d, np.float32)
    if feature_fraction < 1.0:
        fmask = (rng.random(d) < feature_fraction).astype(np.float32)
        if fmask.sum() == 0:
            fmask[rng.integers(d)] = 1.0
    W = (_pad_rows(np.eye(K, dtype=np.float32)[np.asarray(y, int)],
                   dp * num_chunks) * w_pad[:, None])

    fn = _impurity_tree_fn(mesh, int(depth), int(num_bins), K, d,
                           criterion, int(num_chunks))
    hp = jnp.asarray([min_samples, min_gain], jnp.float32)
    fh, th, probs = fn(_shard(mesh, bins_pad), _shard(mesh, W),
                       jnp.asarray(fmask), hp)
    fh = np.asarray(fh)
    thrs = _bins_to_thresholds(edges, fh, np.asarray(th))
    probs = np.asarray(probs)  # (LEAF, K)

    leaf_count = 2 ** depth
    if K == 2:
        leaves = probs[:, 1].reshape(1, 1, leaf_count).astype(np.float32)
        task = "binary"
    else:
        leaves = probs.T.reshape(1, K, leaf_count).astype(np.float32)
        task = "multiclass"
    return TreeEnsemble(depth, fh.reshape(1, -1), thrs.reshape(1, -1),
                        leaves, np.zeros(leaves.shape[1], np.float32), task)


# ---------------------------------------------------------------------------
# RandomForest / DecisionTree
# ---------------------------------------------------------------------------


def train_forest(
    X: np.ndarray,
    y: np.ndarray,
    *,
    task: str,  # regression | binary | multiclass
    num_trees: int = 10,
    depth: int = 6,
    num_bins: int = 64,
    min_samples: float = 2.0,
    min_gain: float = 0.0,
    subsample: float = 1.0,
    feature_fraction: Optional[float] = None,
    num_classes: int = 2,
    bootstrap: bool = True,
    seed: int = 0,
    mesh=None,
) -> TreeEnsemble:
    """Random forest via the same histogram kernels: trees fit targets directly
    (g = -target, h = 1 -> leaf = mean target), variance-reduction splits.
    Classification fits one-vs-all class indicators; predict averages and
    argmaxes — the reference's per-class info-gain forest re-based on the
    shared histogram machinery."""
    _check_depth(depth)
    mesh = mesh or default_mesh()
    dp = mesh.shape[AXIS_DATA]
    rng = np.random.default_rng(seed)
    n, d = X.shape
    X32 = np.asarray(X, np.float32)
    edges = quantile_bins(X32, num_bins)
    bins = apply_bins(X32, edges)
    bins_pad = _compact_bins(_pad_rows(bins, dp), num_bins)
    valid = np.zeros(bins_pad.shape[0], np.float32)
    valid[:n] = 1.0
    bins_s = _shard(mesh, bins_pad)
    n_pad = valid.shape[0]

    K = num_classes if task == "multiclass" else 1
    if task == "regression":
        targets = np.asarray(y, np.float32)[:, None]
    elif task == "binary":
        targets = np.asarray(y, np.float32)[:, None]
    else:
        targets = np.eye(K, dtype=np.float32)[np.asarray(y, int)]

    if feature_fraction is None:
        feature_fraction = 1.0 if num_trees == 1 else max(1.0 / d, np.sqrt(d) / d)

    leaf_count = 2 ** depth
    T = num_trees * K
    feats = np.zeros((T, 2 ** depth - 1), np.int32)
    thrs = np.zeros((T, 2 ** depth - 1), np.float32)
    leaves = np.zeros((T, K, leaf_count), np.float32)

    t = 0
    for it in range(num_trees):
        if bootstrap and num_trees > 1:
            # bootstrap of subsample*n draws, so subsamplingRatio composes
            n_draw = max(1, int(round(n * min(subsample, 1.0))))
            w = rng.multinomial(n_draw, np.ones(n) / n).astype(np.float32)
        elif subsample < 1:
            w = (rng.random(n) < subsample).astype(np.float32)
        else:
            w = np.ones(n, np.float32)
        fmask = (rng.random(d) < feature_fraction).astype(np.float32)
        if fmask.sum() == 0:
            fmask[rng.integers(d)] = 1.0
        for kcls in range(K):
            tgt = targets[:, kcls]
            g = _pad_rows(-(tgt * w), dp)  # leaf = mean target, l2=0
            h = _pad_rows(w, dp)
            c = _pad_rows(w, dp)
            g_s = _shard(mesh, g * valid)
            h_s = _shard(mesh, h * valid)
            c_s = _shard(mesh, c * valid)
            fh, th, node = _grow_tree(
                bins_s, g_s, h_s, c_s, mesh, edges, depth, num_bins,
                1e-9, min_samples, min_gain, fmask, n_pad,
            )
            lf = _leaf_fn(mesh, leaf_count, 1e-9)
            leaf_vals = np.asarray(lf(g_s, h_s, node)) / num_trees
            feats[t] = fh
            thrs[t] = th
            leaves[t, kcls] = leaf_vals
            t += 1

    base = np.zeros(K, np.float32)
    return TreeEnsemble(depth, feats, thrs, leaves, base, task)
