"""Post-training quantization for served models — the opt-in precision
policy behind ``ModelServer.load(..., precision="int8")``.

Policies (``fp32`` is the identity — precision unset leaves every scoring
path byte-identical to the unquantized code):

- ``int8`` — per-channel symmetric int8 weights everywhere. Kernels whose
  hot loop is one plain matmul (linear scoring, the Naive-Bayes factor
  matmuls, the FM linear term) run **static W8A8**: the activation block is
  quantized with a per-tensor scale fixed at load time by a calibration
  pass over real warmup rows, the matmul accumulates in int32, and one
  fused rescale restores f32 scores. Multi-stage kernels (MLP hidden
  layers, the FM pairwise factors, tree leaf values, the BERT encoder
  parameters) run **weight-only**: int8 weights dequantize in-kernel to
  bf16 and the matmuls accumulate in f32.
- ``bf16`` — weights and activations cast to bf16, outputs f32; no
  calibration (there are no fixed ranges to learn).

Never silent: the serving loader refuses a quantized load whose
calibration sample is synthetic or degenerate, and gates every quantized
load behind an accuracy band against the fp32 baseline — a failing gate
falls back to fp32 with a counted reason (``serving.precision_fallback``).

Quantized programs live in the process-wide ProgramCache under their own
``quant.*`` kernel ids, so fp32 and int8 versions of the same model
coexist without evicting or cross-contaminating each other's programs.

The policy travels to mappers as stamped op params (mappers are rebuilt
from op params on every predict, so params are the only durable channel):

- ``inferencePrecision`` — the active policy string,
- ``quantCalib`` — ``{site: activation-absmax}`` fixed by calibration,
- ``quantSite`` — the op's unique site prefix inside the serving plan.
"""

from __future__ import annotations

import threading
from contextlib import contextmanager
from typing import Any, Dict, Optional, Tuple

import numpy as np

from .exceptions import AkIllegalArgumentException, AkIllegalStateException

FP32 = "fp32"
BF16 = "bf16"
INT8 = "int8"
PRECISIONS = (FP32, BF16, INT8)

# op-param keys the serving loader stamps and mappers read
PRECISION_KEY = "inferencePrecision"
CALIB_KEY = "quantCalib"
SITE_KEY = "quantSite"

_QMAX = 127.0  # symmetric int8 range; -128 is never produced


def resolve_policy(precision) -> Optional[str]:
    """Normalize a precision request: None/""/"fp32" -> None (the identity
    policy), "bf16"/"int8" -> themselves; anything else raises."""
    if precision is None or precision == "":
        return None
    p = str(precision).lower()
    if p not in PRECISIONS:
        raise AkIllegalArgumentException(
            f"unknown precision {precision!r}; choose one of {PRECISIONS}")
    return None if p == FP32 else p


def policy_of(params) -> Optional[str]:
    """The stamped policy on a mapper's params, or None when unset — the
    one read every fp32 predict performs (a dict-membership check), so
    knob-off stays byte-identical AND cost-identical."""
    if params is None or not params.contains(PRECISION_KEY):
        return None
    return resolve_policy(params.get(PRECISION_KEY))


def site_of(params, default: str) -> str:
    if params is not None and params.contains(SITE_KEY):
        return str(params.get(SITE_KEY))
    return default


def calib_scale(params, site: str) -> float:
    """The calibrated per-tensor activation scale for ``site`` (absmax /
    127). A quantized kernel asking for a range calibration never fixed is
    a loader bug — refuse loudly instead of computing garbage scores."""
    calib = params.get(CALIB_KEY) if params is not None \
        and params.contains(CALIB_KEY) else None
    absmax = (calib or {}).get(site)
    if absmax is None or not np.isfinite(absmax) or absmax <= 0.0:
        raise AkIllegalStateException(
            f"int8 inference has no calibrated activation range for site "
            f"{site!r} — the load-time calibration pass did not cover it")
    return float(absmax) / _QMAX


# ---------------------------------------------------------------------------
# weight quantization
# ---------------------------------------------------------------------------


def quantize_per_channel(w: np.ndarray,
                         axis: int = -1) -> Tuple[np.ndarray, np.ndarray]:
    """Symmetric per-channel int8 quantization of a weight array along
    ``axis`` (the output-channel axis; a 1-D weight is one channel).
    Returns ``(wq int8, scale f32)`` with ``wq * scale ~= w``; an all-zero
    channel gets scale 1.0 so dequantization is exact."""
    w = np.asarray(w, np.float32)
    if w.ndim == 0 or w.size == 0:
        return w.astype(np.int8), np.ones_like(w, np.float32)
    if w.ndim == 1:
        absmax = float(np.max(np.abs(w)))
        scale = np.float32(absmax / _QMAX if absmax > 0.0 else 1.0)
        wq = np.clip(np.round(w / scale), -_QMAX, _QMAX).astype(np.int8)
        return wq, np.asarray(scale, np.float32)
    reduce_axes = tuple(i for i in range(w.ndim)
                        if i != (axis % w.ndim))
    absmax = np.max(np.abs(w), axis=reduce_axes, keepdims=True)
    scale = np.where(absmax > 0.0, absmax / _QMAX, 1.0).astype(np.float32)
    wq = np.clip(np.round(w / scale), -_QMAX, _QMAX).astype(np.int8)
    return wq, np.squeeze(scale, axis=reduce_axes)


def quantize_last_axis(w: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Symmetric int8 with one scale per leading index (reduce over the
    LAST axis only) — e.g. tree leaf tables ``(T, K, 2^D)`` get scales
    ``(T, K)``. All-zero rows get scale 1.0."""
    w = np.asarray(w, np.float32)
    absmax = np.max(np.abs(w), axis=-1, keepdims=True)
    scale = np.where(absmax > 0.0, absmax / _QMAX, 1.0).astype(np.float32)
    wq = np.clip(np.round(w / scale), -_QMAX, _QMAX).astype(np.int8)
    return wq, np.squeeze(scale, axis=-1)


def dequantize(wq: np.ndarray, scale: np.ndarray,
               axis: int = -1) -> np.ndarray:
    """Host-side inverse of :func:`quantize_per_channel` (tests/tools)."""
    wq = np.asarray(wq, np.float32)
    s = np.asarray(scale, np.float32)
    if wq.ndim >= 2 and s.ndim == 1:
        shape = [1] * wq.ndim
        shape[axis % wq.ndim] = s.shape[0]
        s = s.reshape(shape)
    return wq * s


def quantize_tree(params) -> Tuple[Any, Any]:
    """Weight-only quantization of a pytree of model parameters: every
    float leaf with >= 2 dims (the matmul weights) becomes int8 with a
    per-channel (last-axis) scale; 1-D floats (biases, layernorm gains)
    and integer leaves pass through as-is with scale None. Returns
    ``(q_tree, scale_tree)`` with identical treedefs."""
    import jax

    def q(leaf):
        a = np.asarray(leaf)
        if a.ndim >= 2 and np.issubdtype(a.dtype, np.floating):
            return quantize_per_channel(a, axis=-1)
        return a, None

    pairs = jax.tree_util.tree_map(q, params)
    q_tree = jax.tree_util.tree_map(lambda p: p[0], pairs,
                                    is_leaf=lambda x: isinstance(x, tuple))
    s_tree = jax.tree_util.tree_map(lambda p: p[1], pairs,
                                    is_leaf=lambda x: isinstance(x, tuple))
    return q_tree, s_tree


# ---------------------------------------------------------------------------
# calibration capture
# ---------------------------------------------------------------------------

# Capture is PROCESS-wide, not thread-local: a predict fans out across the
# DAG executor pool (``alink-dag_*`` threads), so the mapper calling
# :func:`observe` is rarely the thread that opened the context. The gate
# lock serializes calibration passes (one model calibrates at a time); the
# record lock guards merges from concurrently-executing mapper blocks.
_capture_gate = threading.Lock()
_capture_lock = threading.Lock()
_capture_rec: Optional[Dict[str, float]] = None


@contextmanager
def calibration(record: Dict[str, float]):
    """Activate activation-range capture for the duration of the context:
    mappers running a predict inside it merge per-site absmax into
    ``record`` — from whatever executor thread the plan schedules them on.
    Outside the context :func:`observe` is a no-op, so production predicts
    pay nothing and change nothing. Calibration passes serialize on a
    process-wide gate; unrelated fp32 traffic served concurrently CAN
    observe into the record, which is why load-time stamping makes sites
    unique per model name."""
    global _capture_rec
    with _capture_gate:
        with _capture_lock:
            _capture_rec = record
        try:
            yield record
        finally:
            with _capture_lock:
                _capture_rec = None


def capturing() -> bool:
    return _capture_rec is not None


def observe(site: str, block) -> None:
    """Record the absmax of one activation block under ``site`` (max-merge
    across calibration batches). Only active inside :func:`calibration`."""
    if _capture_rec is None:
        return
    a = np.asarray(block)
    m = float(np.max(np.abs(a))) if a.size else 0.0
    if not np.isfinite(m):
        m = float("inf")
    with _capture_lock:
        rec = _capture_rec
        if rec is None:
            return
        prev = rec.get(site)
        rec[site] = m if prev is None else max(prev, m)


def degenerate_sites(calib: Dict[str, float]) -> Dict[str, float]:
    """The calibration sites whose recorded range cannot produce a usable
    scale: zero (an all-zero sample quantizes everything to 0) or
    non-finite. An empty dict means the ranges are healthy."""
    return {k: v for k, v in (calib or {}).items()
            if not np.isfinite(v) or v <= 0.0}


# ---------------------------------------------------------------------------
# quantized kernel builders (cached_jit; distinct `quant.*` kernel ids so
# fp32 and int8 programs coexist in the ProgramCache)
# ---------------------------------------------------------------------------


def _quantize_act(jnp, X, sx):
    return jnp.clip(jnp.round(X / sx), -_QMAX, _QMAX).astype(jnp.int8)


def _int8_matmul(jax, jnp, Xq, wq):
    # int8 x int8 -> int32 accumulate; one dot_general for 1-D and 2-D w
    return jax.lax.dot_general(
        Xq, wq, dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)


def _build_int8_linear_score():
    """Static-W8A8 twin of ``linear.score`` (``X @ w + b``): activations
    quantized with the calibrated per-tensor scale, int8 matmul with int32
    accumulation, one fused rescale back to f32."""
    import jax
    import jax.numpy as jnp

    def run(X, wq, b, sw, sx):
        acc = _int8_matmul(jax, jnp, _quantize_act(jnp, X, sx), wq)
        return acc.astype(jnp.float32) * (sx * sw) + b

    return jax.jit(run)


def int8_linear_program():
    from .jitcache import cached_jit

    return cached_jit("quant.linear_score.int8", _build_int8_linear_score)


def _build_int8_nb_score(mtype: str):
    """Static-W8A8 twin of ``naivebayes.score``: each factor matmul runs
    int8 x int8 -> int32 with its own calibrated activation scale (the
    Gaussian form feeds two distinct activations, X² and X)."""
    import jax
    import jax.numpy as jnp

    if mtype == "GAUSSIAN":
        def score(X, aq, bq, c, sa, sb, sxx, sx):
            Xsq = X * X
            t1 = _int8_matmul(jax, jnp, _quantize_act(jnp, Xsq, sxx), aq)
            t2 = _int8_matmul(jax, jnp, _quantize_act(jnp, X, sx), bq)
            return (-(t1.astype(jnp.float32)) * (sxx * sa)
                    + t2.astype(jnp.float32) * (sx * sb) + c)
    elif mtype == "MULTINOMIAL":
        def score(X, aq, bq, c, sa, sb, sxx, sx):
            t = _int8_matmul(jax, jnp, _quantize_act(jnp, X, sx), aq)
            return t.astype(jnp.float32) * (sx * sa) + c
    else:  # BERNOULLI — the binarized block is exactly representable
        def score(X, aq, bq, c, sa, sb, sxx, sx):
            Xb = (X > 0).astype(jnp.int8)
            t = _int8_matmul(jax, jnp, Xb, aq)
            return t.astype(jnp.float32) * sa + c

    return jax.jit(score)


def int8_nb_program(mtype: str):
    from .jitcache import cached_jit

    return cached_jit("quant.naivebayes_score.int8", _build_int8_nb_score,
                      mtype)


def _build_int8_fm_score():
    """FM scoring under int8: the linear term runs static W8A8; the
    pairwise term dequantizes the factor matrix V to bf16 in-kernel
    (weight-only — V feeds squares and cross terms, not one matmul)."""
    import jax
    import jax.numpy as jnp

    from ..optim import fm_pairwise

    def run(X, w0, wq, Vq, sw, sv, sx):
        lin = _int8_matmul(jax, jnp, _quantize_act(jnp, X, sx), wq)
        V = (Vq.astype(jnp.bfloat16)
             * sv.astype(jnp.bfloat16)[None, :])
        pair = fm_pairwise(X.astype(jnp.bfloat16), V)
        return (w0[0] + lin.astype(jnp.float32) * (sx * sw)
                + pair.astype(jnp.float32))

    return jax.jit(run)


def int8_fm_program():
    from .jitcache import cached_jit

    return cached_jit("quant.fm_score.int8", _build_int8_fm_score)


def _build_int8_mlp_score(sizes: tuple):
    """Weight-only int8 MLP forward: each layer's weight matrix
    dequantizes to bf16 in-kernel, activations run bf16, accumulation and
    the sigmoid run f32 (layer inputs are data-dependent, so static
    activation scales would need per-layer calibration depth this runtime
    does not assume)."""
    import jax
    import jax.numpy as jnp

    n_layers = len(sizes) - 1

    def run(X, *packed):
        h = X.astype(jnp.bfloat16)
        for i in range(n_layers):
            Wq, s, b = packed[3 * i], packed[3 * i + 1], packed[3 * i + 2]
            W = Wq.astype(jnp.bfloat16) * s.astype(jnp.bfloat16)[None, :]
            h = jnp.dot(h, W, preferred_element_type=jnp.float32) + b
            if i < n_layers - 1:
                h = jax.nn.sigmoid(h).astype(jnp.bfloat16)
        return h.astype(jnp.float32)

    return jax.jit(run)


def int8_mlp_program(sizes: tuple):
    from .jitcache import cached_jit

    return cached_jit("quant.mlp_score.int8", _build_int8_mlp_score,
                      tuple(int(s) for s in sizes))


def bf16_cast(a: np.ndarray) -> np.ndarray:
    """A block as bfloat16 itself (two bytes a value): what a model that is
    held in bfloat16 on the device is placed from. :func:`bf16_round` widens
    this back to f32, which for a tree of billions of parameters is twice
    the device memory the policy was chosen to save."""
    import ml_dtypes

    a = np.asarray(a)
    return a if a.dtype == ml_dtypes.bfloat16 else \
        a.astype(np.float32, copy=False).astype(ml_dtypes.bfloat16)


def bf16_round(a: np.ndarray) -> np.ndarray:
    """The ``bf16`` policy's numerics: round a block through bfloat16 and
    hand it back as f32. TPU bf16 matmuls accumulate in f32, so rounding
    the inputs and computing in the already-warmed f32 programs reproduces
    the bf16 result without tracing a single new program — the policy
    changes values, never shapes or dtypes on the wire."""
    return bf16_cast(a).astype(np.float32)


def _build_int8_tree_predict(depth: int):
    """Weight-only int8 twin of ``tree.predict``: leaf values dequantize
    in-kernel (per-tree per-output-dim scales); features and thresholds
    stay f32 so split routing — and therefore the traversal path — is
    bit-identical to the fp32 ensemble."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def run(X, feats, thrs, leaves_q, lscale, base_score):
        n = X.shape[0]

        def one_tree(f, t, lq, ls):
            node = jnp.zeros(n, jnp.int32)
            pos = jnp.zeros(n, jnp.int32)
            for _ in range(depth):
                fs = f[pos]
                ts = t[pos]
                safe = jnp.maximum(fs, 0)
                x = jnp.take_along_axis(X, safe[:, None], 1)[:, 0]
                left = (fs < 0) | (x <= ts)
                node = node * 2 + (1 - left.astype(jnp.int32))
                pos = 2 * pos + 1 + (1 - left.astype(jnp.int32))
            lv = lq.astype(jnp.float32) * ls[:, None]
            return lv[:, node]  # (K, n)

        scores = jax.vmap(one_tree)(feats, thrs, leaves_q, lscale)
        return scores.sum(0).T + base_score[None, :]

    return run


def int8_tree_program(depth: int):
    from .jitcache import cached_jit

    return cached_jit("quant.tree_predict.int8", _build_int8_tree_predict,
                      int(depth))


# ---------------------------------------------------------------------------
# accuracy-band gate
# ---------------------------------------------------------------------------


def _is_jsonish(v) -> bool:
    return isinstance(v, str) and v[:1] in ("{", "[")


def accuracy_band_report(base_rows, cand_rows, out_types,
                         *, band: float, tol: float) -> Dict[str, Any]:
    """Compare a quantized predict against its fp32 baseline over the
    calibration rows. Label-like (non-float) columns gate on agreement
    (disagreement fraction <= ``band``); numeric columns gate on relative
    deviation (max |Δ| / max(1, |base|) <= ``tol``). JSON-detail string
    columns are skipped — their low-order probability digits legitimately
    move under quantization and are not the serving contract. Returns
    ``{"ok", "agreement", "max_rel_diff", "band", "tol", "rows"}``."""
    from .mtable import AlinkTypes

    n = len(base_rows)
    agree_num = agree_den = 0
    max_rel = 0.0
    for bi, ci in zip(base_rows, cand_rows):
        for col, (bv, cv) in enumerate(zip(bi, ci)):
            tp = out_types[col] if col < len(out_types) else None
            numeric = tp in (AlinkTypes.DOUBLE, AlinkTypes.FLOAT) or (
                isinstance(bv, float) and not isinstance(bv, bool))
            if numeric and bv is not None and cv is not None:
                b = float(bv)
                c = float(cv)
                max_rel = max(max_rel, abs(b - c) / max(1.0, abs(b)))
                continue
            if _is_jsonish(bv):
                continue
            agree_den += 1
            try:
                agree_num += int(bool(bv == cv))
            except Exception:  # exotic cells (vectors/tensors)
                agree_num += int(str(bv) == str(cv))
    agreement = agree_num / agree_den if agree_den else 1.0
    ok = agreement >= 1.0 - band and max_rel <= tol
    return {"ok": bool(ok), "agreement": round(agreement, 6),
            "max_rel_diff": round(max_rel, 8), "band": band, "tol": tol,
            "rows": n}
