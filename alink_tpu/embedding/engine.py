"""Engine selection for the huge-embedding family.

The reference decides between the in-JVM trainer and the APS
(parameter-server) path per op (huge/impl/Word2VecImpl & friends over
ApsEnv); here the decision is one knob spanning the whole family —
Word2Vec, DeepWalk/Node2Vec embeddings, MetaPath2Vec, LINE:

- ``sharded`` (default): tables row-sharded over the ``model`` mesh axis,
  owner-routed O(B·D) pull/push + hot-key cache (``parallel/aps.py``,
  ``parallel/hotcache.py``) — the pod-scale path, and safe to default
  because it is bit-identical to the host engine at equal seed.
- ``host``: replicated tables, gathered scatter-add updates — the
  single-chip reference twin.

``ALINK_HUGE_ENGINE`` overrides the default; unrecognized values fall back
to ``sharded`` (a typoed tuning knob must not crash a job — both engines
compute identical bits, only the comm pattern differs) and are counted in
``huge.engine_bad_knob``.
"""

from __future__ import annotations

import logging
from typing import Optional

import numpy as np

from ..parallel.mesh import data_axis_size
from .skipgram import SkipGramConfig, train_skipgram, train_skipgram_sharded

_ENGINES = ("sharded", "host")
_log = logging.getLogger("alink_tpu.embedding")


def huge_engine(override: Optional[str] = None) -> str:
    """Resolve the active engine: explicit ``override`` >
    ``ALINK_HUGE_ENGINE`` > ``sharded``."""
    from ..common.env import env_str

    raw = override if override is not None \
        else (env_str("ALINK_HUGE_ENGINE", "sharded") or "sharded")
    val = raw.strip().lower()
    if val in _ENGINES:
        return val
    from ..common.metrics import metrics

    metrics.incr("huge.engine_bad_knob")
    _log.warning("unrecognized huge-embedding engine %r; using 'sharded' "
                 "(valid: %s)", raw, "|".join(_ENGINES))
    return "sharded"


def train_embedding(
    pairs: np.ndarray,
    vocab_size: int,
    counts: np.ndarray,
    cfg: SkipGramConfig,
    *,
    engine: Optional[str] = None,
    mesh=None,
    hot_rows: Optional[int] = None,
) -> np.ndarray:
    """Train SGNS through the resolved engine; returns the (V, dim) input
    table on host either way. ``mesh`` is the caller's data mesh — the
    sharded engine builds its model-axis mesh over the mesh's DATA-axis
    size (:func:`~alink_tpu.parallel.mesh.data_axis_size`), so both
    engines see equal axis sizes and stay bit-identical."""
    if huge_engine(engine) == "host":
        return train_skipgram(pairs, vocab_size, counts, cfg, mesh=mesh)
    from ..parallel.aps import model_mesh

    m = model_mesh(data_axis_size(mesh)) if mesh is not None else None
    handle = train_skipgram_sharded(pairs, vocab_size, counts, cfg,
                                    mesh=m, hot_rows=hot_rows)
    return handle.to_numpy()


def collective_bytes_probe(m: int, engine: str, *, hot_rows: int = 0,
                           rows: int = 64, dim: int = 16, batch: int = 32,
                           negatives: int = 3, zipf_a: float = 1.2) -> int:
    """Per-device steady-state collective bytes of ONE compiled SGNS
    training program on an ``m``-device mesh — the weak-scaling probe of
    ``tests/test_weak_scaling.py`` (one recipe, one set of constants).
    Weak scaling: rows-per-shard, per-device batch, and dim stay
    constant while the vocabulary (``rows·m``) grows with the mesh;
    the frequency table is Zipf-ish so the hot-key cache has a head to
    serve. Compile-only (``_lower_only``): nothing executes."""
    import jax

    from ..common.profiling import collective_bytes
    from ..parallel.aps import model_mesh
    from ..parallel.mesh import default_mesh

    V = rows * m
    counts = 1000.0 / (np.arange(V) + 1.0) ** zipf_a
    p = counts / counts.sum()
    pairs = np.random.default_rng(0).choice(
        V, size=(batch * m, 2), p=p).astype(np.int32)
    cfg = SkipGramConfig(dim=dim, window=2, negatives=negatives, epochs=1,
                         batch_size=batch, seed=0)
    if engine == "host":
        lowered = train_skipgram(pairs, V, counts, cfg,
                                 mesh=default_mesh(jax.devices()[:m]),
                                 _lower_only=True)
    else:
        lowered = train_skipgram_sharded(pairs, V, counts, cfg,
                                         mesh=model_mesh(m),
                                         hot_rows=hot_rows,
                                         _lower_only=True)
    return collective_bytes(lowered.compile())
