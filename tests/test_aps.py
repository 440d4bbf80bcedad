"""APS-analog sharded embedding tests.

Validates the model-axis pull/push engine on the 8-virtual-device CPU mesh
(reference behavior: operator/common/aps/ApsEnv.java pull→train→push with the
model partitioned by key across tasks)."""

import numpy as np
import pytest

from alink_tpu.embedding import (
    SkipGramConfig,
    build_vocab,
    make_pairs,
    train_skipgram,
    train_skipgram_sharded,
)
from alink_tpu.parallel.aps import (
    ShardedEmbedding,
    bucket_capacity,
    model_mesh,
    pull,
    pull_allgather,
    push,
    push_allgather,
)
from alink_tpu.parallel.mesh import AXIS_MODEL
from alink_tpu.parallel.shardmap import shard_map


def test_table_shards_over_model_axis():
    import jax

    mesh = model_mesh()
    m = mesh.shape[AXIS_MODEL]
    assert m == len(jax.devices())
    table = ShardedEmbedding(mesh, vocab_size=20, dim=8)
    # 20 rows pad to a multiple of the axis size; every device holds one shard
    shapes = table.shard_shapes()
    assert len(shapes) == m
    assert all(s == (table.rows_per_shard, 8) for s in shapes)
    assert table.rows_per_shard * m == table.padded_rows >= 20
    # host roundtrip drops the padding
    assert table.to_numpy().shape == (20, 8)


def test_pull_fetches_correct_rows():
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = model_mesh()
    m = mesh.shape[AXIS_MODEL]
    V, D = 4 * m, 3
    base = np.arange(V * D, dtype=np.float32).reshape(V, D)
    table = ShardedEmbedding(mesh, V, D, init=lambda rng: base.copy())
    rows = table.rows_per_shard
    # every device asks for a DIFFERENT id set
    rng = np.random.default_rng(0)
    ids = rng.integers(0, V, size=(m, 5)).astype(np.int32)

    def body(table_l, ids_l):
        return pull(table_l, ids_l[0], AXIS_MODEL, rows)

    f = jax.jit(shard_map(
        body, mesh=mesh, in_specs=(P(AXIS_MODEL), P(AXIS_MODEL)),
        out_specs=P(AXIS_MODEL), check_vma=False))
    got = np.asarray(jax.device_get(f(table.array, jnp.asarray(ids))))
    # output is (m*5, D): device i's 5 pulled rows at block i
    for dev in range(m):
        np.testing.assert_allclose(got[dev * 5:(dev + 1) * 5], base[ids[dev]])


def test_push_updates_owned_rows_once():
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = model_mesh()
    m = mesh.shape[AXIS_MODEL]
    V, D = 2 * m, 2
    table = ShardedEmbedding(mesh, V, D,
                             init=lambda rng: np.zeros((V, D), np.float32))
    rows = table.rows_per_shard
    # every device pushes gradient 1.0 to id 0 and to its own id dev*2
    ids = np.stack([np.zeros(m, np.int32),
                    (np.arange(m) * 2).astype(np.int32)], axis=1)  # (m, 2)
    grads = np.ones((m, 2, D), np.float32)

    def body(table_l, ids_l, grads_l):
        return push(table_l, ids_l[0], grads_l[0], AXIS_MODEL, rows,
                    scale=-1.0)  # negative scale => += grads

    f = jax.jit(shard_map(
        body, mesh=mesh,
        in_specs=(P(AXIS_MODEL), P(AXIS_MODEL), P(AXIS_MODEL)),
        out_specs=P(AXIS_MODEL), check_vma=False))
    table.array = f(table.array, jnp.asarray(ids), jnp.asarray(grads))
    result = table.to_numpy()
    # id 0: one push from every device PLUS device 0's "own id" (0*2 == 0)
    np.testing.assert_allclose(result[0], np.full(D, float(m + 1)))
    # each even id (from device d>=1) got exactly one push
    for dev in range(1, m):
        np.testing.assert_allclose(result[dev * 2], np.ones(D))
    # odd ids untouched
    assert (result[1::2] == 0).all()


def _toy_corpus():
    docs = []
    for _ in range(60):
        docs.append("cat dog cat dog cat dog".split())
        docs.append("sun moon sun moon sun moon".split())
    return docs


def test_sharded_sgns_learns_cooccurrence():
    docs = _toy_corpus()
    vocab, counts = build_vocab(docs)
    cfg = SkipGramConfig(dim=16, window=2, negatives=3, epochs=8,
                         batch_size=64, seed=1)
    pairs = make_pairs(docs, vocab, counts, cfg.window, 0.0, cfg.seed)
    handle = train_skipgram_sharded(pairs, len(vocab), counts, cfg)
    emb = handle.to_numpy()
    assert emb.shape == (len(vocab), 16)
    # the sharded handle stays sharded on device
    import jax
    assert len(handle.shard_shapes()) == len(jax.devices())

    def cos(a, b):
        return float(a @ b / (np.linalg.norm(a) * np.linalg.norm(b) + 1e-12))

    cat, dog = emb[vocab["cat"]], emb[vocab["dog"]]
    sun = emb[vocab["sun"]]
    assert cos(cat, dog) > cos(cat, sun)


def test_sharded_matches_replicated_bitwise():
    """The host (replicated) and sharded trainers share one per-step
    contract — identical pair blocks, negative streams, and per-row update
    sequences — so their results are bit-identical at equal seed (the
    ALINK_HUGE_ENGINE parity guarantee), and both learn the structure."""
    docs = _toy_corpus()
    vocab, counts = build_vocab(docs)
    cfg = SkipGramConfig(dim=16, window=2, negatives=3, epochs=8,
                         batch_size=64, seed=2)
    pairs = make_pairs(docs, vocab, counts, cfg.window, 0.0, cfg.seed)
    emb_rep = train_skipgram(pairs, len(vocab), counts, cfg)
    emb_sh = train_skipgram_sharded(pairs, len(vocab), counts, cfg).to_numpy()
    np.testing.assert_array_equal(emb_rep, emb_sh)

    def cos(E, a, b):
        va, vb = E[vocab[a]], E[vocab[b]]
        return float(va @ vb / (np.linalg.norm(va) * np.linalg.norm(vb) + 1e-12))

    assert cos(emb_rep, "cat", "dog") > cos(emb_rep, "cat", "moon")


# ---------------------------------------------------------------------------
# owner-routed vs all-gather reference: bit-exactness + overflow handling
# ---------------------------------------------------------------------------


def _routed_vs_gather(V, D, ids, grads=None, slack=None):
    """Run routed and all-gather pull (or push) on identical inputs; return
    the pair of host arrays. ``ids``: (m, B) per-device batches."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    mesh = model_mesh()
    m = mesh.shape[AXIS_MODEL]
    assert ids.shape[0] == m
    rng = np.random.default_rng(7)
    base = rng.normal(size=(V, D)).astype(np.float32)
    table = ShardedEmbedding(mesh, V, D, init=lambda r: base.copy())
    rows = table.rows_per_shard

    if grads is None:
        def routed(tl, i):
            return pull(tl, i[0], AXIS_MODEL, rows, slack=slack)

        def gather(tl, i):
            return pull_allgather(tl, i[0], AXIS_MODEL, rows)
    else:
        def routed(tl, i, g):
            return push(tl, i[0], g[0], AXIS_MODEL, rows, scale=0.5,
                        slack=slack)

        def gather(tl, i, g):
            return push_allgather(tl, i[0], g[0], AXIS_MODEL, rows,
                                  scale=0.5)

    spec = (P(AXIS_MODEL),) * (2 if grads is None else 3)
    args = [table.array, jnp.asarray(ids)]
    if grads is not None:
        args.append(jnp.asarray(grads))
    out = []
    for body in (routed, gather):
        f = jax.jit(shard_map(body, mesh=mesh, in_specs=spec,
                              out_specs=P(AXIS_MODEL), check_vma=False))
        out.append(np.asarray(jax.device_get(f(*args))))
    return out


def test_routed_pull_bit_identical_to_gather():
    import jax

    m = len(jax.devices())
    V, D, B = 16 * m, 5, 12
    rng = np.random.default_rng(3)
    # duplicates on purpose: dedup + inverse mapping must reconstruct
    ids = rng.integers(0, V, size=(m, B)).astype(np.int32)
    ids[:, B // 2:] = ids[:, :B - B // 2]
    routed, gathered = _routed_vs_gather(V, D, ids)
    np.testing.assert_array_equal(routed, gathered)


def test_routed_pull_overflow_remainder_bit_identical():
    import jax

    m = len(jax.devices())
    if m < 2:
        pytest.skip("needs a multi-device mesh")
    V, D, B = 16 * m, 4, 16
    # every device asks for B DISTINCT rows all owned by shard 0 with
    # slack=1.0: capacity ceil(B/m) < B forces the overflow fallback
    assert bucket_capacity(B, m, 1.0) < B
    ids = np.tile(np.arange(B, dtype=np.int32), (m, 1))
    routed, gathered = _routed_vs_gather(V, D, ids, slack=1.0)
    np.testing.assert_array_equal(routed, gathered)


def test_routed_push_bit_identical_to_gather():
    import jax

    m = len(jax.devices())
    V, D, B = 16 * m, 5, 12
    rng = np.random.default_rng(4)
    ids = rng.integers(0, V, size=(m, B)).astype(np.int32)
    ids[:, -2:] = ids[:, :2]          # cross- and within-device duplicates
    grads = rng.normal(size=(m, B, D)).astype(np.float32)
    routed, gathered = _routed_vs_gather(V, D, ids, grads=grads)
    np.testing.assert_array_equal(routed, gathered)


def test_routed_push_overflow_remainder_bit_identical():
    import jax

    m = len(jax.devices())
    if m < 2:
        pytest.skip("needs a multi-device mesh")
    V, D, B = 16 * m, 4, 16
    rng = np.random.default_rng(5)
    ids = np.tile(np.arange(B, dtype=np.int32), (m, 1))   # all on shard 0
    grads = rng.normal(size=(m, B, D)).astype(np.float32)
    routed, gathered = _routed_vs_gather(V, D, ids, grads=grads, slack=1.0)
    np.testing.assert_array_equal(routed, gathered)


def test_bucket_overflow_counter_increments():
    import jax

    from alink_tpu.common.metrics import metrics

    m = len(jax.devices())
    if m < 2:
        pytest.skip("needs a multi-device mesh")
    V, D, B = 16 * m, 4, 16
    ids = np.tile(np.arange(B, dtype=np.int32), (m, 1))
    before = metrics.counter("aps.bucket_overflows")
    _routed_vs_gather(V, D, ids, slack=1.0)
    jax.effects_barrier()
    after = metrics.counter("aps.bucket_overflows")
    # every device overflows B - ceil(B/m) unique ids
    assert after - before == m * (B - bucket_capacity(B, m, 1.0))


def test_bucket_slack_env_knob(monkeypatch):
    from alink_tpu.parallel.aps import bucket_capacity, bucket_slack

    monkeypatch.setenv("ALINK_APS_BUCKET_SLACK", "3.5")
    assert bucket_slack() == 3.5
    assert bucket_capacity(8, 4) == 7
    monkeypatch.setenv("ALINK_APS_BUCKET_SLACK", "0.25")
    assert bucket_slack() == 1.0        # clamped: capacity never shrinks B/M
    monkeypatch.setenv("ALINK_APS_BUCKET_SLACK", "0")
    assert bucket_slack() == 1.0        # explicit 0 clamps too, not default
    monkeypatch.delenv("ALINK_APS_BUCKET_SLACK")
    assert bucket_slack(3.0) == 3.0


def test_estimator_shard_map_fit_path_runs_in_container():
    """A KMeans ``Pipeline.fit``, whose Lloyd kernel is
    ``jax.jit(shard_map(...))``, runs in-container through
    ``parallel/shardmap.py``."""
    from alink_tpu.common.mtable import MTable
    from alink_tpu.operator.batch.base import TableSourceBatchOp
    from alink_tpu.pipeline import KMeans, Pipeline

    rng = np.random.default_rng(9)
    blob = np.concatenate([rng.normal(-4, 0.3, size=(40, 2)),
                           rng.normal(4, 0.3, size=(40, 2))])
    t = MTable({"a": blob[:, 0], "b": blob[:, 1]})
    src = TableSourceBatchOp(t)
    pipe = Pipeline(KMeans(k=2, maxIter=20, featureCols=["a", "b"],
                           predictionCol="pred"))
    pred = np.asarray(pipe.fit(src).transform(src).collect().col("pred"))
    # the two well-separated blobs land in two distinct clusters
    assert len(set(pred[:40])) == 1 and len(set(pred[40:])) == 1
    assert pred[0] != pred[-1]


def test_routed_parity_stress_skewed_batches():
    """Zipf-ish id batches (frequency-sorted vocab concentrates load on
    shard 0) across slack settings: routed pull AND push stay bit-identical
    to the all-gather reference in every overflow regime."""
    import jax

    m = len(jax.devices())
    V, D, B = 16 * m, 3, 10
    rng = np.random.default_rng(11)
    for trial, slack in enumerate((1.0, 1.5, None)):
        raw = rng.zipf(1.6, size=(m, B)).astype(np.int64)
        ids = np.minimum(raw - 1, V - 1).astype(np.int32)
        grads = rng.normal(size=(m, B, D)).astype(np.float32)
        r_pull, g_pull = _routed_vs_gather(V, D, ids, slack=slack)
        np.testing.assert_array_equal(r_pull, g_pull, err_msg=f"pull {trial}")
        r_push, g_push = _routed_vs_gather(V, D, ids, grads=grads,
                                           slack=slack)
        np.testing.assert_array_equal(r_push, g_push, err_msg=f"push {trial}")
