"""Device-staging cache + wire precision (common/staging.py).

Reference analog: SessionSharedObjs.cachePartitionedData
(core/.../common/comqueue/SessionSharedObjs.java:158) — here content-keyed
and spanning jobs."""

import numpy as np
import pytest

from alink_tpu.common.env import AlinkGlobalConfiguration
from alink_tpu.common.staging import (
    clear_staging_cache,
    stage_replicated,
    stage_sharded,
    staging_cache,
    staging_cache_stats,
)
from alink_tpu.parallel.comqueue import shard_rows
from alink_tpu.parallel.mesh import default_mesh


@pytest.fixture(autouse=True)
def _clean_cache():
    clear_staging_cache()
    yield
    clear_staging_cache()
    AlinkGlobalConfiguration.set_wire_precision("auto")


def test_repeat_staging_hits_cache():
    mesh = default_mesh()
    X = np.random.RandomState(0).normal(size=(100, 8)).astype(np.float32)
    a = shard_rows(mesh, X)
    b = shard_rows(mesh, X.copy())  # same content, different buffer
    assert a is b
    stats = staging_cache_stats()
    assert stats["hits"] >= 1


def test_different_content_misses():
    mesh = default_mesh()
    X = np.ones((50, 4), np.float32)
    Y = np.zeros((50, 4), np.float32)
    a = shard_rows(mesh, X)
    b = shard_rows(mesh, Y)
    assert a is not b
    assert float(np.asarray(a).sum()) == 200.0
    assert float(np.asarray(b).sum()) == 0.0


def test_mask_cached_and_correct():
    mesh = default_mesh()
    n_shards = mesh.shape["data"]
    n = 7 * n_shards + 3  # forces padding
    X = np.arange(n * 2, dtype=np.float32).reshape(n, 2)
    a, m = shard_rows(mesh, X, with_mask=True)
    m_np = np.asarray(m)
    assert m_np[:n].sum() == n
    assert m_np[n:].sum() == 0
    _, m2 = shard_rows(mesh, X, with_mask=True)
    assert m is m2


def test_bf16_wire_upcasts_to_fp32():
    mesh = default_mesh()
    AlinkGlobalConfiguration.set_wire_precision("bf16")
    X = np.random.RandomState(1).normal(size=(64, 16)).astype(np.float32)
    a = shard_rows(mesh, X)
    assert a.dtype == np.float32
    # bf16 has ~3 decimal digits; values round but stay close
    np.testing.assert_allclose(np.asarray(a)[:64], X, rtol=8e-3, atol=8e-3)
    stats = staging_cache_stats()
    assert stats["wire_bytes_saved"] > 0


def test_fp32_policy_is_exact():
    mesh = default_mesh()
    AlinkGlobalConfiguration.set_wire_precision("fp32")
    X = np.random.RandomState(2).normal(size=(64, 16)).astype(np.float32)
    a = shard_rows(mesh, X)
    np.testing.assert_array_equal(np.asarray(a)[:64], X)
    assert staging_cache_stats()["wire_bytes_saved"] == 0


def test_auto_policy_keeps_small_blocks_exact():
    mesh = default_mesh()
    X = np.random.RandomState(3).normal(size=(64, 16)).astype(np.float32)
    a = shard_rows(mesh, X)  # 4KB << 4MB threshold
    np.testing.assert_array_equal(np.asarray(a)[:64], X)


def test_auto_policy_big_block_exact_on_fast_wire(monkeypatch):
    """auto is precision-safe by default: a ≥4 MiB float block stays exact
    fp32 on a fast (local/PCIe-class) wire — bf16 only engages when the
    link measures slow."""
    monkeypatch.setenv("ALINK_ASSUME_SLOW_WIRE", "0")
    X = np.random.RandomState(4).normal(size=(1 << 20, 2)).astype(np.float32)
    assert X.nbytes >= 4 * 1024 * 1024
    a = stage_replicated(X)
    np.testing.assert_array_equal(np.asarray(a), X)
    assert staging_cache_stats()["wire_bytes_saved"] == 0


def test_auto_policy_big_block_bf16_on_slow_wire(monkeypatch):
    """...and the slow-link gate actually exercises the bf16 tradeoff on
    the same ≥4 MiB block: wire bytes halve, values round to bf16."""
    monkeypatch.setenv("ALINK_ASSUME_SLOW_WIRE", "1")
    X = np.random.RandomState(5).normal(size=(1 << 20, 2)).astype(np.float32)
    assert X.nbytes >= 4 * 1024 * 1024
    a = stage_replicated(X)
    assert a.dtype == np.float32
    got = np.asarray(a)
    np.testing.assert_allclose(got, X, rtol=8e-3, atol=8e-3)  # bf16 rounding
    assert (got != X).any()  # the downcast really happened
    assert staging_cache_stats()["wire_bytes_saved"] == X.nbytes // 2


def test_auto_cache_key_tracks_slow_gate(monkeypatch):
    """Flipping the slow-wire gate mid-process must not serve a bf16-rounded
    cached array to a caller expecting exact fp32 (the key carries the
    effective auto decision, not just the policy name)."""
    monkeypatch.setenv("ALINK_ASSUME_SLOW_WIRE", "1")
    X = np.random.RandomState(6).normal(size=(1 << 20, 2)).astype(np.float32)
    a = np.asarray(stage_replicated(X))
    assert (a != X).any()                      # slow gate: bf16 wire
    monkeypatch.setenv("ALINK_ASSUME_SLOW_WIRE", "0")
    b = np.asarray(stage_replicated(X))
    np.testing.assert_array_equal(b, X)        # fast gate: exact, no reuse


def test_wire_stats_are_locked_under_concurrency():
    """stage_* from many threads (the pipelined executor does this) must not
    lose wire-byte updates: total sent == sum of distinct block sizes."""
    import threading

    AlinkGlobalConfiguration.set_wire_precision("fp32")
    blocks = [np.full((256, 16), float(i), np.float32) for i in range(16)]
    threads = [threading.Thread(target=stage_replicated, args=(b,))
               for b in blocks]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    assert staging_cache_stats()["wire_bytes_sent"] == sum(
        b.nbytes for b in blocks)


def test_int_arrays_never_downcast():
    mesh = default_mesh()
    AlinkGlobalConfiguration.set_wire_precision("bf16")
    idx = np.arange(128, dtype=np.int32).reshape(64, 2)
    a = shard_rows(mesh, idx)
    assert a.dtype == np.int32
    np.testing.assert_array_equal(np.asarray(a)[:64], idx)


def test_replicated_staging_cached():
    a = stage_replicated(np.full((10, 3), 2.5, np.float32))
    b = stage_replicated(np.full((10, 3), 2.5, np.float32))
    assert a is b


def test_eviction_by_bytes():
    cache = staging_cache()
    old = cache.max_bytes
    try:
        mesh = default_mesh()
        cache.set_max_bytes(300 * 1024)
        for i in range(8):
            shard_rows(mesh, np.full((100, 100), float(i), np.float32))  # 40KB each
        stats = staging_cache_stats()
        assert stats["resident_bytes"] <= 300 * 1024
        assert stats["evictions"] > 0
    finally:
        cache.set_max_bytes(old)


def test_mtable_block_memoized():
    from alink_tpu.common.mtable import MTable

    t = MTable({"a": np.arange(5, dtype=np.float64),
                "b": np.arange(5, dtype=np.float64)})
    b1 = t.to_numeric_block(["a", "b"])
    b2 = t.to_numeric_block(["a", "b"])
    assert b1 is b2
    assert not b1.flags.writeable
    # different projection is a different block
    b3 = t.to_numeric_block(["a"])
    assert b3.shape == (5, 1)


def test_optimize_twice_reuses_staged_features():
    """The L-BFGS path (the softmax bench shape) must hit the cache on rerun."""
    from alink_tpu.optim.objfunc import softmax_obj
    from alink_tpu.optim.optimizers import optimize

    rng = np.random.RandomState(0)
    X = rng.normal(size=(256, 10)).astype(np.float32)
    y = rng.randint(0, 3, 256).astype(np.float32)
    obj = softmax_obj(10, 3)
    r1 = optimize(obj, X, y, max_iter=5)
    before = staging_cache_stats()["hits"]
    r2 = optimize(obj, X, y, max_iter=5)
    after = staging_cache_stats()["hits"]
    assert after > before
    np.testing.assert_allclose(r1.weights, r2.weights, rtol=1e-6)
