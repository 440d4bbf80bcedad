"""Pipelined DAG executor (common/executor.py): concurrent branch
scheduling, exactly-once shared upstreams, mapper-chain fusion parity,
double-buffered streaming, and the per-node trace."""

import threading
import time

import numpy as np
import pytest

from alink_tpu.common.metrics import executor_trace, metrics
from alink_tpu.common.mtable import AlinkTypes, MTable
from alink_tpu.mapper.base import BlockKernelMapper, FusedMapperChain
from alink_tpu.operator.batch import MemSourceBatchOp, TableSourceBatchOp
from alink_tpu.operator.batch.utils import MapBatchOp


def _affine_op(col, out, a, b):
    """A row-wise kernel mapper op: out = col * a + b (fp32 on device)."""

    class _M(BlockKernelMapper):
        def kernel(self, schema):
            def fn(X):
                return X * np.float32(a) + np.float32(b)

            return ([col], [out], [AlinkTypes.DOUBLE], fn)

    class _Op(MapBatchOp):
        mapper_cls = _M

    _Op.__name__ = f"Affine_{out}"
    return _Op()


def _table(n=64, seed=0):
    rng = np.random.RandomState(seed)
    return MTable({"x": rng.rand(n), "tag": np.asarray(
        [f"r{i}" for i in range(n)], object)})


# -- concurrent branch scheduling -------------------------------------------


def test_multi_branch_concurrent_and_exactly_once():
    """Two independent branches off one shared source: both run in wall
    clock < the serial sum, and the shared upstream computes exactly once."""
    calls = {"n": 0}
    lock = threading.Lock()
    SLEEP = 0.25

    class CountingSource(MemSourceBatchOp):
        def _execute_impl(self):
            with lock:
                calls["n"] += 1
            return super()._execute_impl()

    src = CountingSource([(float(i),) for i in range(32)], "v double")

    def slow_branch(name):
        def work(t):
            time.sleep(SLEEP)
            return MTable({name: np.asarray(t.col("v")) * 2.0})

        return src.apply_func(work, out_schema=f"{name} double")

    outs = {}
    slow_branch("a").lazy_collect(lambda t: outs.setdefault("a", t))
    slow_branch("b").lazy_collect(lambda t: outs.setdefault("b", t))
    t0 = time.perf_counter()
    src.execute()
    wall = time.perf_counter() - t0
    assert set(outs) == {"a", "b"}
    assert calls["n"] == 1                       # shared upstream: once
    assert wall < 2 * SLEEP * 0.9                # branches overlapped


def test_diamond_dag_schedules_all_and_memoizes():
    src = TableSourceBatchOp(_table())
    left = src.filter("x <= 0.5")
    right = src.filter("x > 0.5")
    import alink_tpu.operator.sql as sql

    join = sql.UnionAllOp().link_from(left, right)
    out = join.collect()
    assert out.num_rows == 64
    assert left._executed and right._executed and src._executed


def test_exception_propagates_from_scheduled_branch():
    src = TableSourceBatchOp(_table())

    def boom(t):
        raise RuntimeError("branch exploded")

    bad = src.apply_func(boom, out_schema="x double")
    with pytest.raises(RuntimeError, match="branch exploded"):
        bad.collect()


def test_first_failure_propagates_with_branches_in_flight():
    """A fast-failing branch raises while a slow sibling is mid-flight:
    the original exception propagates unchanged (same instance), the
    in-flight future is drained (no deadlock, slow branch completes), and
    the run returns promptly."""
    src = TableSourceBatchOp(_table())
    marker = RuntimeError("fast branch down")
    SLEEP = 0.3

    def fail_fast(t):
        raise marker

    slow_done = threading.Event()

    def slow(t):
        time.sleep(SLEEP)
        slow_done.set()
        return t

    bad = src.apply_func(fail_fast, out_schema="x double")
    ok = src.apply_func(slow, out_schema=_table().schema.to_str())
    got = {}
    bad.lazy_collect(lambda t: got.setdefault("bad", t))
    ok.lazy_collect(lambda t: got.setdefault("ok", t))
    try:
        with pytest.raises(RuntimeError) as ei:
            src.execute()
        assert ei.value is marker        # unchanged, not wrapped
        assert slow_done.is_set()        # in-flight branch was drained
        assert ok._executed
        assert got.get("ok") is not None  # completed sink still fired
    finally:
        # the always-failing sink stays pending by design (a later execute
        # would re-plan it); drop it so it can't poison other tests
        src.env.lazy_manager.clear()


def test_sink_callback_error_does_not_mask_dag_failure():
    """When a branch fails AND a completed sibling's lazy callback raises,
    the caller still sees the original DAG failure (the callback error is
    counted, not propagated), and other completed sinks still fire."""
    from alink_tpu.common.metrics import metrics

    src = TableSourceBatchOp(_table())
    marker = RuntimeError("real infrastructure failure")

    def fail(t):
        raise marker

    bad = src.apply_func(fail, out_schema="x double")
    ok1 = src.select(["x"])
    ok2 = src.select(["tag"])
    got = {}
    bad.lazy_collect(lambda t: got.setdefault("bad", t))
    ok1.lazy_collect(lambda t: (_ for _ in ()).throw(ValueError("cb bug")))
    ok2.lazy_collect(lambda t: got.setdefault("ok2", t))
    before = metrics.counter("resilience.sink_callback_errors")
    try:
        with pytest.raises(RuntimeError) as ei:
            src.execute()
        assert ei.value is marker
        assert got.get("ok2") is not None   # sibling sink still fired
        assert metrics.counter("resilience.sink_callback_errors") > before
    finally:
        src.env.lazy_manager.clear()


def test_failed_run_leaves_dag_recollectable_without_recompute():
    """After a branch fails, a second collect() re-plans only the
    unfinished sub-DAG: the shared upstream does NOT recompute."""
    calls = {"src": 0, "flaky": 0}
    lock = threading.Lock()

    class CountingSource(MemSourceBatchOp):
        def _execute_impl(self):
            with lock:
                calls["src"] += 1
            return super()._execute_impl()

    src = CountingSource([(float(i),) for i in range(16)], "v double")

    def flaky_once(t):
        with lock:
            calls["flaky"] += 1
            n = calls["flaky"]
        if n == 1:
            # fatal (not retryable): the run must fail, not retry
            raise ValueError("transient-looking but fatal")
        return MTable({"v": np.asarray(t.col("v")) * 2.0})

    good = src.apply_func(
        lambda t: MTable({"v": np.asarray(t.col("v")) + 1.0}),
        out_schema="v double")
    bad = good.apply_func(flaky_once, out_schema="v double")
    with pytest.raises(ValueError):
        bad.collect()
    assert calls["src"] == 1 and good._executed and not bad._executed
    out = bad.collect()                  # re-plan: only `bad` re-runs
    assert calls["src"] == 1             # memoized upstream untouched
    assert calls["flaky"] == 2
    np.testing.assert_array_equal(
        np.asarray(out.col("v")), (np.arange(16) + 1.0) * 2.0)


def test_serial_fallback_knob(monkeypatch):
    monkeypatch.setenv("ALINK_DAG_SCHEDULER", "off")
    src = TableSourceBatchOp(_table())
    out = src.select(["x"]).collect()
    assert out.num_rows == 64


# -- mapper-chain fusion -----------------------------------------------------


def _chain(src):
    c1 = _affine_op("x", "x1", 2.0, 1.0).link_from(src)
    c2 = _affine_op("x1", "x2", 0.5, -3.0).link_from(c1)
    c3 = _affine_op("x2", "x3", 4.0, 0.25).link_from(c2)
    return c1, c2, c3


def test_fused_chain_bit_identical_to_node_by_node(monkeypatch):
    t = _table(seed=3)

    monkeypatch.setenv("ALINK_DAG_FUSION", "0")
    _, _, tail_a = _chain(TableSourceBatchOp(t))
    unfused = tail_a.collect()

    monkeypatch.setenv("ALINK_DAG_FUSION", "1")
    c1, c2, tail_b = _chain(TableSourceBatchOp(t))
    fused = tail_b.collect()

    assert fused.schema == unfused.schema
    for col in fused.names:
        a, b = fused.col(col), unfused.col(col)
        if a.dtype == object:
            assert list(a) == list(b)
        else:
            np.testing.assert_array_equal(a, b)  # bit-identical
    # intermediates were never materialized by the fused run
    assert not c1._executed and not c2._executed
    assert tail_b._executed


def test_fusion_stops_at_shared_intermediate():
    """A chain member with a second consumer must materialize (it is needed
    by both paths) — fusion may not swallow it."""
    src = TableSourceBatchOp(_table(seed=4))
    c1 = _affine_op("x", "x1", 2.0, 0.0).link_from(src)
    c2 = _affine_op("x1", "x2", 3.0, 0.0).link_from(c1)
    side = c1.select(["x1"])  # second consumer of c1

    got = {}
    c2.lazy_collect(lambda t: got.setdefault("c2", t))
    side.lazy_collect(lambda t: got.setdefault("side", t))
    src.execute()
    assert c1._executed                      # materialized: it was shared
    np.testing.assert_array_equal(
        got["side"].col("x1"), got["c2"].col("x1"))


def test_fused_mapper_chain_kernels_compose():
    """FusedMapperChain over kernel mappers equals sequential map_table."""
    t = _table(seed=5)
    ops = [_affine_op("x", "x1", 2.0, 1.0), _affine_op("x1", "x2", 0.5, -3.0),
           _affine_op("x2", "x3", 4.0, 0.25)]
    schema = t.schema
    mappers = []
    for op in ops:
        m = op.mapper_cls(schema, op.get_params())
        mappers.append(m)
        schema = m.output_schema(schema)

    seq = t
    for m in mappers:
        seq = m.map_table(seq)
    fused = FusedMapperChain(mappers).map_table(t)
    assert fused.schema == seq.schema
    for col in ("x1", "x2", "x3"):
        np.testing.assert_array_equal(fused.col(col), seq.col(col))


def test_fused_chain_keeps_passthrough_columns():
    src = TableSourceBatchOp(_table(seed=6))
    _, _, tail = _chain(src)
    out = tail.collect()
    assert "tag" in out.names and "x" in out.names
    assert list(out.col("tag")) == [f"r{i}" for i in range(64)]


# -- per-node executor trace -------------------------------------------------


def _trace_since(before):
    """The records added after ``before`` was read, by their spans' ids: the
    trace is read from the span ring, and an offset into it means nothing
    once earlier tests of this process have filled it."""
    old = {r["span_id"] for r in before}
    return [r for r in executor_trace() if r["span_id"] not in old]


def test_executor_records_per_node_trace():
    before = executor_trace()
    src = TableSourceBatchOp(_table(seed=7))
    a = src.select(["x"])
    b = src.filter("x > 0.25")
    got = {}
    a.lazy_collect(lambda t: got.setdefault("a", t))
    b.lazy_collect(lambda t: got.setdefault("b", t))
    src.execute()
    trace = _trace_since(before)
    assert len(trace) >= 3                       # src + two branches
    assert all("op" in r and "wall_s" in r for r in trace)
    run = metrics.last("executor.run")
    assert run is not None and run["nodes"] >= 3


def test_trace_marks_fused_units():
    before = executor_trace()
    src = TableSourceBatchOp(_table(seed=8))
    _, _, tail = _chain(src)
    tail.collect()
    fused = [r for r in _trace_since(before) if r.get("fused")]
    assert fused and fused[0]["fused"] == 3
    assert "+" in fused[0]["op"]


# -- double-buffered streaming ----------------------------------------------


def test_stream_map_order_and_results():
    import jax.numpy as jnp

    from alink_tpu.common.streaming import iter_row_chunks, stream_map

    X = np.arange(1000, dtype=np.float32).reshape(250, 4)
    phases = {}
    outs = [
        (m, np.asarray(r))
        for m, r in stream_map(lambda a: jnp.sum(a, axis=1),
                               iter_row_chunks([X], 64), phases=phases)
    ]
    assert [m for m, _ in outs] == [64, 64, 64, 58]
    np.testing.assert_allclose(
        np.concatenate([r for _, r in outs]), X.sum(axis=1))
    assert phases["batches"] == 4
    assert phases["transfer_s"] >= 0 and phases["compute_s"] >= 0


def test_stream_map_split_transfers_bit_identical():
    """split=k ships each batch as k parallel chunk transfers reassembled
    on device — the compute fn must see bit-identical input."""
    import jax.numpy as jnp

    from alink_tpu.common.streaming import iter_row_chunks, stream_map

    X = np.random.RandomState(2).rand(250, 8).astype(np.float32)
    plain = [np.asarray(r) for _, r in stream_map(
        lambda a: jnp.tanh(a), iter_row_chunks([X], 100))]
    split = [np.asarray(r) for _, r in stream_map(
        lambda a: jnp.tanh(a), iter_row_chunks([X], 100), split=3)]
    assert len(plain) == len(split) == 3
    for a, b in zip(plain, split):
        np.testing.assert_array_equal(a, b)


def test_stream_map_through_staging_cache():
    from alink_tpu.common.staging import (clear_staging_cache,
                                          stage_replicated,
                                          staging_cache_stats)
    from alink_tpu.common.streaming import iter_row_chunks, stream_map

    clear_staging_cache()
    X = np.random.RandomState(0).rand(128, 4).astype(np.float32)

    def run():
        return [np.asarray(r) for _, r in stream_map(
            lambda a: a * 2, iter_row_chunks([X], 32),
            put=lambda arrs: [stage_replicated(a) for a in arrs])]

    r1, r2 = run(), run()
    for a, b in zip(r1, r2):
        np.testing.assert_array_equal(a, b)
    assert staging_cache_stats()["hits"] >= 4   # second pass was free


def test_ingest_mapper_still_batches_through_stream(tmp_path):
    """The torch ingest path (uses stream_map under the hood) stays exact."""
    torch = pytest.importorskip("torch")
    import torch.nn as nn

    from alink_tpu.operator.batch import TorchModelPredictBatchOp

    torch.manual_seed(0)
    model = nn.Linear(4, 1).eval()
    ep = torch.export.export(model, (torch.randn(2, 4),))
    path = str(tmp_path / "m.pt2")
    torch.export.save(ep, path)

    X = np.random.RandomState(1).randn(300, 4).astype(np.float64)
    src = TableSourceBatchOp(MTable({f"f{i}": X[:, i] for i in range(4)}))
    out = TorchModelPredictBatchOp(
        modelPath=path, selectedCols=[f"f{i}" for i in range(4)],
        outputCols=["s"], predictBatchSize=64).link_from(src).collect()
    want = model(torch.tensor(X, dtype=torch.float32)).detach().numpy()[:, 0]
    np.testing.assert_allclose(
        np.asarray(out.col("s")), want, rtol=1e-5, atol=1e-5)
