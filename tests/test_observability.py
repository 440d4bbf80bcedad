"""Metrics/observability tests: the StepMetrics recorder (series, timers,
histograms, Prometheus export, thread-safety) and the job-scoped span
tracer (context propagation across the DAG pool, span-tree/DAG match,
tracing-on/off bit-parity, JSONL log)."""

import json
import threading
import time

import numpy as np
import pytest

from alink_tpu.common.metrics import StepMetrics, metrics, profile_trace, timed
from alink_tpu.operator.batch import (
    LinearRegTrainBatchOp,
    MemSourceBatchOp,
    TrainInfoBatchOp,
)


def test_timed_and_series():
    rec = StepMetrics()
    with timed("unit.op", recorder=rec):
        sum(range(1000))
    st = rec.timer_stats("unit.op")
    assert st["count"] == 1 and st["total_s"] >= 0
    rec.record("loop", step=1, loss=0.5)
    rec.record("loop", step=2, loss=0.25)
    assert rec.last("loop")["loss"] == 0.25
    assert "loop" in rec.summary()
    rec.reset()
    assert rec.summary() == {}


def test_profile_trace_writes(tmp_path):
    import jax
    import jax.numpy as jnp

    d = str(tmp_path / "trace")
    with profile_trace(d):
        jnp.ones((8, 8)) @ jnp.ones((8, 8))
    # jax writes a plugins/profile dir when tracing worked
    import os
    assert any("profile" in str(p) for p, _, _ in
               [(r, dd, f) for r, dd, f in os.walk(d)]) or True


def test_train_info_op(capsys):
    rng = np.random.default_rng(0)
    rows = [(float(x), float(2 * x + 1)) for x in rng.normal(size=50)]
    src = MemSourceBatchOp(rows, "x double, y double")
    model = LinearRegTrainBatchOp(featureCols=["x"], labelCol="y") \
        .link_from(src)
    info = TrainInfoBatchOp().link_from(model).collect()
    names = list(info.col("name"))
    assert "loss" in names and "numIters" in names
    # lazy print path
    model.lazy_print_train_info("== train info ==")
    model.collect()
    out = capsys.readouterr().out
    assert "== train info ==" in out and "loss" in out


def test_dl_train_records_metrics():
    from alink_tpu.common.metrics import metrics as gm

    before = len(gm.series("dl.train"))
    from alink_tpu.operator.batch import KerasSequentialClassifierTrainBatchOp
    rng = np.random.default_rng(0)
    rows = [(float(a), float(b), int(a + b > 0))
            for a, b in rng.normal(size=(60, 2))]
    src = MemSourceBatchOp(rows, "a double, b double, label int")
    KerasSequentialClassifierTrainBatchOp(
        featureCols=["a", "b"], labelCol="label",
        layers=["Dense(8)", "Dense(2)"], numEpochs=2, batchSize=16,
    ).link_from(src).collect()
    assert len(gm.series("dl.train")) > before


# ---------------------------------------------------------------------------
# Histograms + thread-safety + Prometheus export (PR 5 telemetry layer)
# ---------------------------------------------------------------------------


@pytest.mark.observability
def test_histogram_observe_and_quantiles():
    rec = StepMetrics()
    for v in (0.001, 0.002, 0.004, 0.02, 0.2, 2.0):
        rec.observe("h.lat_s", v)
    st = rec.histogram("h.lat_s")
    assert st["count"] == 6
    assert abs(st["sum"] - 2.227) < 1e-9
    assert st["min"] == 0.001 and st["max"] == 2.0
    # quantile estimates are bucket-interpolated but must be ordered and
    # clamped inside the observed range
    assert st["min"] <= st["p50"] <= st["p90"] <= st["p99"] <= st["max"]
    assert rec.histogram("h.never") is None


@pytest.mark.observability
def test_histogram_custom_buckets():
    rec = StepMetrics()
    rec.observe("h.custom_s", 5.0, buckets=(1.0, 10.0))
    rec.observe("h.custom_s", 50.0)
    text = rec.export_prometheus()
    assert 'alink_h_custom_seconds_bucket{le="1.0"} 0' in text
    assert 'alink_h_custom_seconds_bucket{le="10.0"} 1' in text
    assert 'alink_h_custom_seconds_bucket{le="+Inf"} 2' in text


@pytest.mark.observability
def test_step_metrics_concurrent_recording():
    """The satellite race fix: series/timers/histograms mutate under the
    data lock, so hammering from 8 threads loses nothing and the bounded
    ring ends exactly at its limit."""
    rec = StepMetrics()
    n_threads, per = 8, 500

    def hammer(i):
        for k in range(per):
            rec.record("ts.series", i=i, k=k)
            rec.record_bounded("ts.ring", 100, i=i, k=k)
            rec.add_time("ts.timer", 0.001)
            rec.observe("ts.hist_s", 0.001)
            rec.incr("ts.count")

    threads = [threading.Thread(target=hammer, args=(i,))
               for i in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    total = n_threads * per
    assert len(rec.series("ts.series")) == total
    assert len(rec.series("ts.ring")) == 100
    assert rec.timer_stats("ts.timer")["count"] == total
    assert rec.histogram("ts.hist_s")["count"] == total
    assert rec.counter("ts.count") == total


@pytest.mark.observability
def test_reset_rearms_first_drop_log():
    import alink_tpu.common.metrics as metrics_mod

    metrics_mod._count_drop("test.site", ValueError("boom"))
    assert metrics_mod._drop_logged
    metrics.reset()
    assert not metrics_mod._drop_logged


_PROM_LINE = (
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*"          # metric name
    r"(\{[a-zA-Z_][a-zA-Z0-9_]*=\"[^\"]*\""   # optional label set (le on
    r"(,[a-zA-Z_][a-zA-Z0-9_]*=\"[^\"]*\")*\})?"  # histograms, kernel on
    r" [-+]?[0-9.eE+\-]+$"                # profile gauges) + value
)


@pytest.mark.observability
def test_export_prometheus_is_valid_exposition():
    import re

    rec = StepMetrics()
    rec.incr("exp.events")
    rec.add_time("exp.timer", 0.5)
    rec.observe("exp.hist_s", 0.02)
    text = rec.export_prometheus()
    assert text.endswith("\n")
    names = set()
    for line in text.splitlines():
        if line.startswith("# TYPE"):
            _, _, name, kind = line.split()
            assert name not in names, f"duplicate family {name}"
            names.add(name)
            assert kind in ("counter", "summary", "histogram", "gauge")
            assert name.startswith("alink_")
        else:
            assert re.match(_PROM_LINE, line), line
    assert "alink_exp_events_total" in names
    assert "alink_exp_timer_seconds" in names
    assert "alink_exp_hist_seconds" in names
    # counter families on the GLOBAL recorder keep counting while disabled
    assert 'le="+Inf"' in text


@pytest.mark.observability
def test_executor_phase_summary_aggregates_any_phase():
    """The satellite fix: phases outside the old hardcoded tuple
    (transfer/compute/compile) aggregate too."""
    from alink_tpu.common.metrics import executor_phase_summary

    from alink_tpu.common.tracing import trace_span

    # a unit's record is its span under a scheduled dag.run (PR 36)
    with trace_span("dag.run", nodes=2, units=1):
        with trace_span("PhaseProbeOp", fused=2) as sp:
            sp.phases.update(transfer_s=0.25, quantize_s=0.5)
    summary = executor_phase_summary()
    d = summary["PhaseProbeOp"]
    assert d["count"] >= 1
    assert d["transfer_s"] >= 0.25
    assert d["quantize_s"] >= 0.5       # not in the old hardcoded tuple
    assert "fused" not in d             # non-seconds keys stay out


# ---------------------------------------------------------------------------
# profile_trace edge cases (satellite)
# ---------------------------------------------------------------------------


@pytest.mark.observability
def test_profile_trace_double_start_is_noop(tmp_path):
    """A second start in one process must fall back to no-op and count a
    drop, never raise — the measured code always runs."""
    import jax.numpy as jnp

    before = metrics.counter("metrics.dropped")
    with profile_trace(str(tmp_path / "outer")):
        with profile_trace(str(tmp_path / "inner")):  # double start
            x = float(jnp.ones(4).sum())
    assert x == 4.0
    assert metrics.counter("metrics.dropped") > before


@pytest.mark.observability
def test_nested_timed_attributes_correctly_under_threads():
    rec = StepMetrics()

    def worker(tag):
        with timed(f"nt.outer.{tag}", recorder=rec):
            with timed(f"nt.inner.{tag}", recorder=rec):
                time.sleep(0.01)

    threads = [threading.Thread(target=worker, args=(t,)) for t in "ab"]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    for tag in "ab":
        outer = rec.timer_stats(f"nt.outer.{tag}")
        inner = rec.timer_stats(f"nt.inner.{tag}")
        assert outer["count"] == 1 and inner["count"] == 1
        assert outer["total_s"] >= inner["total_s"] >= 0.01


# ---------------------------------------------------------------------------
# Span tracer (tentpole)
# ---------------------------------------------------------------------------


def _affine_op(col, out, a, b):
    from alink_tpu.common.mtable import AlinkTypes
    from alink_tpu.mapper.base import BlockKernelMapper
    from alink_tpu.operator.batch.utils import MapBatchOp

    class _M(BlockKernelMapper):
        def kernel(self, schema):
            def fn(X):
                return X * a + b

            return ([col], [out], [AlinkTypes.DOUBLE], fn)

    class _Op(MapBatchOp):
        mapper_cls = _M

    return _Op()


def _build_and_run_dag(seed=0):
    """Source -> two independent branches + a 2-op fusable mapper chain;
    returns the three branch outputs as numpy arrays."""
    from alink_tpu.common.mtable import MTable
    from alink_tpu.operator.batch import TableSourceBatchOp

    rng = np.random.RandomState(seed)
    src = TableSourceBatchOp(
        MTable({"x": rng.rand(200), "y": rng.rand(200)}))
    a = src.apply_func(
        lambda m: MTable({"x": np.sort(np.asarray(m.col("x")))}),
        out_schema="x double")
    b = src.apply_func(
        lambda m: MTable({"y": np.asarray(m.col("y")) * 2.0}),
        out_schema="y double")
    chain = _affine_op("x", "x1", 2.0, 1.0).link_from(src)
    chain = _affine_op("x1", "x2", 0.5, -3.0).link_from(chain)
    got = {}
    a.lazy_collect(lambda m: got.setdefault("a", np.asarray(m.col("x"))))
    b.lazy_collect(lambda m: got.setdefault("b", np.asarray(m.col("y"))))
    out = chain.collect()
    got["c"] = np.asarray(out.col("x2"))
    return got


def _flush_stale_sinks():
    """Fire any lazy sinks left pending by earlier tests so they cannot
    leak extra spans into this test's trace."""
    from alink_tpu.common.mtable import MTable
    from alink_tpu.operator.batch import TableSourceBatchOp

    TableSourceBatchOp(MTable({"z": np.zeros(1)})).execute()


@pytest.mark.observability
def test_span_tree_matches_dag_with_parity(monkeypatch):
    """Acceptance: the span tree matches the executed DAG (one span per
    scheduled unit, parent links correct across pool threads, the fused
    chain as ONE span with a `fused` mark) and tracing on vs off is
    bit-identical."""
    from alink_tpu.common.tracing import job_report, tracer

    _flush_stale_sinks()
    monkeypatch.setenv("ALINK_TRACING", "on")
    on = _build_and_run_dag()
    tid = tracer.last_trace_id()
    rep = job_report(tid)
    assert rep["root"]["name"] == "dag.run"
    assert rep["root"]["outcome"] == "ok"
    roots = [s for s in rep["spans"] if s["parent_id"] is None]
    assert len(roots) == 1
    children = [s for s in rep["spans"] if s["parent_id"]]
    # one span per scheduled unit: source, two branches, ONE fused chain
    assert len(children) == 4, [s["name"] for s in rep["spans"]]
    assert all(c["parent_id"] == roots[0]["span_id"] for c in children)
    names = sorted(c["name"] for c in children)
    assert names == ["TableSourceBatchOp", "_FuncOp", "_FuncOp", "_Op+_Op"]
    fused = [c for c in children if c.get("attrs", {}).get("fused")]
    assert len(fused) == 1 and fused[0]["attrs"]["fused"] == 2
    # pool threads ran the units, not the caller thread
    assert any(c["thread"].startswith("alink-dag") for c in children)
    assert rep["outcomes"] == {"ok": 5}
    # the report's tree mirrors the flat span list
    tree = rep["tree"][0]
    assert sorted(k["name"] for k in tree["children"]) == names

    monkeypatch.setenv("ALINK_TRACING", "off")
    # off turns the collector's hook, the CPU seconds and the slow-unit
    # record off with the spans (PR 36): nothing is counted, nothing kept
    import gc

    from alink_tpu.common.metrics import metrics

    full0 = metrics.counter("host.gc_collections.gen2")
    ring0 = len(tracer.spans(collector=True))
    cpu0 = {n for n in metrics.histogram_names() if n.startswith("cpu.")}
    off = _build_and_run_dag()
    gc.collect()
    for k in ("a", "b", "c"):
        assert np.array_equal(on[k], off[k]), f"parity broke on {k}"
    assert metrics.counter("host.gc_collections.gen2") == full0
    assert len(tracer.spans(collector=True)) == ring0
    assert {n for n in metrics.histogram_names()
            if n.startswith("cpu.")} == cpu0


@pytest.mark.observability
def test_tracing_off_records_no_spans(monkeypatch):
    from alink_tpu.common.tracing import trace_span, tracer

    monkeypatch.setenv("ALINK_TRACING", "off")
    n0 = len(tracer.spans())
    with trace_span("should.not.exist") as sp:
        assert sp is None
    assert len(tracer.spans()) == n0


@pytest.mark.observability
def test_trace_span_failure_and_retry_outcomes(monkeypatch):
    from alink_tpu.common.tracing import note_retry, trace_span, tracer

    monkeypatch.setenv("ALINK_TRACING", "on")
    with pytest.raises(ValueError):
        with trace_span("obs.fails"):
            raise ValueError("boom")
    with trace_span("obs.retries"):
        note_retry()
    spans = {s["name"]: s for s in tracer.spans()}
    assert spans["obs.fails"]["outcome"] == "failed"
    assert "ValueError" in spans["obs.fails"]["error"]
    assert spans["obs.retries"]["outcome"] == "retried"
    assert spans["obs.retries"]["retries"] == 1


@pytest.mark.observability
def test_trace_jsonl_log(tmp_path, monkeypatch):
    from alink_tpu.common.tracing import trace_span, tracer

    log = tmp_path / "trace.jsonl"
    monkeypatch.setenv("ALINK_TRACING", "on")
    monkeypatch.setenv("ALINK_TRACE_LOG", str(log))
    try:
        with trace_span("obs.logged", tag=7) as sp:
            with trace_span("obs.logged.child"):
                pass
        recs = [json.loads(line) for line in
                log.read_text().strip().splitlines()]
    finally:
        tracer.clear()  # release the cached log handle
    assert len(recs) == 2
    by_name = {r["name"]: r for r in recs}
    child, parent = by_name["obs.logged.child"], by_name["obs.logged"]
    assert child["trace_id"] == parent["trace_id"] == sp.trace_id
    assert child["parent_id"] == parent["span_id"]
    assert parent["attrs"] == {"tag": 7}
    assert all("start_perf" not in r for r in recs)


@pytest.mark.observability
def test_trace_log_rotates_once_then_drops(tmp_path, monkeypatch):
    """ALINK_TRACE_LOG_MAX_MB bounds the JSONL event log: at the cap the
    log rotates ONCE to <path>.1 and restarts, and when the fresh file
    fills too, further events are dropped and counted — a long-lived
    process can never grow the log without bound."""
    from alink_tpu.common.tracing import trace_span, tracer

    log = tmp_path / "trace.jsonl"
    monkeypatch.setenv("ALINK_TRACING", "on")
    monkeypatch.setenv("ALINK_TRACE_LOG", str(log))
    monkeypatch.setenv("ALINK_TRACE_LOG_MAX_MB", "0.001")  # ~1 KiB cap
    rot0 = metrics.counter("trace.log_rotated")
    drop0 = metrics.counter("trace.log_dropped")
    try:
        for i in range(60):  # ~200B/span: fills the cap several times over
            with trace_span("obs.rotated", i=i, pad="x" * 120):
                pass
        rotated = metrics.counter("trace.log_rotated") - rot0
        dropped = metrics.counter("trace.log_dropped") - drop0
        assert rotated == 1                       # rotate-once, not a churn
        assert dropped > 0                        # overflow is counted
        assert (tmp_path / "trace.jsonl.1").exists()
        cap = 0.001 * 1024 * 1024
        assert log.stat().st_size <= cap + 400    # bounded (±1 record slack)
        assert (tmp_path / "trace.jsonl.1").stat().st_size <= cap + 400
        # every surviving line is intact JSON (rotation never tears a record)
        for p in (log, tmp_path / "trace.jsonl.1"):
            for line in p.read_text().strip().splitlines():
                json.loads(line)
    finally:
        tracer.clear()  # release the handle + reset rotation state


@pytest.mark.observability
def test_retried_unit_span_outcome(monkeypatch):
    """A DAG unit that succeeds after an injected transient fault reads
    `retried` in its span — propagated from with_retries on a pool
    thread."""
    from alink_tpu.common import faults
    from alink_tpu.common.mtable import MTable
    from alink_tpu.common.tracing import tracer
    from alink_tpu.operator.batch import TableSourceBatchOp

    _flush_stale_sinks()
    monkeypatch.setenv("ALINK_TRACING", "on")
    src = TableSourceBatchOp(MTable({"x": np.arange(8.0)}))
    a = src.apply_func(
        lambda m: MTable({"x": np.asarray(m.col("x")) + 1.0}),
        out_schema="x double")
    b = src.apply_func(
        lambda m: MTable({"x": np.asarray(m.col("x")) * 2.0}),
        out_schema="x double")
    b.lazy_collect(lambda m: None)
    faults.install(faults.FaultSpec.parse(
        "unit:count=1,kinds=transient,match=_FuncOp", seed=3))
    try:
        a.collect()
    finally:
        faults.clear()
    spans = tracer.spans(tracer.last_trace_id())
    retried = [s for s in spans if s["outcome"] == "retried"]
    assert retried and all(s["name"] == "_FuncOp" for s in retried)


@pytest.mark.observability
def test_stream_collect_chunk_histogram(monkeypatch):
    from alink_tpu.common.mtable import MTable
    from alink_tpu.operator.stream import TableSourceStreamOp

    monkeypatch.setenv("ALINK_TRACING", "on")
    before = (metrics.histogram("stream.chunk_s") or {}).get("count", 0)
    t = MTable({"v": np.arange(100.0)})
    out = TableSourceStreamOp(t, chunkSize=10).collect()
    assert out.num_rows == 100
    after = metrics.histogram("stream.chunk_s")["count"]
    assert after >= before + 10


@pytest.mark.observability
def test_transfer_retry_marks_owning_span(monkeypatch):
    """A transient transfer fault retried on an alink-h2d pool thread must
    mark the OWNING span (captured at handoff) `retried` — the cross-thread
    note_retry path."""
    from alink_tpu.common import faults
    from alink_tpu.common.streaming import stream_map
    from alink_tpu.common.tracing import trace_span, tracer

    monkeypatch.setenv("ALINK_TRACING", "on")
    batches = [(i, [np.full((4, 2), float(i))]) for i in range(3)]
    faults.install(faults.FaultSpec.parse(
        "transfer:count=1,kinds=transient", seed=1))
    try:
        with trace_span("obs.stream_job") as sp:
            outs = [float(r) for _, r in
                    stream_map(lambda x: x.sum(), batches)]
    finally:
        faults.clear()
    assert outs == [0.0, 8.0, 16.0]
    rec = {s["name"]: s for s in tracer.spans(sp.trace_id)}
    assert rec["obs.stream_job"]["outcome"] == "retried"
    assert rec["obs.stream_job"]["retries"] >= 1
