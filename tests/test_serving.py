"""Online serving tier: concurrent router, dynamic micro-batching, admission
control, breaker degradation, deadlines, HTTP surface, and the LocalPredictor
cached-plan parity contract.

The load-bearing guarantees pinned here:

- batched/concurrent results are BIT-IDENTICAL to serial LocalPredictor
  predicts (micro-batching only changes the leading kernel dimension, which
  the bucketing contract already pins as parity-safe);
- after load-time warmup, sustained mixed-batch-size load performs ZERO new
  traces (``jit.trace`` counter delta is 0 — the PR 4 contract carried to
  the serving tier);
- past-capacity load sheds gracefully: rejections are counted, accepted
  requests all complete (no deadlock), and their results stay bit-identical.

Pipelines here use StandardScaler + VectorAssembler + NaiveBayes — fit paths
that avoid the container's removed ``jax.shard_map`` (ROADMAP Open item 3);
the serving tier itself is model-agnostic.
"""

import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from alink_tpu.common import MTable
from alink_tpu.common.metrics import metrics
from alink_tpu.common.exceptions import (
    AkCircuitOpenException,
    AkDeadlineExceededException,
    AkIllegalStateException,
    AkServingOverloadException,
)
from alink_tpu.pipeline import (
    LocalPredictor,
    NaiveBayes,
    Pipeline,
    StandardScaler,
    VectorAssembler,
)
from alink_tpu.serving import (
    ModelServer,
    ServingConfig,
    serving_bucket_ladder,
)
from alink_tpu.serving.router import _Request, PredictFuture

pytestmark = pytest.mark.serving

SCHEMA = "f0 double, f1 double, f2 double, f3 double"
FEATS = ["f0", "f1", "f2", "f3"]


def _make_data(n_per=60, seed=0):
    rng = np.random.default_rng(seed)
    X = np.concatenate([rng.normal(c, 0.4, size=(n_per, 4))
                        for c in [(0, 0, 0, 0), (2, 2, 2, 2)]])
    y = np.repeat(["neg", "pos"], n_per)
    t = MTable({f"f{i}": X[:, i] for i in range(4)}).with_column("label", y)
    return X, t


@pytest.fixture(scope="module")
def fitted():
    X, t = _make_data()
    model = Pipeline(
        StandardScaler(selectedCols=FEATS),
        VectorAssembler(selectedCols=FEATS, outputCol="vec"),
        NaiveBayes(vectorCol="vec", labelCol="label", predictionCol="pred"),
    ).fit(t)
    return X, t, model


@pytest.fixture(scope="module")
def serial_rows(fitted):
    """Ground truth: serial, uncached-plan, single-row predicts."""
    X, _, model = fitted
    lp = LocalPredictor(model, SCHEMA, cache_plan=False)
    return [lp.predict_row(tuple(r)) for r in X]


# ---------------------------------------------------------------------------
# LocalPredictor cached transform plan
# ---------------------------------------------------------------------------


def test_cached_plan_parity_with_uncached(fitted):
    """The construction-time transform plan returns bit-identical tables to
    rebuilding the DAG per call, across repeated mixed-size predicts."""
    X, t, model = fitted
    cached = LocalPredictor(model, SCHEMA)          # default: plan cached
    plain = LocalPredictor(model, SCHEMA, cache_plan=False)
    feat = t.select(FEATS)
    for n in (1, 3, 7, 20, 120, 5):                 # revisit sizes too
        assert cached.predict_table(feat.head(n)) == \
            plain.predict_table(feat.head(n))
    assert cached.predict_row(tuple(X[4])) == plain.predict_row(tuple(X[4]))
    assert cached.get_output_schema() == plain.get_output_schema()


def test_cached_plan_skips_replanning(fitted):
    """Repeated predicts reuse one plan: the op-node sub-DAG is built once
    (same object identity across calls)."""
    X, t, model = fitted
    cached = LocalPredictor(model, SCHEMA)
    cached.predict_table(t.select(FEATS).head(4))
    plan1 = cached._plan
    cached.predict_table(t.select(FEATS).head(9))
    assert cached._plan is plan1 and plan1 is not None


# ---------------------------------------------------------------------------
# Router: parity, batching, zero recompiles
# ---------------------------------------------------------------------------


def test_concurrent_results_bit_identical_to_serial(fitted, serial_rows):
    X, _, model = fitted
    srv = ModelServer(ServingConfig(max_batch_rows=16,
                                    flush_deadline_s=0.002))
    try:
        srv.load("parity", model, SCHEMA, warmup_rows=[tuple(X[0])])
        results = {}

        def client(cid):
            rows = [tuple(r) for r in X[cid::4]]
            results[cid] = srv.predict_many("parity", rows, timeout=60)

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(4)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        for cid in range(4):
            assert results[cid] == serial_rows[cid::4], \
                f"client {cid} diverged from serial predicts"
        st = srv.stats()["models"][0]
        assert st["completed"] == len(X)
        # coalescing actually happened (fewer batches than requests)
        assert st["batches"] < st["completed"]
    finally:
        srv.close()


def test_zero_recompiles_under_sustained_mixed_load(fitted, serial_rows):
    """After load-time warmup of every ladder rung <= max_batch_rows,
    sustained concurrent mixed-batch-size load performs ZERO new traces."""
    X, _, model = fitted
    srv = ModelServer(ServingConfig(max_batch_rows=16,
                                    flush_deadline_s=0.001))
    try:
        srv.load("steady", model, SCHEMA, warmup_rows=[tuple(X[0])])
        traces0 = metrics.counter("jit.trace")
        compiles0 = metrics.counter("jit.compile")
        results = {}

        def client(cid):
            out = []
            for rep in range(3):  # several rounds => many distinct sizes
                rows = [tuple(r) for r in X[cid::5]]
                out.append(srv.predict_many("steady", rows, timeout=60))
            results[cid] = out

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(5)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        assert metrics.counter("jit.trace") == traces0
        assert metrics.counter("jit.compile") == compiles0
        for cid in range(5):
            for rep_out in results[cid]:
                assert rep_out == serial_rows[cid::5]
    finally:
        srv.close()


def test_default_warmup_synthesized_from_schema(fitted):
    """Omitting warmup_rows must not void the zero-traces contract: a zero
    sample row is synthesized from the (primitive-typed) input schema and
    every rung still warms at load."""
    X, _, model = fitted
    srv = ModelServer(ServingConfig(max_batch_rows=16,
                                    flush_deadline_s=0.001))
    try:
        info = srv.load("dwarm", model, SCHEMA)  # no warmup_rows
        assert info["warmup"]["rungs"] >= 2
        traces0 = metrics.counter("jit.trace")
        srv.predict_many("dwarm", [tuple(r) for r in X[:30]], timeout=60)
        assert metrics.counter("jit.trace") == traces0
    finally:
        srv.close()


def test_hot_swap_under_traffic_drops_nothing(fitted, serial_rows):
    """Requests racing a hot-swap re-route to the replacement entry instead
    of failing with 'model unloaded'."""
    X, _, model = fitted
    srv = ModelServer(ServingConfig(max_batch_rows=8,
                                    flush_deadline_s=0.001))
    try:
        srv.load("swaprace", model, SCHEMA, warmup_rows=[tuple(X[0])])
        stop = threading.Event()
        errors: list = []

        def hammer():
            i = 0
            while not stop.is_set():
                try:
                    got = srv.predict("swaprace", tuple(X[i % len(X)]),
                                      timeout=60)
                    assert got == serial_rows[i % len(X)]
                except Exception as e:  # noqa: BLE001 — collected for assert
                    errors.append(e)
                i += 1

        th = threading.Thread(target=hammer)
        th.start()
        for _ in range(5):
            srv.load("swaprace", model, SCHEMA, warmup_rows=[tuple(X[0])])
        stop.set()
        th.join(timeout=60)
        assert not errors, errors[:3]
    finally:
        srv.close()


def test_bucket_ladder_covers_every_batch_size():
    ladder = serving_bucket_ladder(64)
    from alink_tpu.common.jitcache import bucket_rows

    for n in range(1, 65):
        assert bucket_rows(n) in ladder


# ---------------------------------------------------------------------------
# Admission control: saturation, shedding, no deadlock
# ---------------------------------------------------------------------------


def test_saturation_sheds_gracefully(fitted, serial_rows):
    X, _, model = fitted
    srv = ModelServer(ServingConfig(queue_depth=8, max_batch_rows=8,
                                    flush_deadline_s=0.05))
    try:
        srv.load("sat", model, SCHEMA, warmup_rows=[tuple(X[0])])
        shed0 = metrics.counter("serving.shed")
        futs, shed = [], 0
        for i in range(300):
            try:
                futs.append((i % len(X),
                             srv.submit("sat", tuple(X[i % len(X)]))))
            except AkServingOverloadException:
                shed += 1
        assert shed > 0, "flood never hit the high-water mark"
        assert metrics.counter("serving.shed") >= shed0 + shed
        # no deadlock: every accepted request completes within the budget,
        # and bit-identical to the serial predicts
        for idx, fut in futs:
            assert fut.result(timeout=60) == serial_rows[idx]
        st = srv.stats()["models"][0]
        assert st["shed"] == shed
        assert st["completed"] == len(futs)
        assert st["queued"] == 0
    finally:
        srv.close()


def test_shed_policy_oldest_drops_queued_request(fitted):
    X, _, model = fitted
    # queue_depth < max_batch_rows and a long flush deadline: the batcher
    # waits for a fuller batch, so the queue stays full while we overflow it
    srv = ModelServer(ServingConfig(queue_depth=4, max_batch_rows=8,
                                    flush_deadline_s=10.0,
                                    shed_policy="oldest"))
    try:
        srv.load("oldest", model, SCHEMA)
        first = srv.submit("oldest", tuple(X[0]))
        rest = [srv.submit("oldest", tuple(X[i])) for i in range(1, 8)]
        # the overflow admissions dropped the oldest queued requests
        assert first.done()
        with pytest.raises(AkServingOverloadException):
            first.result(0)
        assert srv.stats()["models"][0]["shed"] > 0
        del rest
    finally:
        srv.close()


def test_deadline_expired_in_queue(fitted):
    X, _, model = fitted
    srv = ModelServer(ServingConfig(max_batch_rows=4,
                                    flush_deadline_s=0.2))
    try:
        srv.load("ddl", model, SCHEMA, warmup_rows=[tuple(X[0])])
        fut = srv.submit("ddl", tuple(X[0]), deadline_s=0.0)  # born expired
        with pytest.raises(AkDeadlineExceededException):
            fut.result(timeout=30)
        assert srv.stats()["models"][0]["deadline_expired"] == 1
    finally:
        srv.close()


def test_priority_lane_pops_first(fitted):
    """The batcher drains the priority lane before the normal lane."""
    X, _, model = fitted
    srv = ModelServer(ServingConfig(max_batch_rows=4,
                                    flush_deadline_s=10.0))
    try:
        srv.load("prio", model, SCHEMA)
        entry = srv._entry("prio")
        # inspect lane mechanics under the entry lock (the batcher cannot
        # pop while we hold it); lanes interleaved at submit time
        with entry._cond:
            reqs = [_Request(tuple(X[i]), PredictFuture(None, i % 2 == 0))
                    for i in range(6)]
            for r in reqs:
                (entry._high if r.future.priority else
                 entry._normal).append(r)
            batch = entry._pop_batch_locked()
            assert [r.future.priority for r in batch] == \
                [True] * 3 + [False] * 3
    finally:
        srv.close()


# ---------------------------------------------------------------------------
# Breaker-gated degradation + lifecycle
# ---------------------------------------------------------------------------


class _BoomPredictor(LocalPredictor):
    """A predictor whose execution always fails — the unhealthy-model
    double for breaker tests."""

    def predict_table(self, t):
        raise RuntimeError("boom")


def test_breaker_degrades_failing_model_to_fast_rejects(fitted):
    X, _, model = fitted
    srv = ModelServer(ServingConfig(max_batch_rows=4, flush_deadline_s=0.001,
                                    breaker_threshold=2,
                                    breaker_reset_s=3600.0))
    try:
        srv.load("brk", _BoomPredictor(model, SCHEMA))
        # consecutive batch EXECUTION failures open the model's circuit
        for _ in range(2):
            with pytest.raises(RuntimeError):
                srv.predict("brk", tuple(X[0]), timeout=30)
        assert srv.stats()["models"][0]["breaker_open"]
        t0 = time.perf_counter()
        with pytest.raises(AkCircuitOpenException):
            srv.predict("brk", tuple(X[0]), timeout=30)
        assert time.perf_counter() - t0 < 5.0  # fast reject, not a hang
        assert srv.stats()["models"][0]["breaker_rejected"] >= 1
    finally:
        srv.close()


def test_bad_rows_rejected_per_request_without_tripping_breaker(fitted,
                                                                serial_rows):
    """Rows that cannot build against the input schema are CALLER errors:
    rejected individually, co-batched valid requests still answer, and the
    circuit never opens — one bad client cannot 503 a healthy model."""
    X, _, model = fitted
    srv = ModelServer(ServingConfig(max_batch_rows=8, flush_deadline_s=0.05,
                                    breaker_threshold=2,
                                    breaker_reset_s=3600.0))
    try:
        srv.load("badrows", model, SCHEMA, warmup_rows=[tuple(X[0])])
        for _ in range(3):  # well past the breaker threshold
            bad = srv.submit("badrows", ("boom", "x", "y", "z"))
            good = srv.submit("badrows", tuple(X[5]))
            with pytest.raises(Exception) as ei:
                bad.result(timeout=30)
            assert not isinstance(ei.value, AkCircuitOpenException)
            assert good.result(timeout=30) == serial_rows[5]
        st = srv.stats()["models"][0]
        assert not st["breaker_open"]
        assert st["bad_rows"] == 3
        assert st["completed"] >= 3
    finally:
        srv.close()


def test_hot_swap_gets_a_fresh_breaker(fitted, serial_rows):
    """A hot-swapped model must not inherit the retired entry's failure
    history: the new entry serves immediately even though the old one's
    circuit was open (and may keep failing while it drains)."""
    X, _, model = fitted
    srv = ModelServer(ServingConfig(max_batch_rows=4, flush_deadline_s=0.001,
                                    breaker_threshold=2,
                                    breaker_reset_s=3600.0))
    try:
        srv.load("swapbrk", _BoomPredictor(model, SCHEMA))
        for _ in range(2):
            with pytest.raises(RuntimeError):
                srv.predict("swapbrk", tuple(X[0]), timeout=30)
        assert srv.stats()["models"][0]["breaker_open"]
        srv.load("swapbrk", model, SCHEMA, warmup_rows=[tuple(X[0])])
        assert srv.predict("swapbrk", tuple(X[2]), timeout=30) == \
            serial_rows[2]
        assert not srv.stats()["models"][0]["breaker_open"]
    finally:
        srv.close()


def test_hot_swap_and_unload(fitted, serial_rows):
    X, t, model = fitted
    srv = ModelServer(ServingConfig(max_batch_rows=8,
                                    flush_deadline_s=0.002))
    try:
        srv.load("swap", model, SCHEMA, warmup_rows=[tuple(X[0])])
        assert srv.predict("swap", tuple(X[1]), timeout=30) == serial_rows[1]
        # hot-swap with a refit model: serving continues, new entry answers
        model2 = Pipeline(
            StandardScaler(selectedCols=FEATS),
            VectorAssembler(selectedCols=FEATS, outputCol="vec"),
            NaiveBayes(vectorCol="vec", labelCol="label",
                       predictionCol="pred"),
        ).fit(t)
        srv.load("swap", model2, SCHEMA, warmup_rows=[tuple(X[0])])
        assert srv.predict("swap", tuple(X[1]), timeout=30) == serial_rows[1]
        assert srv.unload("swap")
        assert not srv.unload("swap")
        with pytest.raises(Exception):
            srv.predict("swap", tuple(X[1]), timeout=5)
    finally:
        srv.close()


def test_hot_swap_answers_with_the_new_weights_after_one_more_load(fitted):
    """The loaded mapper lives in the entry's plan: traffic loads nothing
    after the warm-up, and a swap under the same name brings a new plan that
    loads the new model once and answers from it."""
    from alink_tpu.pipeline import LogisticRegression

    X, t, _ = fitted
    flipped = t.with_column(
        "label", np.where(t.col("label") == "pos", "neg", "pos"))
    models = [Pipeline(LogisticRegression(
        featureCols=FEATS, labelCol="label", predictionCol="pred",
        maxIter=20)).fit(d) for d in (t, flipped)]
    rows = [tuple(r) for r in X[::7]]
    want = [[LocalPredictor(m, SCHEMA, cache_plan=False).predict_row(r)
             for r in rows] for m in models]
    assert all(a[-1] != b[-1] for a, b in zip(*want))

    def loads():
        return metrics.counter("mapper.model_loads")

    srv = ModelServer(ServingConfig(max_batch_rows=8,
                                    flush_deadline_s=0.002))
    try:
        for model, answers in zip(models, want):
            at = loads()
            srv.load("reswap", model, SCHEMA, warmup_rows=[rows[0]])
            assert loads() - at == 1
            for _ in range(2):
                assert srv.predict_many("reswap", rows, timeout=60) == answers
            assert loads() - at == 1
    finally:
        srv.close()


def test_unload_fails_fast_without_drain(fitted):
    X, _, model = fitted
    srv = ModelServer(ServingConfig(max_batch_rows=4,
                                    flush_deadline_s=10.0))
    try:
        srv.load("nodrain", model, SCHEMA)
        futs = [srv.submit("nodrain", tuple(X[i])) for i in range(3)]
        srv.unload("nodrain", drain=False)
        for f in futs:
            with pytest.raises(AkIllegalStateException):
                f.result(timeout=30)
    finally:
        srv.close()


# ---------------------------------------------------------------------------
# Observability
# ---------------------------------------------------------------------------


def test_serving_spans_and_histograms(fitted):
    X, _, model = fitted
    from alink_tpu.common.tracing import tracer, tracing_enabled

    srv = ModelServer(ServingConfig(max_batch_rows=8,
                                    flush_deadline_s=0.002))
    try:
        srv.load("obs", model, SCHEMA, warmup_rows=[tuple(X[0])])
        srv.predict_many("obs", [tuple(r) for r in X[:10]], timeout=60)
        st = srv.stats()
        for h in ("serving.request_s", "serving.queue_s",
                  "serving.batch_rows"):
            assert st["histograms"][h]["count"] >= 10 or h == "serving.batch_rows"
            assert st["histograms"][h]["p99"] is not None
        if tracing_enabled():
            names = {s["name"] for s in tracer.spans()}
            assert "serving.batch" in names
            assert "serving.warmup" in names
        # Prometheus exposition carries the serving families
        from alink_tpu.common.metrics import export_prometheus

        text = export_prometheus()
        assert "alink_serving_request_seconds" in text
        assert "alink_serving_accepted_total" in text
    finally:
        srv.close()


# ---------------------------------------------------------------------------
# HTTP surface
# ---------------------------------------------------------------------------


def _req(port, path, method="GET", body=None):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}", method=method,
        data=None if body is None else json.dumps(body).encode())
    with urllib.request.urlopen(req, timeout=60) as r:
        return json.loads(r.read())


def test_http_serving_roundtrip(fitted, serial_rows, tmp_path):
    from alink_tpu.webui import ExperimentStore, WebUIServer

    X, _, model = fitted
    ak = str(tmp_path / "nb.ak")
    model.save(ak)
    srv = ModelServer(ServingConfig(max_batch_rows=8,
                                    flush_deadline_s=0.002))
    web = WebUIServer(port=0, store=ExperimentStore(
        str(tmp_path / "exp.json")), model_server=srv)
    web.start(background=True)
    try:
        out = _req(web.port, "/api/serving/models", "POST",
                   {"name": "nb", "path": ak, "inputSchema": SCHEMA,
                    "warmupRows": [list(map(float, X[0]))]})
        assert out["model"] == "nb" and out["warmup"]["rungs"] >= 1

        got = _req(web.port, "/api/serving/predict/nb", "POST",
                   {"row": list(map(float, X[3]))})
        exp = serial_rows[3]
        assert got["row"][:4] == pytest.approx([float(v) for v in exp[:4]])
        assert got["row"][-1] == exp[-1]

        many = _req(web.port, "/api/serving/predict/nb", "POST",
                    {"rows": [list(map(float, X[i])) for i in range(6)]})
        assert [r[-1] for r in many["rows"]] == \
            [serial_rows[i][-1] for i in range(6)]

        st = _req(web.port, "/api/serving")
        assert st["models"][0]["model"] == "nb"
        assert st["models"][0]["completed"] >= 7

        # unknown model → 400
        with pytest.raises(urllib.error.HTTPError) as ei:
            _req(web.port, "/api/serving/predict/ghost", "POST",
                 {"row": [1, 2, 3, 4]})
        assert ei.value.code == 400

        assert _req(web.port, "/api/serving/models/nb", "DELETE") == \
            {"unloaded": "nb"}
        with pytest.raises(urllib.error.HTTPError) as ei:
            _req(web.port, "/api/serving/models/nb", "DELETE")
        assert ei.value.code == 404
    finally:
        web.stop()
        srv.close()


def test_http_shed_maps_to_429(fitted, tmp_path):
    from alink_tpu.webui import ExperimentStore, WebUIServer

    X, _, model = fitted
    srv = ModelServer(ServingConfig(queue_depth=1, max_batch_rows=1,
                                    flush_deadline_s=5.0))
    srv.load("tiny", model, SCHEMA)
    # fill the queue out-of-band so the HTTP submit sheds
    srv.submit("tiny", tuple(X[0]))
    web = WebUIServer(port=0, store=ExperimentStore(
        str(tmp_path / "exp.json")), model_server=srv)
    web.start(background=True)
    try:
        with pytest.raises(urllib.error.HTTPError) as ei:
            _req(web.port, "/api/serving/predict/tiny", "POST",
                 {"row": list(map(float, X[1]))})
        assert ei.value.code == 429
    finally:
        web.stop()
        srv.close()


# ---------------------------------------------------------------------------
# persisted warmup specs (zero cold start: PR 11)
# ---------------------------------------------------------------------------


def test_warmup_sidecar_roundtrip_bit_identical(fitted, serial_rows,
                                                tmp_path):
    """The save side emits ``<model>.ak.warmup.json`` after a live warmup;
    a later load needs NOTHING but the path — schema and sample rows come
    from the sidecar — and serves bit-identical predictions with zero new
    traces under traffic (the replica-rollout contract)."""
    from alink_tpu.serving import load_warmup_spec, warmup_sidecar_path

    X, _, model = fitted
    ak = str(tmp_path / "m.ak")
    model.save(ak)
    srv = ModelServer(ServingConfig(max_batch_rows=16))
    try:
        info1 = srv.load("live", ak, SCHEMA, warmup_rows=[tuple(X[0])])
        assert info1["warmup_source"] == "caller"
        assert info1["warmup_sidecar"] == warmup_sidecar_path(ak)
        spec = load_warmup_spec(ak)
        assert spec["input_schema"].lower() == SCHEMA  # to_str upper-cases
        assert spec["warmup_rows"] == [tuple(map(float, X[0]))]
        assert spec["max_batch_rows"] == 16
        assert spec["ladder"] == serving_bucket_ladder(16)

        # the fresh-replica side: no schema, no rows — disk artifacts only
        info2 = srv.load("replica", ak)
        assert info2["warmup_source"] == "sidecar"
        # a sidecar-sourced load never rewrites the sidecar: replica loads
        # stay read-only against the model store
        assert info2["warmup_sidecar"] is None
        t0 = metrics.counter("jit.trace")
        got = [srv.predict("replica", tuple(r)) for r in X[:24]]
        assert metrics.counter("jit.trace") == t0, \
            "traffic after a sidecar-warmed load must not trace"
        assert got == serial_rows[:24]
    finally:
        srv.close()


def test_warmup_sidecar_corrupt_falls_back_to_live(fitted, serial_rows,
                                                   tmp_path):
    """A truncated sidecar must read as absent: the load falls back to the
    live (here: schema-synthesized) warmup path, counts the corruption, and
    still serves bit-identical results."""
    from alink_tpu.serving import warmup_sidecar_path

    X, _, model = fitted
    ak = str(tmp_path / "m.ak")
    model.save(ak)
    with open(warmup_sidecar_path(ak), "w") as f:
        f.write('{"version": 1, "warmup_rows": [[')   # truncated JSON
    e0 = metrics.counter("serving.warmup_spec_errors")
    srv = ModelServer(ServingConfig(max_batch_rows=16))
    try:
        info = srv.load("m", ak, SCHEMA)
        assert metrics.counter("serving.warmup_spec_errors") > e0
        assert info["warmup_source"] == "synthesized"
        got = [srv.predict("m", tuple(r)) for r in X[:8]]
        assert got == serial_rows[:8]
    finally:
        srv.close()


def test_warmup_sidecar_knob_off_writes_nothing(fitted, tmp_path,
                                                monkeypatch):
    import os

    from alink_tpu.serving import warmup_sidecar_path

    X, _, model = fitted
    ak = str(tmp_path / "m.ak")
    model.save(ak)
    monkeypatch.setenv("ALINK_SERVING_PERSIST_WARMUP", "0")
    srv = ModelServer(ServingConfig(max_batch_rows=16))
    try:
        info = srv.load("m", ak, SCHEMA, warmup_rows=[tuple(X[0])])
        assert info["warmup_sidecar"] is None
        assert not os.path.exists(warmup_sidecar_path(ak))
    finally:
        srv.close()


def test_load_path_needs_schema_or_sidecar(tmp_path, fitted):
    from alink_tpu.common.exceptions import AkIllegalArgumentException

    _, _, model = fitted
    ak = str(tmp_path / "m.ak")
    model.save(ak)
    srv = ModelServer()
    try:
        with pytest.raises(AkIllegalArgumentException):
            srv.load("m", ak)   # no schema anywhere
    finally:
        srv.close()


def test_warmup_sidecar_stale_after_model_retrain(fitted, tmp_path):
    """Retraining a model at the same path must invalidate the old sidecar
    (its schema/rows describe a DIFFERENT model): the load falls back to
    live warmup and counts the staleness — while a byte-preserving
    copy/re-save (the normal rollout) keeps the sidecar valid (the
    fingerprint is content, not mtime, so cp/gsutil-style distribution
    cannot void zero cold start)."""
    import os
    import shutil

    from alink_tpu.serving import load_warmup_spec, warmup_sidecar_path

    X, _, model = fitted
    ak = str(tmp_path / "m.ak")
    model.save(ak)
    srv = ModelServer(ServingConfig(max_batch_rows=16))
    try:
        srv.load("v1", ak, SCHEMA, warmup_rows=[tuple(X[0])])
        assert load_warmup_spec(ak) is not None
        # a copy with rewritten mtimes (every rollout tool) stays VALID
        ak2 = str(tmp_path / "copy.ak")
        shutil.copyfile(ak, ak2)
        shutil.copyfile(warmup_sidecar_path(ak), warmup_sidecar_path(ak2))
        st = os.stat(ak2)
        os.utime(ak2, (st.st_atime, st.st_mtime + 999))
        assert load_warmup_spec(ak2) is not None
        # "retrain": same path, different CONTENT
        _, t2 = _make_data(seed=9)
        Pipeline(
            StandardScaler(selectedCols=FEATS),
            VectorAssembler(selectedCols=FEATS, outputCol="vec"),
            NaiveBayes(vectorCol="vec", labelCol="label",
                       predictionCol="pred"),
        ).fit(t2).save(ak)
        s0 = metrics.counter("serving.warmup_spec_stale")
        assert load_warmup_spec(ak) is None
        assert metrics.counter("serving.warmup_spec_stale") > s0
        info = srv.load("v2", ak, SCHEMA)
        assert info["warmup_source"] == "synthesized"   # not the stale rows
    finally:
        srv.close()
