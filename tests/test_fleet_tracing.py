"""Fleet-wide observability plane: cross-process trace propagation,
replica telemetry aggregation, and the stitched frontdoor-to-kernel
waterfall (alink_tpu/common/tracing + common/telemetry + serving/fleet).

The load-bearing guarantees pinned here:

- a frontdoor predict through a 2-replica fleet yields ONE
  ``job_report(trace_id)`` span tree containing the frontend request
  span AND the replica-side batcher spans, process-tagged, with
  ``chrome_trace()`` laying them out in real per-process lanes (>= 2
  distinct pids);
- ``ALINK_TRACING=off`` through the full fleet path serves bit-identical
  results to the single-process ground truth and records zero spans —
  the wire field degrades to ``None``, never changes the frame shape;
- orphan-span fallback: a missing/None/garbage wire context is tolerated
  on both sides (old client, old replica) — spans become local roots and
  garbage counts ``trace.bad_wire_context``;
- failed-over and deadline-expired requests carry their ``outcome``
  (``retried`` / ``failed``) on the stitched tree;
- fleet-wide histogram quantiles at the supervisor are the EXACT merge
  of per-replica bucket counts (never averaged averages), exposed as
  ``replica``-labeled Prometheus families;
- telemetry payloads are bounded and garbage-tolerant: malformed or
  oversized payloads are dropped whole and counted, never half-merged.
"""

import json
import os
import socket
import threading
import time

import numpy as np
import pytest

from alink_tpu.common import MTable
from alink_tpu.common.metrics import StepMetrics, _Histogram, metrics
from alink_tpu.common.resilience import CircuitBreaker
from alink_tpu.common.telemetry import (
    MAX_PAYLOAD_BYTES,
    TelemetrySink,
    TelemetrySource,
    validate_telemetry,
)
from alink_tpu.common.tracing import (
    Tracer,
    adopt_context,
    chrome_trace,
    job_report,
    trace_span,
    tracer,
    wire_context,
)
from alink_tpu.pipeline import (
    NaiveBayes,
    Pipeline,
    StandardScaler,
    VectorAssembler,
)
from alink_tpu.serving import (
    FleetConfig,
    FleetFrontend,
    ModelServer,
    ReplicaClient,
    ServingFleet,
)
from alink_tpu.serving.fleet_frontend import recv_frame, send_frame

pytestmark = pytest.mark.observability

SCHEMA = "f0 double, f1 double, f2 double, f3 double"
FEATS = ["f0", "f1", "f2", "f3"]


def _wait(pred, timeout=30.0, interval=0.1):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(interval)
    return False


def _walk(nodes, out=None, depth=0):
    """Flatten a job_report tree into (depth, span) rows."""
    out = [] if out is None else out
    for n in nodes or []:
        out.append((depth, n))
        _walk(n.get("children"), out, depth + 1)
    return out


# ---------------------------------------------------------------------------
# Unit: wire context contract (emit side + adopt side + orphan fallback)
# ---------------------------------------------------------------------------


def test_wire_context_roundtrip_parents_remote_span(monkeypatch):
    monkeypatch.setenv("ALINK_TRACING", "on")
    assert wire_context() is None  # no span open -> old-client shape
    with trace_span("wire.origin") as sp:
        ctx = wire_context()
        assert ctx == {"trace_id": sp.trace_id, "span_id": sp.span_id}
    # "receiving process": adopt the token, open a span under it
    with adopt_context(ctx):
        with trace_span("wire.remote"):
            pass
    spans = {s["name"]: s for s in tracer.spans()}
    remote = spans["wire.remote"]
    assert remote["trace_id"] == ctx["trace_id"]
    assert remote["parent_id"] == ctx["span_id"]
    # one stitched tree: origin is the root, remote is its child
    rep = job_report(ctx["trace_id"])
    rows = _walk(rep["tree"])
    assert [(d, n["name"]) for d, n in rows] == [
        (0, "wire.origin"), (1, "wire.remote")]


def test_adopt_context_orphan_fallback(monkeypatch):
    """None (old client / tracing off at origin) and garbage tokens are
    tolerated: the block's spans become local ROOTS, garbage counts
    trace.bad_wire_context, and nothing ever raises."""
    monkeypatch.setenv("ALINK_TRACING", "on")
    with adopt_context(None):
        with trace_span("orphan.none"):
            pass
    bad_before = metrics.counters().get("trace.bad_wire_context", 0)
    for garbage in ({"trace_id": 7, "span_id": "x"},
                    {"trace_id": "t" * 129, "span_id": "x"},
                    {"span_id": "x"}, "not-a-dict", 42):
        with adopt_context(garbage):
            with trace_span("orphan.garbage"):
                pass
    assert metrics.counters()["trace.bad_wire_context"] == bad_before + 5
    spans = [s for s in tracer.spans()
             if s["name"].startswith("orphan.")]
    assert len(spans) == 6
    assert all(s["parent_id"] is None for s in spans)


def test_wire_context_none_when_tracing_off(monkeypatch):
    monkeypatch.setenv("ALINK_TRACING", "off")
    with trace_span("off.span"):
        assert wire_context() is None


# ---------------------------------------------------------------------------
# Unit: span export/ingest relay (the heartbeat span batch)
# ---------------------------------------------------------------------------


def test_export_drain_and_ingest_stamps_process(monkeypatch):
    monkeypatch.setenv("ALINK_TRACING", "on")
    t = Tracer()
    assert t.drain_export() == []  # never armed -> empty, not an error
    t.enable_export()
    sp = t.start("relay.unit")
    t.finish(sp)
    batch = t.drain_export()
    assert len(batch) == 1
    assert "start_perf" not in batch[0]  # process-local; dead on the wire
    assert t.drain_export() == []  # drained means drained

    sink = Tracer()
    n = sink.ingest(batch, proc="r9", pid=4242)
    assert n == 1
    got = sink.spans()[0]
    assert (got["proc"], got["pid"]) == ("r9", 4242)
    assert got["name"] == "relay.unit"


def test_ingest_rejects_garbage_all_or_nothing():
    sink = Tracer()
    good = {"trace_id": "t1", "span_id": "s1", "name": "ok.span",
            "t_start": 1.0, "wall_s": 0.5, "parent_id": None}
    for batch in (
            "not-a-list",
            [good, "not-a-dict"],
            [good, {"trace_id": "t1", "name": "missing-span-id"}],
            [good, dict(good, span_id="s2", t_start="garbage")],
            [good, dict(good, span_id="s2", parent_id=123)],
    ):
        with pytest.raises(ValueError):
            sink.ingest(batch)
        # ALL-before-ANY: the good entry must not have slipped in
        assert sink.spans() == []
    assert sink.ingest([good]) == 1


def test_span_tree_stitches_remote_children_arriving_late(monkeypatch):
    """Ring order is arrival order: a relayed child lands AFTER its
    parent finished (heartbeat latency). The tree must still nest and
    sort it — this was a real KeyError before the two-pass fix — and the
    stitched tree falls back to the shared wall-clock base when any span
    lacks start_perf."""
    monkeypatch.setenv("ALINK_TRACING", "on")
    with trace_span("late.parent") as sp:
        tid, sid = sp.trace_id, sp.span_id
    tracer.ingest([{"trace_id": tid, "span_id": "rem-1", "parent_id": sid,
                    "name": "late.child", "t_start": time.time(),
                    "wall_s": 0.01}], proc="r1", pid=777)
    rep = job_report(tid)
    rows = _walk(rep["tree"])
    assert [(d, n["name"]) for d, n in rows] == [
        (0, "late.parent"), (1, "late.child")]
    child = rows[1][1]
    assert (child["proc"], child["pid"]) == ("r1", 777)
    assert "rel_start_s" in child and "start_perf" not in child


# ---------------------------------------------------------------------------
# Unit: exact histogram merge + labeled exposition
# ---------------------------------------------------------------------------


def test_histogram_state_roundtrip_and_exact_merge():
    a, b, pooled = _Histogram(), _Histogram(), _Histogram()
    va = [0.001, 0.003, 0.02, 0.4]
    vb = [0.002, 0.09, 1.5]
    for v in va:
        a.observe(v)
        pooled.observe(v)
    for v in vb:
        b.observe(v)
        pooled.observe(v)
    restored = _Histogram.from_state(json.loads(json.dumps(a.state())))
    restored.merge(_Histogram.from_state(b.state()))
    # the merge IS the pooled distribution — same buckets, count, sum,
    # min/max, hence identical quantiles (exact, not averaged averages)
    assert restored.state() == pooled.state()
    assert restored.stats() == pooled.stats()

    with pytest.raises(ValueError):
        restored.merge(_Histogram([1.0, 2.0]))  # different edges
    for garbage in ("x", {"buckets": [1], "counts": [1]},
                    {"buckets": [1.0], "counts": ["a", "b"]},
                    {"buckets": [1.0], "counts": [1, -2]}):
        with pytest.raises(ValueError):
            _Histogram.from_state(garbage)


def test_merged_histogram_and_labeled_prometheus_families():
    rec = StepMetrics()
    rec.observe("serving.request_s", 0.01)  # local unlabeled series
    base = rec.export_prometheus()

    h1, h2 = _Histogram(), _Histogram()
    for v in (0.002, 0.004, 0.004):
        h1.observe(v)
    for v in (0.25, 0.9):
        h2.observe(v)
    rec.merge_histogram("serving.request_s", h1.state(), replica="r1")
    rec.merge_histogram("serving.request_s", h2.state(), replica="r2")
    rec.merge_histogram("serving.request_s", h1.state(), replica="r1")

    merged = rec.merged_histogram("serving.request_s")
    assert merged["count"] == 2 * h1.count + h2.count
    r1 = rec.labeled_histogram("serving.request_s", replica="r1")
    r2 = rec.labeled_histogram("serving.request_s", replica="r2")
    assert r1["count"] + r2["count"] == merged["count"]
    assert rec.labeled_histogram("serving.request_s", replica="nope") is None

    out = rec.export_prometheus()
    # one # TYPE header per family, unlabeled + labeled series under it
    assert out.count("# TYPE alink_serving_request_seconds histogram") == 1
    assert 'alink_serving_request_seconds_bucket{replica="r1",le=' in out
    assert 'alink_serving_request_seconds_count{replica="r2"} 2' in out
    # every unlabeled line survives byte-identical — scrapes that predate
    # the fleet keep parsing the exact same series
    for line in base.splitlines():
        assert line in out, line


# ---------------------------------------------------------------------------
# Unit: telemetry delta source -> sink relay
# ---------------------------------------------------------------------------


def test_telemetry_delta_roundtrip_and_idle_none():
    worker, supervisor = StepMetrics(), StepMetrics()
    src = TelemetrySource(worker)
    sink = TelemetrySink(supervisor)

    worker.incr("serving.requests", 3)
    worker.observe("serving.request_s", 0.02)
    worker.observe("serving.request_s", 0.7)
    d1 = src.delta()
    assert d1["counters"]["serving.requests"] == 3
    sink.ingest(d1, replica="r1")
    assert src.delta() is None  # nothing changed -> nothing rides the hb

    worker.incr("serving.requests", 2)
    worker.observe("serving.request_s", 0.03)
    d2 = src.delta()
    assert d2["counters"]["serving.requests"] == 2  # delta, not cumulative
    assert d2["hists"]["serving.request_s"]["count"] == 1
    sink.ingest(d2, replica="r1")

    assert sink.counters_for("r1")["serving.requests"] == 5
    assert sink.counter_totals("serving.")["serving.requests"] == 5
    merged = supervisor.labeled_histogram("serving.request_s", replica="r1")
    assert merged["count"] == 3  # bucket-count deltas re-sum exactly
    sink.forget("r1")
    assert sink.counters_for("r1") == {}


def test_telemetry_sink_drops_garbage_whole():
    supervisor = StepMetrics()
    sink = TelemetrySink(supervisor)
    ok_hist = _Histogram()
    ok_hist.observe(0.5)
    for payload in (
            None, [], {"v": 99, "counters": {}, "hists": {}},
            {"v": 1, "counters": {"x": True}, "hists": {}},
            {"v": 1, "counters": {"x": "nan"}, "hists": {}},
            {"v": 1, "counters": {"n" * 300: 1}, "hists": {}},
            {"v": 1, "counters": {},
             "hists": {"h": {"buckets": [1], "counts": [1]}}},
            # one bad histogram poisons the WHOLE payload: the good
            # counter below must not merge
            {"v": 1, "counters": {"good": 1},
             "hists": {"bad": "garbage", "ok": ok_hist.state()}},
    ):
        with pytest.raises(ValueError):
            sink.ingest(payload, replica="r1")
    assert sink.counters_for("r1") == {}
    assert supervisor.labeled_histogram("ok", replica="r1") is None


def test_telemetry_source_trims_loudly_never_silently():
    rec = StepMetrics()
    src = TelemetrySource(rec)
    for i in range(520):
        rec.incr(f"c.{i:04d}")
    d = src.delta()
    assert len(d["counters"]) == 512  # MAX_COUNTERS
    # the trim itself is COUNTED and rides the next delta
    assert rec.counters()["telemetry.trimmed"] == 8
    d2 = src.delta()
    assert d2["counters"]["telemetry.trimmed"] == 8


def test_validate_telemetry_size_cap():
    # within the NAME caps but over the BYTE cap (huge int values):
    # oversized payloads are a bug or an attack, not data
    fat = {"v": 1, "hists": {},
           "counters": {"k" + "x" * 150 + str(i): 10 ** 250
                        for i in range(400)}}
    assert len(json.dumps(fat)) > MAX_PAYLOAD_BYTES
    with pytest.raises(ValueError):
        validate_telemetry(fat)
    ok = {"v": 1, "counters": {"a": 1}, "hists": {}}
    assert validate_telemetry(ok) == ({"a": 1}, {})


# ---------------------------------------------------------------------------
# Unit: chrome trace process lanes
# ---------------------------------------------------------------------------


def test_chrome_trace_local_first_event_byte_stable(monkeypatch):
    monkeypatch.setenv("ALINK_TRACING", "on")
    with trace_span("lane.local"):
        pass
    blob = chrome_trace()
    assert blob["traceEvents"][0] == {
        "ph": "M", "pid": 1, "tid": 0, "name": "process_name",
        "args": {"name": "alink_tpu"},
    }
    tid = tracer.last_trace_id()
    xs = [e for e in chrome_trace(tid)["traceEvents"] if e["ph"] == "X"]
    assert xs and all(e["pid"] == 1 for e in xs)


def test_chrome_trace_remote_spans_get_own_lanes(monkeypatch):
    monkeypatch.setenv("ALINK_TRACING", "on")
    with trace_span("lane.frontdoor") as sp:
        tid, sid = sp.trace_id, sp.span_id
    now = time.time()
    tracer.ingest([{"trace_id": tid, "span_id": "a-1", "parent_id": sid,
                    "name": "lane.batch", "t_start": now, "wall_s": 0.01,
                    "thread": "batcher"}], proc="r1", pid=3001)
    tracer.ingest([{"trace_id": tid, "span_id": "b-1", "parent_id": sid,
                    "name": "lane.batch", "t_start": now, "wall_s": 0.01,
                    "thread": "batcher"}], proc="r2", pid=3002)
    blob = chrome_trace(tid)
    xs = [e for e in blob["traceEvents"] if e["ph"] == "X"]
    assert sorted({e["pid"] for e in xs}) == [1, 3001, 3002]
    names = {e["pid"]: e["args"]["name"]
             for e in blob["traceEvents"]
             if e["ph"] == "M" and e["name"] == "process_name"}
    assert names == {1: "alink_tpu", 3001: "r1", 3002: "r2"}
    # a pid collision (two procs reporting the same OS pid) must not
    # alias lanes: the second gets a synthetic lane id
    tracer.ingest([{"trace_id": tid, "span_id": "c-1", "parent_id": sid,
                    "name": "lane.batch", "t_start": now,
                    "wall_s": 0.01}], proc="r3", pid=3001)
    pids = {e["pid"] for e in chrome_trace(tid)["traceEvents"]
            if e["ph"] == "X"}
    assert len(pids) == 4


# ---------------------------------------------------------------------------
# Frontend-level (in-thread fake replicas): outcome on the stitched tree
# ---------------------------------------------------------------------------


class _FakeReplica:
    """In-thread frame-protocol server with a scriptable handler (same
    shape as test_fleet's — raises ConnectionError to fail transport)."""

    def __init__(self, rid, handler):
        self.rid = rid
        self.handler = handler
        self.seen = []
        self._sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._sock.bind(("127.0.0.1", 0))
        self._sock.listen(8)
        self.port = self._sock.getsockname()[1]
        CircuitBreaker.replace_endpoint(f"fleet:{rid}", failure_threshold=5,
                                        reset_timeout=30.0)
        self.client = ReplicaClient(rid, "127.0.0.1", self.port)
        threading.Thread(target=self._accept, daemon=True).start()

    def _accept(self):
        while True:
            try:
                conn, _ = self._sock.accept()
            except OSError:
                return
            threading.Thread(target=self._serve, args=(conn,),
                             daemon=True).start()

    def _serve(self, conn):
        try:
            while True:
                op = recv_frame(conn)
                self.seen.append(op)
                try:
                    send_frame(conn, self.handler(op))
                except ConnectionError:
                    conn.close()
                    return
        except (ConnectionError, OSError, EOFError):
            conn.close()

    def close(self):
        self._sock.close()
        self.client.close()


def test_frontend_stamps_wire_context_into_frames(monkeypatch):
    monkeypatch.setenv("ALINK_TRACING", "on")
    ok = _FakeReplica("fx-ctx", lambda op: {"ok": True, "value": "A"})
    try:
        fe = FleetFrontend(lambda: [(ok.rid, ok.client)])
        assert fe.predict("m", (1.0,), timeout=10) == "A"
        op = ok.seen[-1]
        span = next(s for s in reversed(tracer.spans())
                    if s["name"] == "fleet.request")
        assert op["trace"] == {"trace_id": span["trace_id"],
                               "span_id": span["span_id"]}
    finally:
        ok.close()


def test_frontend_frame_carries_none_trace_when_off(monkeypatch):
    """Tracing off: the field is present but None — the frame SHAPE never
    changes (an old replica that ignores it keeps working; a new replica
    adopting None is a no-op)."""
    monkeypatch.setenv("ALINK_TRACING", "off")
    ok = _FakeReplica("fx-off", lambda op: {"ok": True, "value": "B"})
    try:
        fe = FleetFrontend(lambda: [(ok.rid, ok.client)])
        n0 = len(tracer.spans())
        assert fe.predict("m", (1.0,), timeout=10) == "B"
        assert ok.seen[-1]["trace"] is None
        assert len(tracer.spans()) == n0
    finally:
        ok.close()


def test_failover_outcome_retried_on_stitched_tree(monkeypatch):
    monkeypatch.setenv("ALINK_TRACING", "on")

    def die(op):
        raise ConnectionError("boom")

    dead = _FakeReplica("fx-t-dead", die)
    live = _FakeReplica("fx-t-live", lambda op: {"ok": True, "value": "A"})
    try:
        fe = FleetFrontend(lambda: [(dead.rid, dead.client),
                                    (live.rid, live.client)])
        for _ in range(4):  # whatever round-robin picks first, both paths
            assert fe.predict("m", (1.0,), timeout=10) == "A"
        retried = [s for s in tracer.spans()
                   if s["name"] == "fleet.request"
                   and s["outcome"] == "retried"]
        assert retried, "no fleet.request span recorded the failover"
        rep = job_report(retried[-1]["trace_id"])
        assert rep["tree"][0]["outcome"] == "retried"
        assert rep["retries"] >= 1
    finally:
        dead.close()
        live.close()


def test_deadline_expired_outcome_failed_on_stitched_tree(monkeypatch):
    from alink_tpu.common.exceptions import AkDeadlineExceededException

    monkeypatch.setenv("ALINK_TRACING", "on")
    ok = _FakeReplica("fx-t-dl", lambda op: {"ok": True, "value": "A"})
    try:
        fe = FleetFrontend(lambda: [(ok.rid, ok.client)])
        with pytest.raises(AkDeadlineExceededException):
            fe.predict("m", (1.0,), timeout=1e-9)
        span = next(s for s in reversed(tracer.spans())
                    if s["name"] == "fleet.request")
        assert span["outcome"] == "failed"
        assert "AkDeadlineExceededException" in span["error"]
        rep = job_report(span["trace_id"])
        assert rep["tree"][0]["outcome"] == "failed"
    finally:
        ok.close()


# ---------------------------------------------------------------------------
# The real thing: a 2-replica fleet, one stitched trace, exact fleet-wide
# quantiles (acceptance for the observability plane)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def fitted(tmp_path_factory):
    rng = np.random.default_rng(0)
    X = np.concatenate([rng.normal(c, 0.4, size=(40, 4))
                        for c in [(0, 0, 0, 0), (2, 2, 2, 2)]])
    y = np.repeat(["neg", "pos"], 40)
    t = MTable({f"f{i}": X[:, i] for i in range(4)}).with_column("label", y)
    model = Pipeline(
        StandardScaler(selectedCols=FEATS),
        VectorAssembler(selectedCols=FEATS, outputCol="vec"),
        NaiveBayes(vectorCol="vec", labelCol="label", predictionCol="pred"),
    ).fit(t)
    path = str(tmp_path_factory.mktemp("fleet_tracing") / "model.ak")
    model.save(path)
    return X, path


@pytest.fixture(scope="module")
def serial_rows(fitted):
    X, path = fitted
    srv = ModelServer()
    srv.load("m", path, SCHEMA, warmup_rows=[tuple(X[0])])
    rows = [tuple(r) for r in X]
    serial = [srv.predict("m", r) for r in rows]
    srv.close()
    return rows, serial


@pytest.fixture(scope="module")
def traced_fleet(fitted, serial_rows):
    _, path = fitted
    os.environ["ALINK_TRACING"] = "on"
    fleet = ServingFleet(FleetConfig(replicas=2, heartbeat_s=0.2,
                                     heartbeat_timeout_s=1.5))
    fleet.start()
    fleet.load("m", path, SCHEMA)
    yield fleet
    fleet.stop()
    os.environ.pop("ALINK_TRACING", None)


@pytest.mark.fleet
def test_fleet_stitched_trace_acceptance(traced_fleet, serial_rows):
    """ONE job_report tree per frontdoor predict: the frontend request
    span at the root, the replica-side request/batcher spans nested under
    it and process-tagged; chrome_trace lays the trace out in >= 2
    distinct process lanes."""
    rows, serial = serial_rows
    assert traced_fleet.predict("m", rows[0]) == serial[0]
    # not last_trace_id(): the heartbeat relay can ingest replica-side
    # LOAD spans (local roots — no span was active in the supervisor
    # during load) into the ring right after the predict, shadowing it
    tid = next(s["trace_id"] for s in reversed(tracer.spans())
               if s["name"] == "fleet.request")

    def _replica_spans():
        return [n for _, n in _walk(job_report(tid)["tree"])
                if n.get("proc")]

    # the replica's spans arrive by heartbeat relay, children a heartbeat
    # before the parents they finished inside — poll until the stitch is whole
    assert _wait(lambda: bool(_replica_spans())
                 and len(job_report(tid)["tree"]) == 1, timeout=15), \
        "replica spans never stitched into the frontdoor trace"
    rep = job_report(tid)
    rows_ = _walk(rep["tree"])
    assert len(rep["tree"]) == 1  # ONE tree, not a forest
    root = rep["tree"][0]
    assert root["name"] == "fleet.request" and root["outcome"] == "ok"
    names = {n["name"] for _, n in rows_}
    assert {"fleet.request", "serving.request", "serving.batch"} <= names
    remote = _replica_spans()
    assert {"serving.request", "serving.batch"} <= {
        n["name"] for n in remote}
    procs = {n["proc"] for n in remote}
    assert procs and procs <= {"r0", "r1"}
    pids = {n["pid"] for n in remote}
    assert all(isinstance(p, int) and p > 1 for p in pids)

    blob = chrome_trace(tid)
    xpids = {e["pid"] for e in blob["traceEvents"] if e["ph"] == "X"}
    assert len(xpids) >= 2  # frontdoor lane + replica lane(s)
    lane_names = {e["args"]["name"] for e in blob["traceEvents"]
                  if e["ph"] == "M" and e["name"] == "process_name"}
    assert "alink_tpu" in lane_names and (lane_names & {"r0", "r1"})


@pytest.mark.fleet
def test_fleet_wide_quantiles_are_exact_merge(traced_fleet, serial_rows):
    rows, serial = serial_rows

    def _merged():
        return metrics.merged_histogram("serving.request_s") or {}

    def _parts():
        parts = {r: metrics.labeled_histogram("serving.request_s",
                                              replica=r)
                 for r in ("r0", "r1")}
        return {r: p for r, p in parts.items() if p}

    def _quiesced():
        # deltas trail the requests by up to a heartbeat: wait until two
        # consecutive reads agree so merged/parts come from one snapshot
        before = _merged().get("count", 0)
        time.sleep(0.6)
        return _merged().get("count", 0) == before

    # the process-wide metrics singleton already holds labeled series from
    # earlier tests (this module's acceptance predict; other fleets — with
    # OTHER replica ids — when the full suite runs first), so the exact-
    # merge contract is asserted on the DELTA these 8 predicts add
    assert _wait(_quiesced, timeout=15)
    base = _merged()
    base_count, base_sum = base.get("count", 0), base.get("sum", 0.0)
    bparts = _parts()
    bp_count = sum(p["count"] for p in bparts.values())
    bp_sum = sum(p["sum"] for p in bparts.values())

    for k in range(8):
        assert traced_fleet.predict("m", rows[k]) == serial[k]

    assert _wait(
        lambda: _merged().get("count", 0) >= base_count + 8,
        timeout=15), "replica telemetry never reached supervisor"
    assert _wait(_quiesced, timeout=15)
    merged = _merged()
    parts = _parts()
    # exact merge: the fleet-wide count/sum deltas are the SUMS of the
    # per-replica deltas (bucket counts add; quantiles come from the
    # pooled buckets — never averaged averages)
    assert merged["count"] - base_count == sum(
        p["count"] for p in parts.values()) - bp_count
    # stats() rounds sums to 6 decimals, and four independently-rounded
    # values enter this delta — allow a few ulps at that resolution (the
    # count equality above is the integer-exact merge contract)
    assert merged["sum"] - base_sum == pytest.approx(
        sum(p["sum"] for p in parts.values()) - bp_sum, abs=5e-6)
    assert merged["max"] >= max(p["max"] for p in parts.values())

    # /metrics: replica-labeled family + the pooled-quantile gauges the
    # export hook refreshes
    out = metrics.export_prometheus()
    assert 'alink_serving_request_seconds_bucket{replica="' in out
    assert "alink_fleet_serving_request_s_p50" in out
    summ = traced_fleet.fleet_summary()
    assert summ["fleet_wide"]["serving.request_s"]["count"] \
        >= merged["count"]
    assert any(summ["replica_counters"].get(r) for r in ("r0", "r1"))


@pytest.mark.fleet
def test_fleet_tracing_off_bit_parity(fitted, serial_rows, monkeypatch):
    """ALINK_TRACING=off through the FULL fleet path (supervisor +
    workers): served bits identical to the single-process ground truth,
    zero spans recorded anywhere, heartbeats carry no span batches."""
    _, path = fitted
    rows, serial = serial_rows
    monkeypatch.setenv("ALINK_TRACING", "off")
    fleet = ServingFleet(FleetConfig(
        replicas=2, heartbeat_s=0.2, heartbeat_timeout_s=1.5,
        worker_env={"ALINK_TRACING": "off"}))
    try:
        fleet.start()
        fleet.load("m", path, SCHEMA)
        time.sleep(0.5)  # let any straggler relay from earlier fleets land
        n0 = len(tracer.spans())
        ingested0 = metrics.counters().get("fleet.spans_ingested", 0)
        got = [fleet.predict("m", r) for r in rows[:24]]
        assert got == serial[:24]
        got_many = fleet.predict_many("m", rows[:16])
        assert got_many == serial[:16]
        time.sleep(1.0)  # a few heartbeats: nothing must arrive
        assert len(tracer.spans()) == n0
        assert metrics.counters().get(
            "fleet.spans_ingested", 0) == ingested0
    finally:
        fleet.stop()
