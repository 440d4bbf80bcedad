"""Fault-tolerant serving fleet: multi-process replicas, failover routing,
chaos drills, and autoscaling (alink_tpu/serving/fleet + fleet_frontend).

The load-bearing guarantees pinned here:

- fleet predicts are BIT-IDENTICAL to a single-process ModelServer over the
  same rows (pickle frames round-trip rows bitwise; replicas run the same
  router);
- accepted-means-answered: a predict the front-end accepts either returns a
  result or raises a typed shed/deadline error — killing a replica mid-batch
  never loses an accepted request (the front-end re-dispatches under the
  retry budget);
- a respawned replica warms ONLY from the ``.ak.warmup.json`` sidecar: its
  jit trace delta stays 0 (live traffic never traces);
- drain-under-decommission completes every accepted request before the
  worker exits;
- corrupt heartbeat/stats payloads mark the replica unhealthy and count
  ``fleet.bad_heartbeat`` — they never crash the supervisor;
- autoscaling rides the shared BackpressureController: hysteresis, cooldown,
  and the flap breaker all apply to replica counts.

Fleets here are small (1-2 replicas, one of 4 for the parity case) and
fast-heartbeat so the whole module stays inside the tier-1 budget; a fleet's
rate under load is not measured on the chip (ROADMAP M7).
"""

import contextlib
import json
import os
import socket
import threading
import time

import numpy as np
import pytest

from alink_tpu.common import MTable
from alink_tpu.common.exceptions import (
    AkCircuitOpenException,
    AkDeadlineExceededException,
    AkIllegalArgumentException,
    AkPlanValidationException,
    AkServingOverloadException,
)
from alink_tpu.common.faults import (
    REPLICA_BEHAVIORS,
    FaultSpec,
    InjectedReplicaFault,
)
from alink_tpu.common.metrics import metrics
from alink_tpu.common.resilience import CircuitBreaker
from alink_tpu.parallel.distributed import scrub_cluster_env
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
from alink_tpu.serving.fleet import _validate_hb_stats
from alink_tpu.serving.fleet_frontend import (
    DRAINING,
    encode_error,
    recv_frame,
    send_frame,
)

pytestmark = pytest.mark.fleet

SCHEMA = "f0 double, f1 double, f2 double, f3 double"
FEATS = ["f0", "f1", "f2", "f3"]


def _counter(name):
    return metrics.counters("fleet.").get(name, 0)


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
    path = str(tmp_path_factory.mktemp("fleet") / "model.ak")
    model.save(path)
    return X, path


@pytest.fixture(scope="module")
def serial_rows(fitted):
    """Single-process ground truth; the load also writes the warmup
    sidecar every fleet replica warms from."""
    X, path = fitted
    srv = ModelServer()
    srv.load("m", path, SCHEMA, warmup_rows=[tuple(X[0])])
    rows = [tuple(r) for r in X]
    serial = [srv.predict("m", r) for r in rows]
    srv.close()
    return rows, serial


@pytest.fixture(scope="module")
def fleet2(fitted, serial_rows):
    """One 2-replica fleet shared by the fault-free tests."""
    _, path = fitted
    fleet = ServingFleet(FleetConfig(replicas=2, heartbeat_s=0.2,
                                     heartbeat_timeout_s=1.5))
    fleet.start()
    fleet.load("m", path, SCHEMA)
    yield fleet
    fleet.stop()


def _wait(pred, timeout=30.0, interval=0.1):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(interval)
    return False


# ---------------------------------------------------------------------------
# Unit: env scrub, breaker registry readout, replica fault kinds
# ---------------------------------------------------------------------------


def test_scrub_cluster_env_strips_training_pod_vars():
    env = {"COORDINATOR_ADDRESS": "h:1", "NUM_PROCESSES": "2",
           "PROCESS_ID": "0", "PATH": "/bin", "ALINK_FLEET_REPLICAS": "2"}
    out = scrub_cluster_env(env)
    assert "COORDINATOR_ADDRESS" not in out
    assert "NUM_PROCESSES" not in out
    assert "PROCESS_ID" not in out
    assert out["PATH"] == "/bin" and out["ALINK_FLEET_REPLICAS"] == "2"


def test_endpoint_states_prefix_readout():
    CircuitBreaker.replace_endpoint("fleet-test:a", failure_threshold=1)
    CircuitBreaker.replace_endpoint("fleet-test:b", failure_threshold=1)
    CircuitBreaker.for_endpoint("fleet-test:a").record_failure()
    states = CircuitBreaker.endpoint_states("fleet-test:")
    assert states["fleet-test:a"] == "open"
    assert states["fleet-test:b"] == "closed"


def test_replica_fault_kinds_parse_and_target_one_incarnation():
    spec = FaultSpec.parse(
        "replica:count=1,kinds=kill_mid_batch,match=r1.g2.batch")
    # other replicas / other generations never match (and consume nothing)
    spec.fire("replica", label="r0.g1.batch")
    spec.fire("replica", label="r1.g3.batch")
    with pytest.raises(InjectedReplicaFault) as ei:
        spec.fire("replica", label="r1.g2.batch")
    assert ei.value.behavior == "kill_mid_batch"
    assert ei.value.behavior in REPLICA_BEHAVIORS
    spec.fire("replica", label="r1.g2.batch")  # count=1: spent


def test_replica_fault_kind_rejected_elsewhere():
    from alink_tpu.common.exceptions import AkParseErrorException

    with pytest.raises(AkParseErrorException):
        FaultSpec.parse("replica:count=1,kinds=no_such_behavior")


# ---------------------------------------------------------------------------
# Unit: heartbeat payload hardening
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("garbage", [
    "not-a-dict",
    {"accepted": "NaN-ish-garbage"},
    {"queue_s": "not-a-dict"},
    {"queue_s": {"count": "x"}},
    {"synced": [1, 2, 3]},
])
def test_validate_hb_stats_rejects_garbage(garbage):
    with pytest.raises((ValueError, TypeError)):
        _validate_hb_stats(garbage)


def test_validate_hb_stats_accepts_real_payload():
    out = _validate_hb_stats({
        "accepted": 3, "completed": 3, "shed": 0, "queued": 0,
        "jit_trace": 8, "trace_delta": 0,
        "queue_s": {"count": 3, "sum": 0.01},
        "request_s": {"count": 3, "sum": 0.02, "p50": 0.005},
        "synced": {"m": 1},
    })
    assert out["synced"] == {"m": 1}


# ---------------------------------------------------------------------------
# Unit: FleetConfig env knobs
# ---------------------------------------------------------------------------


def test_fleet_config_env_knobs(monkeypatch):
    monkeypatch.setenv("ALINK_FLEET_REPLICAS", "3")
    monkeypatch.setenv("ALINK_FLEET_AUTOSCALE", "1")
    monkeypatch.setenv("ALINK_FLEET_MIN_REPLICAS", "2")
    monkeypatch.setenv("ALINK_FLEET_MAX_REPLICAS", "8")
    monkeypatch.setenv("ALINK_FLEET_HEARTBEAT_S", "0.1")
    monkeypatch.setenv("ALINK_FLEET_HEARTBEAT_TIMEOUT_S", "0.9")
    monkeypatch.setenv("ALINK_FLEET_HANG_GRACE_S", "2.5")
    monkeypatch.setenv("ALINK_FLEET_RESPAWN", "0")
    monkeypatch.setenv("ALINK_FLEET_TARGET_QUEUE_S", "0.2")
    cfg = FleetConfig.default()
    assert cfg.replicas == 3 and cfg.autoscale
    assert cfg.min_replicas == 2 and cfg.max_replicas == 8
    assert cfg.heartbeat_s == 0.1 and cfg.heartbeat_timeout_s == 0.9
    assert cfg.hang_grace_s == 2.5 and not cfg.respawn
    assert cfg.target_queue_s == 0.2


# ---------------------------------------------------------------------------
# Unit: ALK110 pre-flight (fleet model without warmup sidecar)
# ---------------------------------------------------------------------------


def test_alk110_off_mode_skips(monkeypatch, tmp_path):
    from alink_tpu.analysis import preflight_fleet_models

    monkeypatch.delenv("ALINK_VALIDATE_PLAN", raising=False)
    assert preflight_fleet_models([("m", str(tmp_path / "no.ak"))]) is None


def test_alk110_warns_without_sidecar(monkeypatch, tmp_path):
    from alink_tpu.analysis import WARNING, preflight_fleet_models

    monkeypatch.setenv("ALINK_VALIDATE_PLAN", "warn")
    blob = tmp_path / "bare.ak"
    blob.write_bytes(b"x")
    report = preflight_fleet_models([("m", str(blob))])
    assert report.by_rule() == {"ALK110": 1}
    assert report.diagnostics[0].severity == WARNING


def test_alk110_error_severity_with_respawn(monkeypatch, tmp_path):
    from alink_tpu.analysis import preflight_fleet_models

    monkeypatch.setenv("ALINK_VALIDATE_PLAN", "warn")
    blob = tmp_path / "bare.ak"
    blob.write_bytes(b"x")
    report = preflight_fleet_models([("m", str(blob))], recovery=True)
    assert len(report.errors()) == 1
    monkeypatch.setenv("ALINK_VALIDATE_PLAN", "error")
    with pytest.raises(AkPlanValidationException):
        preflight_fleet_models([("m", str(blob))], recovery=True)


def test_alk110_clean_with_sidecar(monkeypatch, fitted, serial_rows):
    from alink_tpu.analysis import preflight_fleet_models

    _, path = fitted  # serial_rows fixture wrote the sidecar
    monkeypatch.setenv("ALINK_VALIDATE_PLAN", "error")
    report = preflight_fleet_models([("m", path)], recovery=True)
    assert report.ok


# ---------------------------------------------------------------------------
# Unit: failover front-end vs fake in-thread replicas
# ---------------------------------------------------------------------------


class _FakeReplica:
    """In-thread frame-protocol server with a scriptable handler. The
    handler gets the decoded op and returns a response dict, or raises
    ``ConnectionError`` to slam the connection shut (transport failure)."""

    def __init__(self, rid, handler):
        self.rid = rid
        self.handler = handler
        self.calls = 0
        self._sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._sock.bind(("127.0.0.1", 0))
        self._sock.listen(8)
        self.port = self._sock.getsockname()[1]
        CircuitBreaker.replace_endpoint(f"fleet:{rid}", failure_threshold=3,
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
                self.calls += 1
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


def _frontend(*fakes):
    return FleetFrontend(
        lambda: [(f.rid, f.client) for f in fakes])


def test_frontend_failover_on_transport_error():
    def die(op):
        raise ConnectionError("boom")

    dead = _FakeReplica("fx-dead", die)
    live = _FakeReplica("fx-live", lambda op: {"ok": True, "value": "A"})
    try:
        before = _counter("fleet.failovers")
        fe = _frontend(dead, live)
        # whichever replica round-robin picks first, the answer arrives
        for _ in range(4):
            assert fe.predict("m", (1.0,), timeout=10.0) == "A"
        assert dead.calls >= 1  # it was tried, failed, and failed over
        assert _counter("fleet.failovers") > before
    finally:
        dead.close()
        live.close()


def test_frontend_typed_error_propagates_without_failover():
    def shed(op):
        return encode_error(AkServingOverloadException("queue full"))

    a = _FakeReplica("fx-shed-a", shed)
    b = _FakeReplica("fx-shed-b", shed)
    try:
        fe = _frontend(a, b)
        before = _counter("fleet.failovers")
        with pytest.raises(AkServingOverloadException):
            fe.predict("m", (1.0,), timeout=10.0)
        # the replica ANSWERED: its typed error is the answer, no failover
        assert a.calls + b.calls == 1
        assert _counter("fleet.failovers") == before
    finally:
        a.close()
        b.close()


def test_frontend_draining_redirects():
    draining = _FakeReplica(
        "fx-drain", lambda op: {"ok": False, "etype": DRAINING, "msg": ""})
    live = _FakeReplica("fx-drain-live",
                        lambda op: {"ok": True, "value": "B"})
    try:
        fe = _frontend(draining, live)
        for _ in range(4):
            assert fe.predict("m", (1.0,), timeout=10.0) == "B"
    finally:
        draining.close()
        live.close()


def test_frontend_no_replica_is_typed_overload():
    fe = FleetFrontend(lambda: [])
    with pytest.raises(AkServingOverloadException):
        fe.predict("m", (1.0,), timeout=5.0)


def test_frontend_deadline_expires_typed():
    def stall(op):
        time.sleep(3.0)  # longer than the socket budget: never answers
        return {"ok": True, "value": "late"}

    slow = _FakeReplica("fx-slow", stall)
    try:
        fe = _frontend(slow)
        with pytest.raises(
                (AkDeadlineExceededException, AkServingOverloadException)):
            fe.predict("m", (1.0,), timeout=0.5)
    finally:
        slow.close()


def test_frontend_malformed_frame_is_transport_error():
    torn = _FakeReplica("fx-torn", lambda op: "not-a-dict")
    live = _FakeReplica("fx-torn-live",
                        lambda op: {"ok": True, "value": "C"})
    try:
        fe = _frontend(torn, live)
        for _ in range(4):
            assert fe.predict("m", (1.0,), timeout=10.0) == "C"
    finally:
        torn.close()
        live.close()


# ---------------------------------------------------------------------------
# Unit: ModelStreamPublisher fleet duck-typing
# ---------------------------------------------------------------------------


def test_publisher_binds_fleet_source_and_counts_swap_outcomes(tmp_path):
    from alink_tpu.modelstream import ModelStreamPublisher

    class FakeFleet:
        def __init__(self):
            self.sources = {}
            self.loads = []
            self._config = None

        def bind_model_source(self, name, resolver):
            self.sources[name] = resolver

        def has_model(self, name):
            return any(call[0] == name for call in self.loads)

        def load(self, name, path, schema, config=None):
            self.loads.append((name, path))
            return {"model": name, "seq": 1,
                    "replicas": {"r0": {"ok": True},
                                 "r1": {"ok": False, "error": "x"}}}

    fleet = FakeFleet()
    pub = ModelStreamPublisher(str(tmp_path / "store"), "live",
                               server=fleet, input_schema=SCHEMA)
    # the publisher registered its store-latest resolver at construction
    assert "live" in fleet.sources
    assert fleet.sources["live"]() is None  # nothing committed yet
    assert not pub._server_has_model()  # duck-types fleet.has_model

    ok0 = metrics.counters("modelstream.").get(
        "modelstream.fleet_swap_ok", 0)
    miss0 = metrics.counters("modelstream.").get(
        "modelstream.fleet_swap_missed", 0)
    pub.store.publish(0, lambda p: open(p, "wb").write(b"blob"),
                      meta={"model": "live"})
    pub.swap_epoch(0)
    assert fleet.loads and fleet.loads[0][0] == "live"
    counters = metrics.counters("modelstream.")
    assert counters["modelstream.fleet_swap_ok"] == ok0 + 1
    assert counters["modelstream.fleet_swap_missed"] == miss0 + 1
    assert pub._server_has_model()
    # after the commit, the bound resolver serves the blob path
    assert fleet.sources["live"]() == pub.store.blob_path(0)


# ---------------------------------------------------------------------------
# Live fleet: parity, zero-trace, observability, hardening
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n", [1, 2, 4])
def test_fleet_parity_with_single_process(n, fitted, serial_rows, request):
    """Bit parity with the single-process server at every fleet size (2 is
    the shared fleet); the other sizes also stay at zero traces."""
    rows, serial = serial_rows
    with contextlib.ExitStack() as stack:
        if n == 2:
            fleet = request.getfixturevalue("fleet2")
        else:
            fleet = stack.enter_context(ServingFleet(FleetConfig(
                replicas=n, heartbeat_s=0.2, heartbeat_timeout_s=1.5)))
            fleet.load("m", fitted[1], SCHEMA)
        got = [fleet.predict("m", r) for r in rows]
        assert got == serial
        assert fleet.predict_many("m", rows[:16]) == serial[:16]

        def warm_and_untraced():
            s = fleet.fleet_summary()
            return s["states"] == {"ready": n} and all(
                r["trace_delta"] == 0 for r in s["replicas"])

        if n != 2:  # test_fleet_zero_trace_after_warmup has the shared one
            assert _wait(warm_and_untraced, timeout=5.0)


def test_fleet_zero_trace_after_warmup(fleet2, serial_rows):
    rows, _ = serial_rows
    for r in rows[:8]:  # traffic AFTER sidecar warmup
        fleet2.predict("m", r)
    assert _wait(lambda: all(
        r["trace_delta"] == 0
        for r in fleet2.fleet_summary()["replicas"]), timeout=5.0)
    summary = fleet2.fleet_summary()
    assert summary["states"] == {"ready": 2}
    assert all(r["trace_delta"] == 0 for r in summary["replicas"])


def test_replicas_announce_the_device_they_serve_from(fleet2):
    """Every ready frame names the platform jax gave the worker; the fleet
    readout carries it, so no replica serves from a device unannounced."""
    for r in fleet2.fleet_summary()["replicas"]:
        assert r["device"]["platform"] == "cpu"     # this suite's backend
        assert r["device"]["device_count"] >= 1 and r["device"]["device_kind"]
        assert r["fail_reason"] is None


def test_replica_refuses_to_serve_from_a_device_it_was_not_meant_to_have(
        monkeypatch):
    """A supervisor that trained on a chip holds it; its workers then get
    the CPU from jax. Such a replica must report not-ready with the reason
    (never serve from the CPU unannounced), start() must raise with that
    reason instead of waiting out the ready timeout, and the refused
    replica must not respawn-loop."""
    fleet = ServingFleet(FleetConfig(replicas=1, heartbeat_s=0.2,
                                     ready_timeout_s=120.0))
    # stand in for a supervisor whose own backend is a TPU
    monkeypatch.setattr(fleet, "_expected_platform", lambda: "tpu")
    spawned0, refused0 = _counter("fleet.spawned"), \
        _counter("fleet.replica_refused")
    t0 = time.monotonic()
    try:
        with pytest.raises(Exception) as ei:
            fleet.start()
        assert "meant to serve from 'tpu'" in str(ei.value)
        assert "jax gave it 'cpu'" in str(ei.value)
        assert time.monotonic() - t0 < 60.0          # not the ready timeout
        assert _counter("fleet.replica_refused") == refused0 + 1
        assert _wait(lambda: fleet.replica_states() == {"r0": "dead"})
        time.sleep(0.6)                               # a few monitor ticks
        assert _counter("fleet.spawned") == spawned0 + 1   # no respawn
        rep = fleet.fleet_summary()["replicas"][0]
        assert rep["device"] is None and "tpu" in rep["fail_reason"]
    finally:
        fleet.stop()


def test_fleet_load_requires_saved_path(fleet2):
    with pytest.raises(AkIllegalArgumentException):
        fleet2.load("bad", object())


def test_fleet_summary_joins_serving_summary(fleet2):
    from alink_tpu.serving import serving_summary
    from alink_tpu.serving.fleet import active_fleet_summary

    assert active_fleet_summary() is not None
    out = serving_summary()
    assert "fleet" in out
    assert out["fleet"]["states"].get("ready") == 2
    assert set(out["fleet"]["breakers"]) >= {"fleet:r0", "fleet:r1"}


def test_fleet_gauges_on_prometheus_export(fleet2):
    text = metrics.export_prometheus()
    assert 'alink_fleet_replicas{state="ready"} 2.0' in text


def test_frontdoor_serves_frame_protocol(fleet2, serial_rows):
    rows, serial = serial_rows
    lsn = fleet2.open_frontdoor()
    try:
        sock = socket.create_connection((lsn.host, lsn.port), timeout=10)
        send_frame(sock, {"op": "ping"})
        assert recv_frame(sock) == {"ok": True, "value": True}
        send_frame(sock, {"op": "predict", "name": "m", "row": rows[0]})
        resp = recv_frame(sock)
        assert resp["ok"] and tuple(resp["value"]) == serial[0]
        sock.close()
    finally:
        lsn.close()


def test_control_port_garbage_never_crashes_supervisor(fleet2, serial_rows):
    rows, serial = serial_rows
    before = _counter("fleet.bad_heartbeat")
    addr = ("127.0.0.1", fleet2._control_port)
    # raw garbage bytes, then valid-JSON-but-not-an-object, then a fake
    # hello with a bad token — all dropped, all counted or rejected
    for payload in (b"\x00\xffgarbage-bytes\n", b"[1, 2, 3]\n",
                    json.dumps({"t": "hello", "token": "wrong",
                                "rid": "r0", "gen": 1}).encode() + b"\n"):
        s = socket.create_connection(addr, timeout=5)
        s.sendall(payload)
        s.close()
    assert _wait(lambda: _counter("fleet.bad_heartbeat") >= before + 3,
                 timeout=5.0)
    # the real replicas are untouched and still serving
    assert fleet2.replica_states() == {"r0": "ready", "r1": "ready"}
    assert fleet2.predict("m", rows[0]) == serial[0]


def test_fleet_swap_bump_and_resync(fleet2, fitted):
    _, path = fitted
    out = fleet2.load("m2", path, SCHEMA)
    assert all(r["ok"] for r in out["replicas"].values())
    seq = out["seq"]
    assert _wait(lambda: all(
        r["synced"].get("m2") == seq
        for r in fleet2.fleet_summary()["replicas"]), timeout=5.0)

    # simulate a replica that missed the broadcast: wind its synced
    # version back and let the health-recheck resync path repair it
    rep = fleet2._replicas["r1"]
    rep.synced["m2"] = -1
    resyncs = _counter("fleet.resyncs")
    fleet2._resync_if_stale(rep)
    assert rep.synced["m2"] == seq
    assert _counter("fleet.resyncs") == resyncs + 1
    fleet2.unload("m2")


def test_drain_under_load_completes_all_accepted(fleet2, serial_rows):
    """Decommission r1 while clients are mid-flight: every accepted
    request completes (drain or failover — never lost), and scale_to
    restores the fleet for the remaining tests."""
    rows, serial = serial_rows
    lost, done = [], []

    def client(cid):
        for i in range(20):
            k = (cid * 20 + i) % len(rows)
            try:
                assert fleet2.predict("m", rows[k], timeout=30) == serial[k]
                done.append(k)
            except Exception as e:
                lost.append(f"{type(e).__name__}: {e}")

    threads = [threading.Thread(target=client, args=(c,)) for c in range(4)]
    drains = _counter("fleet.drains")
    for th in threads:
        th.start()
    fleet2.decommission("r1")
    for th in threads:
        th.join(timeout=60)
    assert not lost, lost[:3]
    assert len(done) == 80
    assert _counter("fleet.drains") == drains + 1
    assert fleet2.replica_states() == {"r0": "ready"}

    fleet2.scale_to(2)  # the new replica resyncs every desired model
    states = fleet2.replica_states()
    assert len(states) == 2 and all(s == "ready" for s in states.values())
    new_rid = next(rid for rid in states if rid != "r0")
    assert _wait(lambda: all(
        r["synced"].get("m") for r in fleet2.fleet_summary()["replicas"]),
        timeout=10.0)
    got = [fleet2.predict("m", r) for r in rows[:12]]
    assert got == serial[:12]
    assert new_rid != "r1"  # fresh rid, fresh generation, fresh breaker


# ---------------------------------------------------------------------------
# Chaos drills (own fleets: faults are armed via worker_env)
# ---------------------------------------------------------------------------


def test_kill_mid_batch_failover_never_loses_requests(fitted, serial_rows):
    """THE fleet robustness pin: r1's first incarnation dies mid-batch at
    load; accepted requests all complete bit-identically (failover), the
    respawn warms from the sidecar with zero traces, and the fleet is
    back at full strength."""
    _, path = fitted
    rows, serial = serial_rows
    deaths = _counter("fleet.replica_deaths")
    failovers = _counter("fleet.failovers")
    with ServingFleet(FleetConfig(
            replicas=2, heartbeat_s=0.2, heartbeat_timeout_s=1.0,
            worker_env={"ALINK_FAULT_SPEC":
                        "replica:count=1,kinds=kill_mid_batch,"
                        "match=r1.g2.batch"})) as fleet:
        fleet.load("m", path, SCHEMA)
        lost, shed, done = [], [], {}

        def client(cid):
            for i in range(25):
                k = (cid * 25 + i) % len(rows)
                try:
                    done[k] = fleet.predict("m", rows[k], timeout=30)
                except (AkServingOverloadException, AkCircuitOpenException,
                        AkDeadlineExceededException) as e:
                    shed.append(type(e).__name__)
                except Exception as e:
                    lost.append(f"{type(e).__name__}: {e}")

        threads = [threading.Thread(target=client, args=(c,))
                   for c in range(6)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=60)

        # accepted-means-answered: nothing vanished, results bit-identical
        assert not lost, lost[:3]
        assert all(serial[k] == v for k, v in done.items())
        assert _counter("fleet.replica_deaths") == deaths + 1
        assert _counter("fleet.failovers") > failovers

        # respawn: same rid, next generation, warmed from the sidecar only
        assert _wait(lambda: fleet.fleet_summary()["states"].get(
            "ready") == 2, timeout=30.0)
        assert _wait(lambda: all(
            r["trace_delta"] == 0 and r["synced"].get("m")
            for r in fleet.fleet_summary()["replicas"]), timeout=10.0)
        summary = fleet.fleet_summary()
        respawned = [r for r in summary["replicas"] if r["replica"] == "r1"]
        assert respawned[0]["gen"] > 2
        assert [ld["warmup_source"] for ld in respawned[0]["loads"]] \
            == ["sidecar"]
        assert summary["counters"]["fleet.respawns"] >= 1

        # post-recovery traffic still bit-identical
        assert [fleet.predict("m", r) for r in rows[:12]] == serial[:12]


def test_hang_detected_then_replaced(fitted, serial_rows):
    """A hung replica (alive, silent on heartbeats AND data plane) is
    marked unhealthy at heartbeat timeout, killed past the hang grace,
    and respawned — while the healthy replica keeps serving."""
    _, path = fitted
    rows, serial = serial_rows
    hung0 = _counter("fleet.hung_killed")
    with ServingFleet(FleetConfig(
            replicas=2, heartbeat_s=0.2, heartbeat_timeout_s=0.8,
            hang_grace_s=1.0,
            worker_env={"ALINK_FAULT_SPEC":
                        "replica:count=1,kinds=hang,"
                        "match=r1.g2.heartbeat"})) as fleet:
        fleet.load("m", path, SCHEMA)
        # service continuity all through the detect->kill->respawn window
        deadline = time.monotonic() + 15
        while time.monotonic() < deadline:
            assert fleet.predict("m", rows[0], timeout=30) == serial[0]
            if _counter("fleet.hung_killed") > hung0 and \
                    fleet.fleet_summary()["states"].get("ready") == 2:
                break
            time.sleep(0.1)
        assert _counter("fleet.hung_killed") == hung0 + 1
        summary = fleet.fleet_summary()
        assert summary["states"].get("ready") == 2
        assert [r["gen"] for r in summary["replicas"]
                if r["replica"] == "r1"][0] > 2


def test_refuse_health_keeps_data_plane_up(fitted, serial_rows):
    """refuse_health stops heartbeats only: the replica goes unhealthy
    (unrouted) while its data plane would still answer — health-based
    routing without a real death. No respawn: the process is alive."""
    _, path = fitted
    rows, serial = serial_rows
    with ServingFleet(FleetConfig(
            replicas=2, heartbeat_s=0.2, heartbeat_timeout_s=0.8,
            hang_grace_s=3600.0,  # never escalate to a kill here
            worker_env={"ALINK_FAULT_SPEC":
                        "replica:count=1,kinds=refuse_health,"
                        "match=r1.g2.heartbeat"})) as fleet:
        fleet.load("m", path, SCHEMA)
        assert _wait(lambda: fleet.replica_states().get(
            "r1") == "unhealthy", timeout=10.0)
        # unrouted but alive: predicts ride r0, bit-identical
        assert [fleet.predict("m", r) for r in rows[:8]] == serial[:8]
        # the worker process did NOT die — its data plane still answers
        rep = fleet._replicas["r1"]
        assert rep.proc.poll() is None
        resp = rep.client.call({"op": "ping"}, timeout=5.0)
        assert resp["ok"] and resp["value"]["rid"] == "r1"


# ---------------------------------------------------------------------------
# Autoscaling: scripted backlog schedule through the shared controller
# ---------------------------------------------------------------------------


def test_autoscale_up_down_and_flap_breaker(fitted, serial_rows):
    """Scripted lag schedule: sustained backlog scales 1→2, idle scales
    2→1, and the next reversal trips the flap breaker (the controller's
    hysteresis machinery, reused verbatim from elastic streaming)."""
    _, path = fitted
    # epoch → injected backlog seconds (anything ≥ target*0.5 is "high")
    schedule = {1: 1.0, 2: 0.0, 3: 1.0, 4: 1.0}
    up0 = _counter("fleet.autoscale_up")
    down0 = _counter("fleet.autoscale_down")
    with ServingFleet(FleetConfig(
            replicas=1, autoscale=True, min_replicas=1, max_replicas=2,
            heartbeat_s=0.2, heartbeat_timeout_s=1.5,
            autoscale_interval_s=3600.0,  # ticks driven by the test
            autoscale_patience=1, autoscale_cooldown=0, max_flips=2,
            lag_fn=lambda stats: schedule.get(stats["epoch"], 0.0),
    )) as fleet:
        fleet.load("m", path, SCHEMA)
        assert fleet._autoscale_tick() == 2          # backlog: scale out
        states = fleet.replica_states()
        assert len(states) == 2
        assert all(s == "ready" for s in states.values())
        assert _counter("fleet.autoscale_up") == up0 + 1

        assert fleet._autoscale_tick() == 1          # idle: scale in
        assert _wait(lambda: len(fleet.replica_states()) == 1, timeout=20.0)
        assert _counter("fleet.autoscale_down") == down0 + 1

        # third reversal inside the window: flap breaker opens, no action
        assert fleet._autoscale_tick() is None
        assert fleet.fleet_summary()["autoscale"]["breaker_open"]
        assert len(fleet.replica_states()) == 1
        assert fleet._autoscale_tick() is None       # latched open
