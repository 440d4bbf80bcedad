"""Fault-tolerant execution runtime (common/resilience.py, common/faults.py,
executor/streaming/connector integration): error taxonomy, retry/backoff,
circuit breaking, graceful degradation, dead-letter ingest, and seeded
deterministic fault injection — the acceptance gate is *parity*: a run
under injected transient faults must produce bit-identical output to the
fault-free run, and a fatal fault must propagate unchanged."""

import json
import threading
import time

import numpy as np
import pytest

from alink_tpu.common import faults
from alink_tpu.common.exceptions import (
    AkCircuitOpenException,
    AkIllegalArgumentException,
    AkIllegalStateException,
    AkRetryableException,
    is_retryable,
    mark_retryable,
)
from alink_tpu.common.metrics import metrics
from alink_tpu.common.mtable import MTable
from alink_tpu.common.resilience import (
    CircuitBreaker,
    RetryPolicy,
    dead_letters,
    resilience_summary,
    with_retries,
)
from alink_tpu.operator.batch import TableSourceBatchOp


@pytest.fixture(autouse=True)
def _clean_resilience_state():
    faults.clear()
    CircuitBreaker.reset_all()
    dead_letters.clear()
    yield
    faults.clear()
    CircuitBreaker.reset_all()
    dead_letters.clear()


def _counter_delta(name):
    """Counters are process-global; tests assert on deltas."""
    start = metrics.counter(name)
    return lambda: metrics.counter(name) - start


# -- error taxonomy ----------------------------------------------------------


def test_is_retryable_classification():
    assert is_retryable(AkRetryableException("transient"))
    assert is_retryable(TimeoutError("deadline"))
    assert is_retryable(ConnectionResetError())
    assert is_retryable(OSError("socket closed"))
    assert is_retryable(mark_retryable(RuntimeError("lib-specific")))
    # kafka-python contract: errors self-declare via `.retriable`
    class FakeKafkaError(Exception):
        retriable = True
    assert is_retryable(FakeKafkaError())

    assert not is_retryable(AkIllegalArgumentException("bad arg"))
    assert not is_retryable(AkIllegalStateException("bad state"))
    assert not is_retryable(FileNotFoundError("gone"))
    assert not is_retryable(PermissionError("denied"))
    assert not is_retryable(RuntimeError("unknown"))
    assert not is_retryable(ValueError("parse"))
    assert not is_retryable(KeyboardInterrupt())


def test_injected_fault_kinds_map_to_taxonomy():
    assert is_retryable(faults.InjectedFaultError("x"))
    assert not is_retryable(faults.InjectedFatalError("x"))


# -- retry policy engine -----------------------------------------------------


def test_with_retries_recovers_from_transient():
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise AkRetryableException("blip")
        return "ok"

    got = with_retries(flaky, RetryPolicy(max_attempts=5, base_delay=0.001),
                       sleep=lambda s: None)
    assert got == "ok" and calls["n"] == 3


def test_with_retries_fatal_fails_fast():
    calls = {"n": 0}

    def fatal():
        calls["n"] += 1
        raise AkIllegalArgumentException("bad")

    with pytest.raises(AkIllegalArgumentException):
        with_retries(fatal, RetryPolicy(max_attempts=5, base_delay=0.001),
                     sleep=lambda s: None)
    assert calls["n"] == 1  # fatal: exactly one attempt


def test_with_retries_exhausts_attempt_budget():
    calls = {"n": 0}

    def always():
        calls["n"] += 1
        raise AkRetryableException("forever")

    with pytest.raises(AkRetryableException):
        with_retries(always, RetryPolicy(max_attempts=3, base_delay=0.001),
                     sleep=lambda s: None)
    assert calls["n"] == 3


def test_with_retries_deadline_budget():
    calls = {"n": 0}

    def always():
        calls["n"] += 1
        raise AkRetryableException("forever")

    # huge attempt budget but a zero wall budget: the first failure is final
    with pytest.raises(AkRetryableException):
        with_retries(always,
                     RetryPolicy(max_attempts=100, base_delay=0.01,
                                 deadline=0.0),
                     sleep=lambda s: None)
    assert calls["n"] == 1


def test_backoff_delays_are_bounded_and_grow():
    p = RetryPolicy(base_delay=0.1, multiplier=2.0, max_delay=1.0,
                    jitter=False)
    assert p.delay(0) == pytest.approx(0.1)
    assert p.delay(1) == pytest.approx(0.2)
    assert p.delay(10) == pytest.approx(1.0)  # capped
    pj = RetryPolicy(base_delay=0.1, multiplier=2.0, max_delay=1.0,
                     jitter=True)
    for k in range(6):
        d = pj.delay(k)
        assert 0.0 <= d <= min(1.0, 0.1 * 2 ** k)  # full jitter envelope


def test_retries_off_env_restores_fail_fast(monkeypatch):
    monkeypatch.setenv("ALINK_RETRIES", "off")
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        raise AkRetryableException("blip")

    with pytest.raises(AkRetryableException):
        with_retries(flaky, sleep=lambda s: None)
    assert calls["n"] == 1


def test_policy_env_knobs(monkeypatch):
    monkeypatch.setenv("ALINK_RETRY_MAX_ATTEMPTS", "7")
    monkeypatch.setenv("ALINK_RETRY_DEADLINE_S", "12.5")
    p = RetryPolicy.default()
    assert p.max_attempts == 7 and p.deadline == 12.5
    monkeypatch.setenv("ALINK_RETRY_MAX_ATTEMPTS", "not-a-number")
    assert RetryPolicy.default().max_attempts == 3  # typo -> default


# -- circuit breaker ---------------------------------------------------------


def test_circuit_breaker_opens_and_half_opens():
    t = {"now": 0.0}
    b = CircuitBreaker(failure_threshold=3, reset_timeout=10.0,
                       name="svc", clock=lambda: t["now"])
    for _ in range(3):
        b.before_call()
        b.record_failure()
    assert b.is_open
    with pytest.raises(AkCircuitOpenException):
        b.before_call()
    # circuit-open is itself classified retryable (transient by definition)
    try:
        b.before_call()
    except AkCircuitOpenException as e:
        assert is_retryable(e)
    t["now"] = 10.5  # past reset: exactly one probe allowed through
    b.before_call()
    with pytest.raises(AkCircuitOpenException):
        b.before_call()
    b.record_success()
    assert not b.is_open
    b.before_call()  # closed again


def test_breaker_with_retries_integration():
    b = CircuitBreaker(failure_threshold=2, reset_timeout=60.0, name="dead")

    def dying():
        raise ConnectionResetError("peer gone")

    with pytest.raises(ConnectionResetError):
        with_retries(dying, RetryPolicy(max_attempts=2, base_delay=0.001),
                     breaker=b, sleep=lambda s: None)
    assert b.is_open
    # subsequent calls fail fast without touching the endpoint
    calls = {"n": 0}

    def counted():
        calls["n"] += 1

    with pytest.raises(AkCircuitOpenException):
        with_retries(counted, RetryPolicy(max_attempts=2, base_delay=0.001),
                     breaker=b, sleep=lambda s: None)
    assert calls["n"] == 0


def test_breaker_ignores_non_retryable_failures():
    """Deterministic user errors ('table not found') are not a service-
    health signal: they must never open a shared endpoint breaker."""
    b = CircuitBreaker(failure_threshold=2, reset_timeout=60.0, name="svc")

    def user_error():
        raise AkIllegalArgumentException("no such table")

    for _ in range(5):
        with pytest.raises(AkIllegalArgumentException):
            with_retries(user_error,
                         RetryPolicy(max_attempts=3, base_delay=0.001),
                         breaker=b, sleep=lambda s: None)
    assert not b.is_open


def test_breaker_registry_shared_per_endpoint():
    a = CircuitBreaker.for_endpoint("svc:1")
    b = CircuitBreaker.for_endpoint("svc:1")
    c = CircuitBreaker.for_endpoint("svc:2")
    assert a is b and a is not c


def test_failed_nonretryable_probe_does_not_brick_breaker():
    """Regression: a half-open probe that fails with a *non-retryable*
    error must release the probe slot — the breaker stays open but the
    next caller past the reset window can probe again (and a healthy
    probe closes it)."""
    t = {"now": 0.0}
    b = CircuitBreaker(failure_threshold=1, reset_timeout=10.0,
                       name="svc", clock=lambda: t["now"])
    with pytest.raises(ConnectionResetError):
        with_retries(lambda: (_ for _ in ()).throw(ConnectionResetError()),
                     RetryPolicy(max_attempts=1), breaker=b,
                     sleep=lambda s: None)
    assert b.is_open
    t["now"] = 11.0
    # probe window: the probe hits a user error (fatal, not health signal)
    with pytest.raises(AkIllegalArgumentException):
        with_retries(lambda: (_ for _ in ()).throw(
            AkIllegalArgumentException("bad table")),
            RetryPolicy(max_attempts=3), breaker=b, sleep=lambda s: None)
    assert b.is_open  # still open...
    b.before_call()   # ...but the probe slot is free again, not bricked
    b.record_success()
    assert not b.is_open


# -- fault spec --------------------------------------------------------------


@pytest.mark.faults
class TestFaultSpec:
    def test_parse_and_count_semantics(self):
        spec = faults.FaultSpec.parse("io:count=2", seed=0)
        fired = 0
        for _ in range(5):
            try:
                spec.fire("io")
            except faults.InjectedFaultError:
                fired += 1
        assert fired == 2  # exactly the first two calls

    def test_rate_is_seed_deterministic(self):
        def pattern(seed):
            spec = faults.FaultSpec.parse("unit:rate=0.5", seed=seed)
            out = []
            for _ in range(32):
                try:
                    spec.fire("unit")
                    out.append(0)
                except faults.InjectedFaultError:
                    out.append(1)
            return out

        assert pattern(7) == pattern(7)
        assert pattern(7) != pattern(8)
        assert 4 <= sum(pattern(7)) <= 28  # ~rate, not degenerate

    def test_fatal_kind(self):
        spec = faults.FaultSpec.parse("unit:count=1,kinds=fatal")
        with pytest.raises(faults.InjectedFatalError):
            spec.fire("unit")
        spec.fire("unit")  # count exhausted: passes

    def test_unknown_point_is_noop(self):
        spec = faults.FaultSpec.parse("io:count=99")
        spec.fire("unit")  # no rule for 'unit'

    def test_parse_errors(self):
        from alink_tpu.common.exceptions import AkParseErrorException

        for bad in ("nocolon", "io:rate=x", "io:kinds=weird", "io:rate0.3"):
            with pytest.raises(AkParseErrorException):
                faults.FaultSpec.parse(bad)

    def test_env_spec_activation(self, monkeypatch):
        monkeypatch.setenv("ALINK_FAULT_SPEC", "io:count=1")
        monkeypatch.setenv("ALINK_FAULT_SEED", "3")
        faults.clear()  # drop cache built under previous env
        with pytest.raises(faults.InjectedFaultError):
            faults.maybe_fail("io")
        faults.maybe_fail("io")  # count exhausted
        monkeypatch.delenv("ALINK_FAULT_SPEC")
        faults.clear()
        faults.maybe_fail("io")  # no spec: no-op


# -- executor under fault ----------------------------------------------------


def _branchy_job(n=64, seed=0):
    """A 2-branch + 3-node-fused-chain DAG; returns (roots dict, collect fn)."""
    rng = np.random.RandomState(seed)
    src = TableSourceBatchOp(MTable({"x": rng.rand(n)}))
    a = src.apply_func(
        lambda t: MTable({"a": np.sort(np.asarray(t.col("x")))}),
        out_schema="a double")
    b = src.apply_func(
        lambda t: MTable({"b": np.asarray(t.col("x")) * 3.0 + 1.0}),
        out_schema="b double")
    return src, a, b


@pytest.mark.faults
def test_dag_parity_under_deterministic_unit_faults(monkeypatch):
    """The first 3 unit attempts fail (wherever scheduling lands them):
    retries absorb every fault and output is bit-identical to the
    fault-free run."""
    monkeypatch.setenv("ALINK_RETRY_MAX_ATTEMPTS", "8")
    src, a, b = _branchy_job(seed=1)
    clean_a = np.asarray(a.collect().col("a"))
    clean_b = np.asarray(b.collect().col("b"))

    injected = _counter_delta("faults.injected.unit")
    retried = _counter_delta("resilience.retries")
    faults.install(faults.FaultSpec.parse("unit:count=3,kinds=transient"))
    src2, a2, b2 = _branchy_job(seed=1)
    got = {}
    a2.lazy_collect(lambda t: got.setdefault("a", np.asarray(t.col("a"))))
    b2.lazy_collect(lambda t: got.setdefault("b", np.asarray(t.col("b"))))
    src2.execute()
    faults.clear()

    np.testing.assert_array_equal(got["a"], clean_a)
    np.testing.assert_array_equal(got["b"], clean_b)
    assert injected() == 3
    assert retried() >= 3


@pytest.mark.faults
def test_dag_parity_under_30pct_seeded_fault_rate(monkeypatch):
    """The acceptance-criteria configuration: seeded 30% transient unit
    fault rate over a multi-branch DAG completes and matches the
    fault-free output bit-for-bit. (With a widened attempt budget the
    chance of a seeded schedule exhausting retries is ~0.3^8.)"""
    monkeypatch.setenv("ALINK_RETRY_MAX_ATTEMPTS", "8")
    src, a, b = _branchy_job(seed=6)
    clean_a = np.asarray(a.collect().col("a"))
    clean_b = np.asarray(b.collect().col("b"))

    faults.install(faults.FaultSpec.parse("unit:rate=0.3", seed=11))
    src2, a2, b2 = _branchy_job(seed=6)
    got = {}
    a2.lazy_collect(lambda t: got.setdefault("a", np.asarray(t.col("a"))))
    b2.lazy_collect(lambda t: got.setdefault("b", np.asarray(t.col("b"))))
    src2.execute()
    faults.clear()

    np.testing.assert_array_equal(got["a"], clean_a)
    np.testing.assert_array_equal(got["b"], clean_b)


@pytest.mark.faults
def test_fatal_fault_propagates_unchanged_and_dag_recollectable():
    src, a, b = _branchy_job(seed=2)
    faults.install(faults.FaultSpec.parse("unit:count=1,kinds=fatal"))
    with pytest.raises(faults.InjectedFatalError):
        a.collect()
    faults.clear()
    # the DAG is re-collectable after the failure: both branches finish
    assert a.collect().num_rows == 64
    assert b.collect().num_rows == 64


def _affine_chain(t):
    """src -> 3 fusable kernel-mapper ops (same shape as the executor
    fusion tests)."""
    from alink_tpu.common.mtable import AlinkTypes
    from alink_tpu.mapper.base import BlockKernelMapper
    from alink_tpu.operator.batch.utils import MapBatchOp

    def affine_op(col, out, mul, add):
        class _M(BlockKernelMapper):
            def kernel(self, schema):
                def fn(X):
                    return X * np.float32(mul) + np.float32(add)

                return ([col], [out], [AlinkTypes.DOUBLE], fn)

        class _Op(MapBatchOp):
            mapper_cls = _M

        _Op.__name__ = f"Affine_{out}"
        return _Op()

    src = TableSourceBatchOp(t)
    c1 = affine_op("x", "x1", 2.0, 1.0).link_from(src)
    c2 = affine_op("x1", "x2", 0.5, -3.0).link_from(c1)
    c3 = affine_op("x2", "x3", 4.0, 0.25).link_from(c2)
    return c1, c2, c3


@pytest.mark.faults
def test_fused_chain_defuses_and_succeeds_node_by_node():
    """A fused chain whose attempt fails defuses — re-runs node-by-node
    (intermediates materialize) within the same attempt — and the output
    matches the clean fused run bit-for-bit."""
    from alink_tpu.common.executor import (_collect_pending, _plan_units,
                                           _run_unit)

    rng = np.random.RandomState(9)
    t = MTable({"x": rng.rand(64)})
    _, _, clean_tail = _affine_chain(t)
    clean = clean_tail.collect()

    defused = _counter_delta("resilience.defused")
    retried = _counter_delta("resilience.unit_retries")
    c1, c2, tail = _affine_chain(t)
    units = _plan_units(_collect_pending([tail]), [tail])
    fused_units = [u for u in units if u.fused]
    assert len(fused_units) == 1 and len(fused_units[0].ops) == 3
    # fail exactly the fused unit's first attempt
    faults.install(faults.FaultSpec.parse("unit:count=1"))
    _run_unit(fused_units[0])
    faults.clear()

    assert defused() == 1
    assert retried() == 0  # defusion happened within the first attempt
    # defused execution materializes the intermediates
    assert c1._executed and c2._executed and tail._executed
    fused = tail._evaluate()
    assert fused.schema == clean.schema
    for col in fused.names:
        np.testing.assert_array_equal(fused.col(col), clean.col(col))


@pytest.mark.faults
def test_persistent_fatal_fault_not_absorbed_by_defusion():
    """A fatal fault that keeps firing must propagate from a fused chain
    too: defusion re-runs through the injection tap, it does not bypass
    it."""
    rng = np.random.RandomState(10)
    _, _, tail = _affine_chain(MTable({"x": rng.rand(32)}))
    faults.install(faults.FaultSpec.parse("unit:rate=1.0,kinds=fatal"))
    with pytest.raises(faults.InjectedFatalError):
        tail.collect()
    faults.clear()
    assert not tail._executed
    assert tail.collect().num_rows == 32  # re-collectable after clear


def test_retries_off_restores_fail_fast_in_executor(monkeypatch):
    monkeypatch.setenv("ALINK_RETRIES", "off")
    faults.install(faults.FaultSpec.parse("unit:count=1"))  # transient
    src, a, b = _branchy_job(seed=3)
    with pytest.raises(faults.InjectedFaultError):
        a.collect()
    faults.clear()


def test_dag_pool_failure_degrades_to_serial():
    from alink_tpu.common.env import MLEnvironmentFactory

    degraded = _counter_delta("resilience.degraded_serial")
    env = MLEnvironmentFactory.get_default()
    env.dag_pool.shutdown(wait=True)  # simulate pool death mid-session
    try:
        src, a, b = _branchy_job(seed=4)
        got = {}
        b.lazy_collect(lambda t: got.setdefault("b", t))
        got_a = a.collect()
        assert got_a.num_rows == 64
        assert b._executed and got["b"].num_rows == 64  # whole DAG ran
        assert degraded() >= 1
    finally:
        env.close()  # drop the dead pool so later tests get a fresh one


# -- streaming transfer under fault ------------------------------------------


@pytest.mark.faults
def test_stream_map_parity_under_transfer_faults():
    import jax.numpy as jnp

    from alink_tpu.common.streaming import iter_row_chunks, stream_map

    X = np.arange(400, dtype=np.float32).reshape(100, 4)

    def run():
        return [np.asarray(r) for _, r in stream_map(
            lambda a: jnp.sum(a, axis=1), iter_row_chunks([X], 32))]

    clean = run()
    faults.install(faults.FaultSpec.parse("transfer:count=2"))
    faulty = run()
    faults.clear()
    assert len(clean) == len(faulty)
    for cv, fv in zip(clean, faulty):
        np.testing.assert_array_equal(cv, fv)


# -- connector round trips under fault ---------------------------------------


def _kafka_round_trip(name, n=40):
    from alink_tpu.io.kafka import MemoryKafkaBroker
    from alink_tpu.operator.stream import (KafkaSinkStreamOp,
                                           KafkaSourceStreamOp,
                                           TableSourceStreamOp)

    t = MTable.from_rows([(i, f"s{i}") for i in range(n)],
                         "k long, s string")
    sink = KafkaSinkStreamOp(
        bootstrapServers=f"memory://{name}", topic="t",
    ).link_from(TableSourceStreamOp(t, chunkSize=8))
    for _ in sink._stream():
        pass
    out = []
    src = KafkaSourceStreamOp(
        bootstrapServers=f"memory://{name}", topic="t",
        schemaStr="k long, s string", maxMessages=n, idleTimeoutMs=200)
    for chunk in src._stream():
        out.extend(chunk.rows())
    return out


@pytest.mark.faults
def test_kafka_round_trip_parity_under_io_faults():
    clean = _kafka_round_trip("res-clean")
    injected = _counter_delta("faults.injected.io")
    # count=2: both faults land on one call's first two attempts at worst,
    # still inside the default 3-attempt budget — deterministic absorb
    faults.install(faults.FaultSpec.parse("io:count=2", seed=5))
    faulty = _kafka_round_trip("res-faulty")
    faults.clear()
    assert clean == faulty
    assert injected() == 2


@pytest.mark.faults
def test_datahub_round_trip_parity_under_io_faults():
    from alink_tpu.io.datahub import MemoryDatahubService
    from alink_tpu.operator.stream import (DatahubSinkStreamOp,
                                           DatahubSourceStreamOp,
                                           TableSourceStreamOp)

    def round_trip(name):
        t = MTable.from_rows([(i, float(i)) for i in range(30)],
                             "k long, v double")
        MemoryDatahubService.named(name)
        sink = DatahubSinkStreamOp(
            endpoint=f"memory://{name}", topic="t",
        ).link_from(TableSourceStreamOp(t, chunkSize=10))
        for _ in sink._stream():
            pass
        out = []
        src = DatahubSourceStreamOp(
            endpoint=f"memory://{name}", topic="t",
            schemaStr="k long, v double", maxMessages=30, idleTimeoutMs=200)
        for chunk in src._stream():
            out.extend(chunk.rows())
        return out

    clean = round_trip("dh-res-clean")
    faults.install(faults.FaultSpec.parse("io:count=2", seed=5))
    faulty = round_trip("dh-res-faulty")
    faults.clear()
    assert clean == faulty


def test_datahub_wire_poll_keeps_fetched_rows_across_shard_failure():
    """Regression: with multiple shards, rows fetched from earlier shards
    (whose cursors already advanced) must survive a later shard's failure
    and be delivered on the retried poll — no silent message loss."""
    from alink_tpu.io.datahub import _WireDatahubConsumer

    class Res:
        def __init__(self, rows, nxt):
            self.records = [type("R", (), {"values": list(r)})()
                            for r in rows]
            self.record_count = len(rows)
            self.next_cursor = nxt

    class FakeDh:
        def __init__(self):
            self.s2_fails = 3  # exhausts the inner per-shard retry budget

        def get_tuple_records(self, project, topic, sid, schema, cursor,
                              limit):
            if sid == "s1":
                return Res([(1,), (2,)], cursor + 2) if cursor == 0 \
                    else Res([], cursor)
            if self.s2_fails > 0:
                self.s2_fails -= 1
                raise ConnectionResetError("shard gone")
            return Res([(3,)], cursor + 1) if cursor == 0 else Res([], cursor)

    c = _WireDatahubConsumer.__new__(_WireDatahubConsumer)
    c._dh = FakeDh()
    c._project, c._topic = "p", "t"
    c._shards = ["s1", "s2"]
    c._cursors = {"s1": 0, "s2": 0}
    c._schema = None
    c._carry = []
    with pytest.raises(ConnectionResetError):
        c.poll_batch(8, 100)  # s1 rows fetched, s2 exhausts inner retries
    out = c.poll_batch(8, 100)  # retried poll: carried rows + s2's rows
    assert out == [(1,), (2,), (3,)]


def test_outer_poll_does_not_retry_against_open_breaker():
    """Once the endpoint's breaker is open (inner retry layer gave up),
    the outer poll loop must propagate immediately, not burn its own
    backoff budget re-hitting the open circuit."""
    from alink_tpu.operator.stream.connectors import _bounded_poll

    calls = {"n": 0}

    class Consumer:
        def poll_batch(self, n, t):
            calls["n"] += 1
            raise AkCircuitOpenException("endpoint open")

        def close(self):
            pass

    with pytest.raises(AkCircuitOpenException):
        list(_bounded_poll(Consumer(), lambda p: p, 8, 0, 200))
    assert calls["n"] == 1


def test_odps_read_retries_transient_reader_failure():
    from alink_tpu.io.odps import OdpsCatalog
    from tests.test_odps_datahub import (FakeColumn, FakeOdpsClient,
                                         FakeOdpsTable, FakeReader)

    class FlakyTable(FakeOdpsTable):
        def __init__(self, columns, rows, fail_times):
            super().__init__(columns, rows)
            self.fail_times = fail_times

        def open_reader(self):
            if self.fail_times > 0:
                self.fail_times -= 1
                raise ConnectionResetError("odps tunnel dropped")
            return FakeReader(self.rows)

    client = FakeOdpsClient()
    client.tables["t"] = FlakyTable(
        [FakeColumn("a", "bigint")], [(1,), (2,)], fail_times=2)
    retried = _counter_delta("resilience.io_retries")
    cat = OdpsCatalog(client=client)
    out = cat.read_table("t")
    assert list(out.col("a")) == [1, 2]
    assert retried() == 2


def test_odps_fatal_error_does_not_retry():
    from alink_tpu.io.odps import OdpsCatalog
    from tests.test_odps_datahub import FakeOdpsClient

    class CountingClient(FakeOdpsClient):
        def __init__(self):
            super().__init__()
            self.calls = 0

        def get_table(self, name):
            self.calls += 1
            raise KeyError(name)  # fatal: not classified transient

    client = CountingClient()
    cat = OdpsCatalog(client=client)
    with pytest.raises(KeyError):
        cat.get_table_schema("missing")
    assert client.calls == 1


def test_hbase_mget_retries_thrift_timeout():
    import socket

    from alink_tpu.io.hbase import HBaseClient

    class FlakyTable:
        def __init__(self):
            self.fails = 1

        def rows(self, keys, columns=None):
            if self.fails > 0:
                self.fails -= 1
                raise socket.timeout("thrift gateway timeout")
            return [(k, {b"cf:v": b"1"}) for k in keys]

    class Conn:
        def __init__(self):
            self._t = FlakyTable()

        def table(self, name):
            return self._t

    c = HBaseClient(connection=Conn())
    out = c.get_rows("t", ["r1", "r2"], "cf")
    assert out == [{"v": b"1"}, {"v": b"1"}]


def test_hbase_breaker_opens_on_dead_gateway(monkeypatch):
    monkeypatch.setenv("ALINK_RETRY_MAX_ATTEMPTS", "2")
    from alink_tpu.io.hbase import HBaseClient

    class DeadConn:
        def table(self, name):
            raise ConnectionRefusedError("gateway down")

    c = HBaseClient(connection=DeadConn())
    # breaker threshold is 5 consecutive failures: 3 calls x 2 attempts
    for _ in range(3):
        with pytest.raises((ConnectionRefusedError, AkCircuitOpenException)):
            c.get_row("t", "k")
    with pytest.raises(AkCircuitOpenException):
        c.get_row("t", "k")


# -- dead-letter ingest ------------------------------------------------------


def _poisoned_kafka_source(name, monkeypatch=None):
    from alink_tpu.io.kafka import MemoryKafkaBroker
    from alink_tpu.operator.stream import KafkaSourceStreamOp

    broker = MemoryKafkaBroker.named(name)
    broker.produce("t", json.dumps({"k": 1, "v": 1.5}).encode())
    broker.produce("t", b"{not json at all")
    broker.produce("t", json.dumps({"k": 2, "v": 2.5}).encode())
    return KafkaSourceStreamOp(
        bootstrapServers=f"memory://{name}", topic="t",
        schemaStr="k long, v double", maxMessages=3, idleTimeoutMs=200)


def test_malformed_row_aborts_without_dead_letter_knob(monkeypatch):
    monkeypatch.delenv("ALINK_DEAD_LETTER", raising=False)
    src = _poisoned_kafka_source("dlq-off")
    with pytest.raises(Exception):
        for _ in src._stream():
            pass


def test_malformed_row_dead_letters_under_knob(monkeypatch):
    monkeypatch.setenv("ALINK_DEAD_LETTER", "on")
    dropped = _counter_delta("resilience.dead_letter")
    src = _poisoned_kafka_source("dlq-on")
    rows = []
    for chunk in src._stream():
        rows.extend(chunk.rows())
    assert [r[0] for r in rows] == [1, 2]  # good rows survived, in order
    assert dropped() == 1
    recs = dead_letters.records()
    assert recs and "not json" in recs[-1]["payload"]
    assert recs[-1]["source"] == "kafka.decode"


def test_dead_letter_buffer_is_bounded(monkeypatch):
    monkeypatch.setenv("ALINK_DEAD_LETTER_LIMIT", "4")
    for i in range(10):
        dead_letters.add("test", f"row{i}", ValueError("bad"))
    assert len(dead_letters) == 4
    assert dead_letters.records()[0]["payload"] == "'row6'"  # oldest evicted
    drained = dead_letters.drain()
    assert len(drained) == 4 and len(dead_letters) == 0


# -- metrics satellites ------------------------------------------------------


def test_metrics_counters_and_summary():
    metrics.incr("resilience.test_counter", 2)
    metrics.incr("resilience.test_counter")
    assert metrics.counter("resilience.test_counter") >= 3
    assert "resilience.test_counter" in metrics.counters("resilience.")
    assert "resilience.test_counter" in metrics.summary()
    s = resilience_summary()
    assert "dead_letter_buffered" in s


def test_profile_trace_failure_counted_not_swallowed(monkeypatch):
    import jax

    dropped = _counter_delta("metrics.dropped")

    def boom(*a, **k):
        raise RuntimeError("profiler unavailable")

    monkeypatch.setattr(jax.profiler, "start_trace", boom)
    from alink_tpu.common.metrics import profile_trace

    with profile_trace("/tmp/nonexistent-trace-dir"):
        pass  # must not raise
    assert dropped() == 1


def test_resilience_exports_at_package_root():
    import alink_tpu

    assert alink_tpu.RetryPolicy is RetryPolicy
    assert alink_tpu.FaultSpec is faults.FaultSpec
    assert alink_tpu.is_retryable is is_retryable
    assert alink_tpu.AkRetryableException is AkRetryableException
    assert alink_tpu.with_retries is with_retries
