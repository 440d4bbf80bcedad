"""What a slow cycle or epoch leaves behind (PR 36): the collector as a span
(``host.gc``), thread-CPU seconds on every span, each training step's finish
on the device's side, the slow-unit record, and why a served batch was cut.

All on the CPU: counts, structure and planted pauses; no time here is a
device's.
"""

import gc
import logging
import statistics
import threading
import time

import numpy as np
import pytest

from alink_tpu.common.metrics import metrics
from alink_tpu.common.tracing import (job_report, slow_against, trace_span,
                                      tracer)

pytestmark = pytest.mark.observability


@pytest.fixture(autouse=True)
def tracing_on(monkeypatch):
    monkeypatch.setenv("ALINK_TRACING", "on")


def collector_records():
    return tracer.spans("host.gc")


def hist_count(name):
    h = metrics.histogram(name)
    return (h["count"], h["sum"]) if h else (0, 0.0)


# -- host.gc ------------------------------------------------------------------

def test_a_forced_collection_is_a_record_inside_the_open_span():
    n0, s0 = hist_count("span.host.gc_s")
    full0 = metrics.counter("host.gc_collections.gen2")
    seen = {s["span_id"] for s in collector_records()}
    with trace_span("stall.outer") as sp:
        gc.collect()
    outer = next(s for s in reversed(tracer.spans()) if s["span_id"] == sp.span_id)
    new = [s for s in collector_records() if s["span_id"] not in seen
           and s["parent_id"] == sp.span_id]
    assert len(new) == 1
    rec = new[0]
    assert rec["name"] == "host.gc" and rec["attrs"]["generation"] == 2
    assert rec["thread"] == threading.current_thread().name
    assert rec["start_perf"] >= outer["start_perf"]
    assert rec["start_perf"] + rec["wall_s"] \
        <= outer["start_perf"] + outer["wall_s"] + 1e-6
    # the collection is the span's child: its seconds are not the span's own
    assert outer["self_s"] <= outer["wall_s"] - rec["wall_s"] + 1e-5
    n1, s1 = hist_count("span.host.gc_s")
    assert n1 > n0 and s1 - s0 >= rec["wall_s"] - 1e-6
    assert metrics.counter("host.gc_collections.gen2") == full0 + 1
    # a job's own list and report do not hold the collector's records
    assert all(s["name"] != "host.gc" for s in tracer.spans())


def test_a_reader_of_the_registry_drains_the_collector_without_a_span():
    full0 = metrics.counter("host.gc_collections.gen2")
    n0 = len(collector_records())
    gc.collect()
    assert metrics.counter("host.gc_collections.gen2") == full0 + 1
    assert len(collector_records()) == n0 + 1
    assert collector_records()[-1]["parent_id"] is None


def test_young_collections_are_counted_and_summed_not_recorded():
    gc.collect()
    young0 = metrics.counter("host.gc_collections.gen0")
    n0, s0 = hist_count("span.host.gc_s")
    recs0 = len(collector_records())
    keep = [[i] for i in range(20_000)]     # containers: trips the threshold
    young = metrics.counter("host.gc_collections.gen0") - young0
    assert young >= 5
    n1, s1 = hist_count("span.host.gc_s")
    assert n1 - n0 >= young and s1 > s0
    # none of them a millisecond long: no record each (a full one may fall)
    assert len(collector_records()) - recs0 <= 3
    del keep


@pytest.mark.parametrize("lock", ["registry_data", "registry_counters", "ring"])
def test_a_collection_under_a_held_lock_does_not_deadlock(lock):
    held = {"registry_data": metrics._data_lock,
            "registry_counters": metrics._counter_lock,
            "ring": tracer._lock}[lock]
    done = threading.Event()

    def collect_under_the_lock():
        with held:      # where an allocation may trip the collector
            gc.collect()
            _ = [[i] for i in range(5_000)]
        done.set()

    t = threading.Thread(target=collect_under_the_lock, daemon=True)
    t.start()
    assert done.wait(10), "the collector's callback waited for a lock"
    t.join(10)
    metrics.counter("host.gc_collections.gen2")     # and the drain still runs


# -- thread-CPU seconds ---------------------------------------------------------

@pytest.mark.parametrize("how", ["sleep", "spin"])
def test_cpu_seconds_tell_waiting_from_working(how):
    c0, _ = hist_count(f"cpu.stall.{how}_s")
    with trace_span(f"stall.{how}") as sp:
        if how == "sleep":
            time.sleep(0.1)
        else:
            t_end = time.thread_time() + 0.1
            while time.thread_time() < t_end:
                pass
    rec = next(s for s in reversed(tracer.spans()) if s["span_id"] == sp.span_id)
    assert rec["wall_s"] >= 0.099
    if how == "sleep":
        assert rec["cpu_s"] < 0.02
    else:
        assert rec["cpu_s"] >= 0.099 and rec["cpu_s"] <= rec["wall_s"] + 1e-3
    assert rec["cpu_s"] == pytest.approx(sp.cpu_s, abs=1e-6)
    c1, s1 = hist_count(f"cpu.stall.{how}_s")
    assert c1 == c0 + 1


def test_cpu_seconds_reach_their_histogram_when_the_registry_is_read():
    """A span's finish sums its CPU seconds by name and observes nothing; a
    reader of the registry finds them as so many entries, the sum exact."""
    c0, s0 = hist_count("cpu.stall.summed_s")
    spent = []
    for ms in (1, 3, 5):
        with trace_span("stall.summed") as sp:
            t_end = time.thread_time() + ms / 1000
            while time.thread_time() < t_end:
                pass
        spent.append(sp.cpu_s)
    assert "stall.summed" in tracer._cpu
    c1, s1 = hist_count("cpu.stall.summed_s")
    assert c1 - c0 == 3 and s1 - s0 == pytest.approx(sum(spent), abs=2e-6)
    assert "stall.summed" not in tracer._cpu


def test_a_childs_cpu_seconds_are_not_its_parents_own():
    with trace_span("stall.parent") as parent:
        with trace_span("stall.child"):
            t_end = time.thread_time() + 0.05
            while time.thread_time() < t_end:
                pass
    assert parent.cpu_s >= 0.05
    assert parent.self_cpu_s < 0.02


# -- the device's side of a training step --------------------------------------

def toy_fit(accum_steps=1, accum_mode="micro", epochs=3, log_every=0):
    import flax.linen as nn

    from alink_tpu.dl.train import TrainConfig, train_model

    class Toy(nn.Module):
        @nn.compact
        def __call__(self, x, deterministic=True):
            return nn.Dense(2)(x)

    rng = np.random.default_rng(0)
    x = rng.normal(size=(64, 4)).astype(np.float32)
    y = (x[:, 0] > 0).astype(np.int32)
    cfg = TrainConfig(num_epochs=epochs, batch_size=16, eval_ratio=0.0, seed=3,
                      accum_steps=accum_steps, accum_mode=accum_mode,
                      log_every=log_every)
    return train_model(Toy(), {"x": x}, y, cfg, seq_axis=None)


@pytest.mark.parametrize("accum_steps,accum_mode",
                         [(1, "micro"), (2, "micro"), (2, "fused")])
def test_a_fit_reads_every_step_but_its_first_on_the_devices_side(
        accum_steps, accum_mode):
    tracer.clear()
    n0, _ = hist_count("train.device_step_s")
    steps0 = metrics.counter("train.steps")
    toy_fit(accum_steps, accum_mode)
    steps = metrics.counter("train.steps") - steps0
    assert steps == 12
    n1, total = hist_count("train.device_step_s")
    assert n1 - n0 == steps - 1
    epochs = [s for s in tracer.spans() if s["name"] == "train.epoch"]
    assert [s["attrs"]["steps"] for s in epochs] == [4, 4, 4]
    for k, s in enumerate(epochs):
        lo = 1 if k == 0 else 4 * k     # the call's first step has no reading
        assert lo <= s["attrs"]["slowest_step"] < 4 * (k + 1)
        assert 0 < s["attrs"]["slowest_step_s"] <= s["wall_s"]
    assert not [t for t in threading.enumerate()
                if t.name == "alink-train-watch"]


def test_the_device_step_histogram_resolves_a_step_to_five_percent():
    from alink_tpu.dl.train import _DEVICE_STEP_BUCKETS

    edges = _DEVICE_STEP_BUCKETS
    assert edges[0] == 1e-3 and edges[-1] >= 10.0
    assert max(b / a for a, b in zip(edges, edges[1:])) <= 1.1 + 1e-9


def test_a_fit_that_fails_leaves_no_watcher_behind():
    from alink_tpu.dl.train import TrainConfig, train_model

    class Broken:
        def apply(self, *a, **kw):
            raise RuntimeError("planted")

        def __repr__(self):
            return "Broken()"

    x = np.zeros((8, 2), np.float32)
    with pytest.raises(Exception):
        train_model(Broken(), {"x": x}, np.zeros(8, np.int32),
                    TrainConfig(num_epochs=1, batch_size=8, eval_ratio=0.0),
                    seq_axis=None, init_params={"params": {}})
    assert not [t for t in threading.enumerate()
                if t.name == "alink-train-watch"]


def test_a_watcher_that_cannot_read_a_step_still_answers_drain(monkeypatch):
    """Whatever fails inside the watcher is counted and the thread lives on:
    an instrument never hangs the fit at ``drain``."""
    from alink_tpu.dl import train

    errors0 = metrics.counter("train.watch_errors")
    monkeypatch.setattr(train, "slow_against", lambda *a: 1 / 0)
    toy_fit(epochs=2)      # returns: the watcher answered both drains
    # every step but the call's first is held against the rule
    assert metrics.counter("train.watch_errors") - errors0 == 7
    assert not [t for t in threading.enumerate()
                if t.name == "alink-train-watch"]


def test_where_the_loop_does_not_wait_at_an_epochs_end_neither_does_the_watcher(
        monkeypatch):
    from alink_tpu.dl import train

    waited = []
    real = train._StepWatch.drain

    def drain(self, span, wait=True):
        waited.append(wait)
        return real(self, span, wait)

    monkeypatch.setattr(train._StepWatch, "drain", drain)
    toy_fit(epochs=2)
    assert waited == [True, True]
    del waited[:]
    n0, _ = hist_count("train.device_step_s")
    toy_fit(epochs=2, log_every=3)
    assert waited == [False, False]
    # no step is lost for it: close still waits for the last
    assert hist_count("train.device_step_s")[0] - n0 == 7


# -- the slow-unit record -----------------------------------------------------

@pytest.mark.parametrize("earlier,value,slow", [
    ([3.84] * 6, 4.13, True),        # a Moonlight epoch of +0.29 s
    ([7.45] * 5, 8.35, True),        # a generator cycle of +0.9 s
    ([0.32] * 20, 0.44, True),       # a served cycle late in a window
    ([0.32] * 20, 0.335, False),     # jitter: under 5%
    ([0.010] * 20, 0.025, False),    # over 5% and under 20 ms
    ([0.32] * 3, 2.0, False),        # fewer than four earlier
])
def test_the_rule_for_slow(earlier, value, slow):
    usual = slow_against(earlier, value)
    assert (usual is not None) == slow
    if slow:
        assert usual == statistics.median(earlier)


def toy_unit(i, apply_s=0.01, collect=False):
    with trace_span("serving.batch", unit="toy", model="toy", cycle=i):
        with trace_span("toy.tokenize"):
            t_end = time.thread_time() + 0.005
            while time.thread_time() < t_end:
                pass
        with trace_span("toy.apply"):
            time.sleep(apply_s)
        if collect:
            heap = [[k] for k in range(400_000)]
            with trace_span("toy.collect"):
                gc.collect()
            del heap


def test_planted_pauses_are_recorded_and_logged_and_ordinary_units_are_not(caplog):
    tracer.clear()
    slow0 = metrics.counter("slow.serving.batch")
    n0, s0 = hist_count("slow.serving.batch_excess_s")
    sleep = 0.25
    with caplog.at_level(logging.WARNING, logger="alink_tpu.tracing"):
        for i in range(8):
            toy_unit(i, apply_s=0.01 + (sleep if i == 5 else 0.0),
                     collect=(i == 7))
    units = [s for s in tracer.spans() if s["name"] == "serving.batch"]
    walls = [s["wall_s"] for s in units]
    # the rule, applied plainly to the walls the ring holds
    expected = [i for i in range(len(walls))
                if slow_against(walls[max(0, i - 32):i], walls[i]) is not None]
    assert 5 in expected and 7 in expected
    assert set(expected) <= {4, 5, 6, 7}
    assert metrics.counter("slow.serving.batch") - slow0 == len(expected)
    slow = tracer.slow_units()
    assert [r["start_perf"] for r in slow] \
        == [units[i]["start_perf"] for i in expected]
    by_cycle = {r["start_perf"]: r for r in slow}
    sixth = by_cycle[units[5]["start_perf"]]
    assert sixth["unit"] == "serving.batch" and sixth["key"] == "toy"
    assert sixth["excess_s"] == pytest.approx(sleep, rel=0.10)
    assert sixth["excess_s"] == pytest.approx(sixth["wall_s"] - sixth["usual_s"],
                                              abs=1e-5)
    # the child that grew, and that it waited: its CPU seconds did not grow
    grew = {n: row["self_s"] - sixth["usual_by_name"].get(n, {}).get("self_s", 0.0)
            for n, row in sixth["by_name"].items()}
    assert max(grew, key=grew.get) == "toy.apply"
    assert grew["toy.apply"] == pytest.approx(sleep, rel=0.10)
    assert sixth["by_name"]["toy.apply"]["cpu_s"] < 0.02
    assert "toy.apply +0.2" in sixth["line"]
    assert sixth["line"].startswith("serving.batch key=toy ")
    # the collector's seconds inside the eighth, with generation and thread
    eighth = by_cycle[units[7]["start_perf"]]
    full = [c for c in eighth["collections"] if c["generation"] == 2]
    assert full and full[0]["thread"] == threading.current_thread().name
    assert eighth["by_name"]["host.gc"]["wall_s"] >= full[0]["wall_s"] - 1e-6
    assert "host.gc gen2" in eighth["line"]
    n1, s1 = hist_count("slow.serving.batch_excess_s")
    assert n1 - n0 == len(expected)
    assert s1 - s0 == pytest.approx(sum(r["excess_s"] for r in slow), abs=1e-4)
    # one line a second at most (the eight units take about half a second
    # on a quiet machine: one line)
    lines = [r.getMessage() for r in caplog.records
             if r.name == "alink_tpu.tracing"]
    due, last = [], float("-inf")
    for r in slow:
        if r["start_perf"] + r["wall_s"] - last >= 1.0:
            due.append(r["line"])
            last = r["start_perf"] + r["wall_s"]
    assert lines == due and lines[0] == slow[0]["line"]
    assert job_report()["slow_units"] == slow


def test_ordinary_units_yield_no_record():
    tracer.clear()
    slow0 = metrics.counter("slow.serving.batch")
    for i in range(12):
        with trace_span("serving.batch", unit="even", model="even"):
            with trace_span("toy.apply"):
                time.sleep(0.002)
    assert metrics.counter("slow.serving.batch") == slow0
    assert tracer.slow_units() == []


def test_only_a_slow_unit_walks_the_ring(monkeypatch):
    """An ordinary unit pays a median of its key's last walls and no more:
    the ring is walked for a slow unit alone, for itself and for the last
    ordinary unit, whose records are still there."""
    tracer.clear()
    walked = []
    real = type(tracer)._met_locked

    def met_locked(self, unit):
        walked.append(unit["attrs"]["cycle"])
        return real(self, unit)

    monkeypatch.setattr(type(tracer), "_met_locked", met_locked)
    for i in range(7):
        toy_unit(i, apply_s=0.002)
    assert walked == []
    toy_unit(7, apply_s=0.06)
    assert walked == [7, 6]
    rec = tracer.slow_units()[-1]
    assert rec["usual_by_name"]["toy.apply"]["wall_s"] == pytest.approx(0.002, abs=0.002)
    assert rec["by_name"]["toy.apply"]["wall_s"] == pytest.approx(0.06, abs=0.01)
    # a second slow unit is still held against the last ordinary one
    toy_unit(8, apply_s=0.06)
    assert walked == [7, 6, 8, 6]


def test_an_ordinary_unit_that_left_the_ring_leaves_an_empty_usual(monkeypatch):
    monkeypatch.setenv("ALINK_TRACE_RING", "16")
    tracer.clear()
    try:
        for i in range(5):
            toy_unit(i, apply_s=0.002)
        for i in range(20):
            with trace_span("toy.filler"):
                pass
        toy_unit(5, apply_s=0.06)
        rec = tracer.slow_units()[-1]
        # the unit's own record is kept, what met it has rolled out
        assert set(rec["usual_by_name"]) == {"serving.batch"}
        assert "toy.apply +0.0" in rec["line"]
    finally:
        monkeypatch.delenv("ALINK_TRACE_RING")
        tracer.clear()


def test_units_are_compared_under_their_own_key_and_clear_forgets_them():
    tracer.clear()
    for i in range(6):
        with trace_span("serving.batch", unit="quick", model="quick"):
            pass
    slow0 = metrics.counter("slow.serving.batch")
    with trace_span("serving.batch", unit="long", model="long"):
        time.sleep(0.05)        # the first of its key: nothing to lie over
    assert metrics.counter("slow.serving.batch") == slow0
    with trace_span("serving.batch", unit="quick", model="quick"):
        time.sleep(0.05)
    assert metrics.counter("slow.serving.batch") == slow0 + 1
    assert [r["key"] for r in tracer.slow_units()] == ["quick"]
    tracer.clear()
    assert tracer.slow_units() == []
    with trace_span("serving.batch", unit="quick", model="quick"):
        time.sleep(0.05)        # the history went with the ring
    assert metrics.counter("slow.serving.batch") == slow0 + 1


def test_a_units_summary_counts_a_span_of_another_thread_by_its_overlap():
    tracer.clear()
    started, release = threading.Event(), threading.Event()

    def beside():
        with trace_span("toy.beside"):
            started.set()
            release.wait(5)

    for i in range(5):
        with trace_span("serving.batch", unit="overlap", model="overlap"):
            pass
    t = threading.Thread(target=beside)
    t.start()
    started.wait(5)
    time.sleep(0.05)            # half of toy.beside lies before the unit
    with trace_span("serving.batch", unit="overlap", model="overlap"):
        time.sleep(0.05)
        release.set()
        t.join()
    rec = tracer.slow_units()[-1]
    assert rec["by_name"]["toy.beside"]["wall_s"] == pytest.approx(0.05, abs=0.02)


# -- why a batch was cut ----------------------------------------------------------

@pytest.fixture(scope="module")
def served_model():
    from alink_tpu.common import MTable
    from alink_tpu.pipeline import (NaiveBayes, Pipeline, StandardScaler,
                                    VectorAssembler)

    rng = np.random.default_rng(0)
    x = np.concatenate([rng.normal(c, 0.4, size=(40, 2)) for c in (0, 2)])
    t = MTable({"f0": x[:, 0], "f1": x[:, 1]}).with_column(
        "label", np.repeat(["neg", "pos"], 40))
    return Pipeline(
        StandardScaler(selectedCols=["f0", "f1"]),
        VectorAssembler(selectedCols=["f0", "f1"], outputCol="vec"),
        NaiveBayes(vectorCol="vec", labelCol="label", predictionCol="pred"),
    ).fit(t)


@pytest.mark.parametrize("rows,flushed_by", [(8, "full"), (2, "deadline")])
def test_a_batch_says_why_it_was_cut(served_model, rows, flushed_by):
    from alink_tpu.serving import ModelServer, ServingConfig

    srv = ModelServer(ServingConfig(max_batch_rows=8, queue_depth=16,
                                    flush_deadline_s=0.3))
    try:
        srv.load("toy", served_model, "f0 double, f1 double")
        tracer.clear()
        before = {k: metrics.counter("serving.flush_" + k)
                  for k in ("full", "deadline")}
        futs = [srv.submit("toy", (0.1 * i, 0.2)) for i in range(rows)]
        for f in futs:
            f.result(60)
    finally:
        srv.close()
    collects = [s for s in tracer.spans() if s["name"] == "serving.collect"]
    assert [s["attrs"]["flushed_by"] for s in collects] == [flushed_by]
    assert collects[0]["attrs"]["rows"] == rows
    for k, n in before.items():
        assert metrics.counter("serving.flush_" + k) == n + (k == flushed_by)


# -- off is off -------------------------------------------------------------------

def test_off_the_hook_records_nothing_and_no_watcher_starts(monkeypatch):
    on_params, _ = toy_fit(epochs=2)
    monkeypatch.setenv("ALINK_TRACING", "off")
    metrics.counter("host.gc_collections.gen2")     # drain what was counted on
    full0 = metrics.counter("host.gc_collections.gen2")
    n0, _ = hist_count("span.host.gc_s")
    recs0 = len(collector_records())
    steps0, _ = hist_count("train.device_step_s")
    started = []
    real_start = threading.Thread.start

    def start(self):
        started.append(self.name)
        real_start(self)

    monkeypatch.setattr(threading.Thread, "start", start)
    gc.collect()
    off_params, _ = toy_fit(epochs=2)
    assert "alink-train-watch" not in started
    assert metrics.counter("host.gc_collections.gen2") == full0
    assert hist_count("span.host.gc_s")[0] == n0
    assert len(collector_records()) == recs0
    assert hist_count("train.device_step_s")[0] == steps0
    import jax

    for a, b in zip(jax.tree.leaves(on_params), jax.tree.leaves(off_params)):
        assert np.array_equal(a, b)
