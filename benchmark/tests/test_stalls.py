"""The ten per-layer metrics that read what the program keeps of its own
pauses (PR 36; ``reducers/stalls.py``): their files, their readers on
hand-made facts shaped as a parent's and as a change's, and the toy serve
and toy fine-tune rehearsals traced with them listed."""

import importlib
import json
import os

import pytest

from benchmark.tests.test_run import ROOT, TOY, run
from benchmark.tests.test_spans import hist

SERVE = {"host.gc_share_pct.serve", "host.gc_full_in_window.serve",
         "serve.slow_cycle_loss_pct", "serve.tokenize_offcpu_share_pct",
         "serve.deadline_flush_pct"}
TRAIN = {"host.gc_share_pct.train", "host.gc_full_in_window.train",
         "train.slow_epoch_loss_pct", "train.device_step_ms_p50",
         "train.slow_steps_in_window"}
SLOW = {"serve.slow_cycle_loss_pct", "train.slow_epoch_loss_pct",
        "train.slow_steps_in_window"}


def entries():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return {m["name"]: m for m in json.load(f)["per_layer"]
                if m["name"] in SERVE | TRAIN}


def spec(name):
    with open(os.path.join(ROOT, "benchmark", "metrics", name + ".json")) as f:
        return json.load(f)


def read(name, facts):
    s = spec(name)
    mod, fn = s["reducer"].rsplit(".", 1)
    reader = getattr(importlib.import_module(f"benchmark.reducers.{mod}"), fn)
    return reader(dict(facts, metric=name), **s.get("args", {}))


@pytest.mark.parametrize("name", sorted(SERVE | TRAIN))
def test_each_metric_has_its_entry_its_file_and_a_reader_that_exists(name):
    m = entries()[name]
    serve = name in SERVE
    assert m["moves"] == ("infer_rows_per_s" if serve
                          else "train_rows_per_s_per_chip")
    assert m["source"] in ("program_span", "program_counter")
    assert all(("serve" in w) == serve for w in m["workloads"])
    s = spec(name)
    mod, fn = s["reducer"].rsplit(".", 1)
    assert mod in ("stalls", "counters")
    assert callable(getattr(importlib.import_module(
        f"benchmark.reducers.{mod}"), fn))
    assert s["args"]["phase"] == "window" and s["what"]


def test_the_ten_stand_at_the_end_of_the_list_and_nothing_else_changed():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        names = [m["name"] for m in json.load(f)["per_layer"]]
    assert set(names[-10:]) == SERVE | TRAIN


# a program from before PR 36: spans summed by name, no cpu. family, none of
# the new counters
PARENT = {
    "window_s": 10.0, "traffic": {},
    "counters_window": {"seconds": 10.0,
                        "counters": {"serving.completed": 2560, "train.steps": 40},
                        "hists": {"span.serving.batch_s": hist(9.0, 40),
                                  "span.bert.tokenize_s": hist(7.0, 320),
                                  "span.train.epoch_s": hist(9.9, 2),
                                  "train.step_s": hist(0.2, 40)}},
}
# the change, in a window in which nothing was slow and no full collection fell
CHANGE = {
    "window_s": 10.0, "traffic": {},
    "counters_window": {
        "seconds": 10.0,
        "counters": {"serving.completed": 2560, "train.steps": 40,
                     "serving.flush_full": 40, "host.gc_collections.gen0": 900},
        "hists": {"span.serving.batch_s": hist(9.0, 40),
                  "cpu.serving.batch_s": hist(8.0, 40),
                  "span.bert.tokenize_s": hist(7.0, 320),
                  "cpu.bert.tokenize_s": hist(6.5, 320),
                  "span.train.epoch_s": hist(9.9, 2),
                  "cpu.train.epoch_s": hist(0.4, 2),
                  "span.host.gc_s": hist(0.05, 900),
                  "train.device_step_s": {"buckets": [0.09, 0.099, 0.1089],
                                          "counts": [0, 0, 39, 0], "count": 39,
                                          "sum": 3.9}}},
}


@pytest.mark.parametrize("name", sorted(SERVE | TRAIN))
def test_a_parent_shaped_window_gives_nothing(name):
    assert read(name, PARENT) is None


@pytest.mark.parametrize("name,expected", [
    ("host.gc_share_pct.serve", 0.5), ("host.gc_share_pct.train", 0.5),
    ("host.gc_full_in_window.serve", 0.0), ("host.gc_full_in_window.train", 0.0),
    ("serve.slow_cycle_loss_pct", 0.0), ("train.slow_epoch_loss_pct", 0.0),
    ("train.slow_steps_in_window", 0.0),
    ("serve.tokenize_offcpu_share_pct", 5.0),
    ("serve.deadline_flush_pct", 0.0),
])
def test_a_change_shaped_window_reads_a_number_and_0_where_nothing_was_slow(
        name, expected):
    assert read(name, CHANGE) == pytest.approx(expected)


def test_the_device_step_is_read_within_its_bucket():
    got = read("train.device_step_ms_p50", CHANGE)
    assert 99.0 <= got <= 108.9


def test_a_slow_unit_and_a_split_batch_show():
    facts = json.loads(json.dumps(CHANGE))
    w = facts["counters_window"]
    w["hists"]["slow.serving.batch_excess_s"] = hist(0.3, 2)
    w["hists"]["slow.train.epoch_excess_s"] = hist(0.29, 1)
    w["counters"].update({"slow.train.step": 1, "serving.flush_deadline": 10,
                          "host.gc_collections.gen2": 3})
    assert read("serve.slow_cycle_loss_pct", facts) == pytest.approx(3.0)
    assert read("train.slow_epoch_loss_pct", facts) == pytest.approx(2.9)
    assert read("train.slow_steps_in_window", facts) == 1.0
    assert read("serve.deadline_flush_pct", facts) == pytest.approx(20.0)
    assert read("host.gc_full_in_window.serve", facts) == 3.0


@pytest.fixture(scope="module")
def stalls_toy(tmp_path_factory):
    """The toy cells of ``BENCHMARK.toy.json`` with the ten metrics listed as
    ``BENCHMARK.json`` lists them, cell names apart."""
    with open(TOY) as f:
        bench = json.load(f)
    cell = {True: "toy_cls.serve_toy", False: "toy_cls.finetune_toy"}
    bench["per_layer"] = [dict(m, workloads=[cell[n in SERVE]])
                          for n, m in entries().items()]
    path = tmp_path_factory.mktemp("stalls") / "BENCHMARK.stalls.toy.json"
    path.write_text(json.dumps(bench))
    return str(path)


@pytest.mark.parametrize("workload,listed", [
    ("toy_cls.serve_toy", SERVE), ("toy_cls.finetune_toy", TRAIN)])
def test_a_traced_toy_run_prints_every_metric_listed_for_it(
        stalls_toy, workload, listed):
    p, result = run(workload, "--benchmark-file", stalls_toy, trace=1)
    assert p.returncode == 0, p.stderr[-2000:]
    got = {n: m["value"] for n, m in result["metrics"].items()}
    print({n: round(v, 4) for n, v in got.items()})
    assert set(got) == listed
    assert all(v >= 0 for n, v in got.items() if n not in
               ("serve.tokenize_offcpu_share_pct",))
    if workload.endswith("serve_toy"):
        assert got["host.gc_share_pct.serve"] > 0
        assert got["serve.tokenize_offcpu_share_pct"] > -5.0
    else:
        assert got["train.device_step_ms_p50"] > 0
