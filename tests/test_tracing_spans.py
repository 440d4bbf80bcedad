"""The program's phase spans (ISSUE 26): a span is an event on the profiler's
host plane, knows its self time and is summed per name in the registry; one
served batch and one fit yield exactly the documented names, nested as
documented, and the two spans of a model load occur in the server's warm-up
and in no batch after it (ISSUE 27: the served model stays loaded); the four
batcher spans tile the batcher thread; every span name in
the program is listed in ``docs/observability.md`` and, for the two paths the
benchmark runs, in ``PERF.md``."""

import glob
import json
import os
import re
import threading
import time

import pytest

from alink_tpu.common.metrics import metrics, profile_trace
from alink_tpu.common.model import table_to_model
from alink_tpu.common.tracing import (attach_context, capture_context,
                                      trace_span, tracer)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# every literal span name in the program; the executor's unit spans are named
# by operator class and recovery's chains by ``recovery.chain<i>[.p<j>]``
SERVED_BATCH = {
    "serving.wait", "serving.collect", "serving.batch", "serving.build_table",
    "serving.predict", "serving.reply", "mapper.map_table",
    "bert.tokenize", "dl.predict", "dl.predict.apply", "bert.postprocess"}
# once per load of a model, not per batch: in a server, during the warm-up
MODEL_LOAD = {"mapper.load_model", "dl.predict.place_params"}
FIT = {"train.tokenize", "train.ingest_checkpoint", "train.place_state",
       "train.epoch", "train.export_model"}
# what CausalLMTrainBatchOp opens beside those five
LM_FIT = {"train.pack"}
# the generator's batch (CausalLMGenerateBatchOp), and its one load
GENERATE = {"lm.tokenize", "lm.prefill", "lm.decode", "lm.detokenize",
            "lm.load_model"}
OTHER = {"dag.run", "serving.warmup", "serving.request", "fleet.request",
         "modelstream.publish", "modelstream.swap", "recovery.run",
         "recovery.epoch", "recovery.rescale", "stream.collect",
         "webui.run_experiment"}
# child -> parent, where the parent is not a unit span
PARENT = {"serving.build_table": "serving.batch",
          "serving.predict": "serving.batch", "dag.run": "serving.predict",
          "bert.tokenize": "dl.predict", "dl.predict": "mapper.map_table",
          "bert.postprocess": "mapper.map_table",
          "dl.predict.apply": "dl.predict"}


@pytest.fixture(autouse=True)
def tracing_on(monkeypatch):
    monkeypatch.setenv("ALINK_TRACING", "on")


def span_sum(name):
    h = metrics.histogram_states().get(f"span.{name}_s")
    return (h["sum"], h["count"]) if h else (0.0, 0)


def test_a_span_is_an_event_on_the_profilers_host_plane(tmp_path):
    from jax.profiler import ProfileData

    with profile_trace(str(tmp_path)):
        with trace_span("test.outer"):
            with trace_span("test.inner"):
                time.sleep(0.002)
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb"))
    found = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:CPU"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in ("test.outer", "test.inner"):
                    found[ev.name] = (line.name, ev.start_ns, ev.duration_ns)
    assert set(found) == {"test.outer", "test.inner"}
    (lo, so, do), (li, si, di) = found["test.outer"], found["test.inner"]
    assert lo == li                                   # the opening thread's line
    assert so <= si and si + di <= so + do and di >= 2_000_000
    # the Python tracer is off: no event for a Python call (it names them "$...")
    assert not any(ev.name.startswith("$")
                   for plane in ProfileData.from_file(path).planes
                   for line in plane.lines for ev in line.events)


def _child(kind, parent_token, out):
    if kind == "other_thread":
        def work():
            with attach_context(parent_token), trace_span("test.child") as sp:
                time.sleep(0.01)
            out.append(sp)
        t = threading.Thread(target=work)
        t.start()
        t.join(10)
        assert not t.is_alive()
        return
    try:
        with trace_span("test.child") as sp:
            out.append(sp)
            time.sleep(0.01)
            if kind == "failed":
                raise ValueError("planted")
    except ValueError:
        pass


@pytest.mark.parametrize("kind,subtracted", [
    ("same_thread", True), ("failed", True), ("other_thread", False)])
def test_self_time_is_wall_less_same_thread_children(kind, subtracted):
    kids = []
    with trace_span("test.parent") as parent:
        _child(kind, capture_context(), kids)
        _child("same_thread", None, kids)
    (first, second) = kids
    assert first.parent_id == parent.span_id == second.parent_id
    assert first.outcome == ("failed" if kind == "failed" else "ok")
    expect = second.wall_s + (first.wall_s if subtracted else 0.0)
    assert parent.child_s == pytest.approx(expect, abs=1e-9)
    assert parent.self_s == pytest.approx(parent.wall_s - expect, abs=1e-9)
    assert first.self_s == pytest.approx(first.wall_s)
    rec = next(s for s in reversed(tracer.spans()) if s["name"] == "test.parent")
    assert rec["self_s"] == pytest.approx(parent.self_s, abs=1e-6)


def test_walls_are_summed_per_name_and_the_pooled_histogram_is_gone():
    before = {n: span_sum(n) for n in ("test.summed_a", "test.summed_b")}
    walls = {"test.summed_a": [], "test.summed_b": []}
    for name in ("test.summed_a", "test.summed_b", "test.summed_a"):
        with trace_span(name) as sp:
            time.sleep(0.001)
        walls[name].append(sp.wall_s)
    for name, ws in walls.items():
        s, c = span_sum(name)
        assert c - before[name][1] == len(ws)
        assert s - before[name][0] == pytest.approx(sum(ws), abs=1e-9)
    assert metrics.histogram("trace.span_s") is None
    assert metrics.counter("trace.spans") == 0


def test_a_dropped_span_leaves_no_record():
    before, n0 = span_sum("test.dropped"), len(tracer.spans())
    with trace_span("test.dropped") as sp:
        sp.keep = False
    assert span_sum("test.dropped") == before and len(tracer.spans()) == n0


# ---------------------------------------------------------------------------
# the two paths the benchmark runs, at toy width
# ---------------------------------------------------------------------------

DOCS = [f"tok{i % 7} tok{i % 5} tok{i % 3} number {i}" for i in range(24)]


@pytest.fixture(scope="module")
def checkpoint(tmp_path_factory):
    from benchmark import gen

    with open(os.path.join(ROOT, "benchmark", "tests", "data", "configs",
                           "toy_cls.json")) as f:
        cfg = json.load(f)
    path = str(tmp_path_factory.mktemp("toy_ckpt"))
    gen.write_checkpoint(path, cfg, gen.make_weights(cfg, 7),
                         gen.make_vocab(cfg["vocab_size"]))
    return path


def fit(checkpoint):
    from alink_tpu.common.mtable import MTable
    from alink_tpu.operator.batch.base import TableSourceBatchOp
    from alink_tpu.operator.batch.dl import BertTextClassifierTrainBatchOp

    src = TableSourceBatchOp(MTable({"text": DOCS[:16],
                                     "label": [i % 2 for i in range(16)]}))
    return BertTextClassifierTrainBatchOp(
        textCol="text", labelCol="label", maxSeqLength=16, batchSize=8,
        numEpochs=1, learningRate=1e-3, checkpointFilePath=checkpoint,
        randomSeed=3).link_from(src).collect()


@pytest.fixture(scope="module")
def model_table(checkpoint):
    os.environ["ALINK_TRACING"] = "on"
    tracer.clear()
    table = fit(checkpoint)
    return table, tracer.spans()


def serve(model_table, cycles=3, rows=8, submitted=None):
    from alink_tpu.pipeline import BertTextClassifierModel, PipelineModel
    from alink_tpu.serving import ModelServer, ServingConfig

    stage = BertTextClassifierModel(predictionCol="pred",
                                    predictionDetailCol="detail")
    stage.set_model_data(model_table)
    server = ModelServer(ServingConfig(max_batch_rows=rows, queue_depth=2 * rows,
                                       flush_deadline_s=0.05))
    answers = []
    try:
        tracer.clear()
        server.load("toy", PipelineModel(stage), "text string",
                    warmup_rows=[(DOCS[0],)])
        warmup = tracer.spans()
        tracer.clear()
        loads0 = metrics.counter("mapper.model_loads")
        for c in range(cycles):
            futs = [server.submit("toy", (d,)) for d in DOCS[c:c + rows]]
            if submitted is not None:
                submitted.extend(futs)
            answers.append([f.result(120) for f in futs])
            time.sleep(0.02)        # the queue runs empty: a serving.wait
    finally:
        server.close()
    return (answers, tracer.spans(),
            metrics.counter("mapper.model_loads") - loads0, warmup)


def test_one_fit_yields_the_set_up_spans(model_table):
    _, spans = model_table
    names = {s["name"] for s in spans}
    units = {"BertTextClassifierTrainBatchOp", "TableSourceBatchOp", "dag.run"}
    assert names - units == FIT
    by_name = {}
    for s in spans:
        by_name.setdefault(s["name"], []).append(s)
    # the file read and the graft into a fresh init; device_get and the table
    assert sorted(s["attrs"]["part"] for s in by_name["train.ingest_checkpoint"]) \
        == ["init", "read"]
    assert len(by_name["train.export_model"]) == 2
    op = by_name["BertTextClassifierTrainBatchOp"][0]
    assert all(s["parent_id"] == op["span_id"] for n in FIT for s in by_name[n])


def test_one_served_batch_yields_the_documented_spans(model_table):
    table, _ = model_table
    reuses0 = metrics.counter("mapper.model_reuses")
    _, spans, loads, warmup = serve(table)
    # the model was loaded and its parameters placed once, by the warm-up's
    # first predict; every predict since ran through the loaded mapper
    assert loads == 0
    by_id = {s["span_id"]: s for s in warmup}
    predicts = sum(s["name"] == "mapper.map_table" for s in warmup)
    assert predicts >= 1 and "serving.warmup" in {s["name"] for s in warmup}
    assert metrics.counter("mapper.model_reuses") - reuses0 == predicts - 1 + 3
    (load,) = [s for s in warmup if s["name"] == "mapper.load_model"]
    (place,) = [s for s in warmup if s["name"] == "dl.predict.place_params"]
    assert by_id[load["parent_id"]]["name"] == "BertTextClassifierPredictBatchOp"
    assert by_id[place["parent_id"]]["name"] == "mapper.map_table"
    by_id = {s["span_id"]: s for s in spans}
    units = {"BertTextClassifierPredictBatchOp", "TableSourceBatchOp"}
    assert {s["name"] for s in spans} - units == SERVED_BATCH | {"dag.run"}
    per_batch = {n: sum(s["name"] == n for s in spans) for n in SERVED_BATCH}
    # a batch of 8 rows is one slice: tokenised (asked for by predict_model,
    # so inside dl.predict) and the tokenizer's memo dropped under a second
    # bert.tokenize, its forward dispatched under one dl.predict.apply and
    # its result read under another
    assert per_batch.pop("dl.predict.apply") == 6
    assert per_batch.pop("bert.tokenize") == 6
    assert set(per_batch.values()) == {3}, per_batch
    for s in spans:
        parent = by_id.get(s["parent_id"])
        if s["name"] in PARENT:
            assert parent["name"] == PARENT[s["name"]], s["name"]
        elif s["name"].startswith("mapper."):
            assert parent["name"] == "BertTextClassifierPredictBatchOp"
        elif s["name"] in units:
            assert parent["name"] == "dag.run"
        else:       # the four that tile the batcher thread have no span above
            assert parent is None, s["name"]
        if parent is not None:      # each child inside its parent, on one clock
            assert parent["start_perf"] <= s["start_perf"] + 1e-6
            assert s["start_perf"] + s["wall_s"] \
                <= parent["start_perf"] + parent["wall_s"] + 1e-5
    assert all(s["attrs"]["rows"] == 8 for s in spans
               if s["name"] in ("serving.collect", "serving.batch", "serving.reply"))


def test_the_batcher_spans_tile_the_batcher_thread(model_table):
    table, _ = model_table
    _, spans, _, _ = serve(table)
    tiles = sorted((s for s in spans if s["name"] in (
        "serving.wait", "serving.collect", "serving.batch", "serving.reply")),
        key=lambda s: s["start_perf"])
    assert len({s["thread"] for s in tiles}) == 1
    assert [s["name"] for s in tiles[-4:]] == [
        "serving.wait", "serving.collect", "serving.batch", "serving.reply"]
    first = next(i for i, s in enumerate(tiles) if s["name"] == "serving.collect")
    tiles = tiles[first:]       # from the first batch's collect to the last reply
    wall = tiles[-1]["start_perf"] + tiles[-1]["wall_s"] - tiles[0]["start_perf"]
    covered = sum(s["wall_s"] for s in tiles)
    assert covered <= wall * 1.0001
    assert covered >= 0.98 * wall, (covered, wall)
    # a batch's spans are all finished before its first future is completed
    for reply in (s for s in tiles if s["name"] == "serving.reply"):
        batch = max((s for s in tiles if s["name"] == "serving.batch"
                     and s["start_perf"] < reply["start_perf"]),
                    key=lambda s: s["start_perf"])
        assert batch["start_perf"] + batch["wall_s"] <= reply["start_perf"] + 1e-6


def test_rows_are_taken_out_before_the_first_future_is_completed(
        model_table, monkeypatch):
    """Once a client wakes and resubmits, the next batch's flush deadline is
    running: the reply loop only completes futures."""
    from alink_tpu.common.mtable import MTable

    table, _ = model_table
    futures, completed_before = [], []
    real = MTable.get_row

    def get_row(self, i):
        if threading.current_thread().name.startswith("alink-serving-"):
            completed_before.append(any(f.done() for f in futures))
        return real(self, i)

    monkeypatch.setattr(MTable, "get_row", get_row)
    serve(table, cycles=1, submitted=futures)
    assert len(completed_before) == 8 and not any(completed_before)


@pytest.mark.parametrize("path", ["fit", "serve"])
def test_results_with_tracing_off_equal_results_with_it_on(
        path, checkpoint, model_table, monkeypatch):
    table, _ = model_table
    results = {}
    for mode in ("on", "off"):
        monkeypatch.setenv("ALINK_TRACING", mode)
        tracer.clear()
        if path == "fit":
            meta, arrays = table_to_model(fit(checkpoint))
            results[mode] = (meta, {k: v.tobytes() for k, v in arrays.items()})
        else:
            results[mode] = serve(table, cycles=2)[0]
        assert bool(tracer.spans()) == (mode == "on")
    assert results["on"] == results["off"]


def test_every_span_name_is_documented():
    found = set()
    for path in glob.glob(os.path.join(ROOT, "alink_tpu", "**", "*.py"),
                          recursive=True):
        with open(path) as f:
            src = f.read()
        found |= set(re.findall(r'trace_span\(\s*"([^"]+)"', src))
    found -= {"kmeans.fit"}             # trace_span's own docstring
    assert found == SERVED_BATCH | MODEL_LOAD | FIT | LM_FIT | GENERATE | OTHER
    with open(os.path.join(ROOT, "docs", "observability.md")) as f:
        docs = f.read()
    with open(os.path.join(ROOT, "PERF.md")) as f:
        perf = f.read()
    assert [n for n in sorted(found) if f"`{n}`" not in docs] == []
    on_the_benchmarks_paths = SERVED_BATCH | MODEL_LOAD | FIT | LM_FIT \
        | GENERATE | {"dag.run", "serving.warmup", "serving.request"}
    assert [n for n in sorted(on_the_benchmarks_paths) if f"`{n}`" not in perf] == []
    gone = re.compile(r"trace\.span_s|trace\.spans\b|executor\.node_wall"
                      r"|executor\.schedule\b")
    for path in glob.glob(os.path.join(ROOT, "alink_tpu", "**", "*.py"),
                          recursive=True) + [os.path.join(ROOT, "README.md")]:
        with open(path) as f:
            assert gone.findall(f.read()) == [], path
