"""Predict in slices, dispatched ahead (ISSUE 33): ``predict_model`` takes its
rows whole or a slice at a time and answers the same, rows in order, ragged
tail and data axis included; ``_batched_apply`` asks its producer for slice
i+1 before it reads slice i's result and never has more than ``_SLICES_OUT``
forwards out; the BERT mapper hands it a tokenizer that encodes a slice when
asked, answers what row-at-a-time encoding answers, counts the rows it
dispatched ahead, and its two leaf spans lie beside each other."""

import json
import os
import time

import numpy as np
import pytest

from alink_tpu.common.metrics import metrics
from alink_tpu.common.mtable import MTable
from alink_tpu.common.tracing import tracer
from alink_tpu.dl import train
from alink_tpu.dl.train import PREDICT_SLICE, _batched_apply, predict_model

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOCS = [f"tok{i % 7} tok{i % 5} tok{i % 3} number {i} and {i * i % 11}"
        for i in range(130)]
KEYS = ("input_ids", "attention_mask", "token_type_ids")


def counted():
    return (metrics.counter("predict.rows"),
            metrics.counter("predict.rows_dispatched_ahead"))


def cut(enc, rows):
    n = len(enc[KEYS[0]])
    return [{k: enc[k][s:s + rows] for k in enc} for s in range(0, n, rows)]


# ---------------------------------------------------------------------------
# predict_model: whole arrays or an iterator of slices
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def encoder():
    import jax
    import jax.numpy as jnp

    from alink_tpu.dl.modules import BertConfig, TransformerEncoder

    model = TransformerEncoder(BertConfig.tiny(
        vocab_size=64, hidden_size=32, num_layers=1, intermediate_size=64,
        max_position=16, dtype=jnp.float32))
    rng = np.random.default_rng(3)
    n, seq = 150, 16
    lens = rng.integers(3, seq + 1, n)
    mask = (np.arange(seq)[None] < lens[:, None]).astype(np.int32)
    enc = {"input_ids": (rng.integers(1, 64, (n, seq)) * mask).astype(np.int32),
           "attention_mask": mask,
           "token_type_ids": np.zeros((n, seq), np.int32)}
    params = model.init(jax.random.PRNGKey(0),
                        **{k: v[:1] for k, v in enc.items()})
    return model, params, enc


@pytest.mark.parametrize("devices", [8, 2])
def test_slices_answer_what_the_whole_table_answers(encoder, devices):
    import jax

    from alink_tpu.parallel.mesh import AXIS_DATA, default_mesh

    model, params, enc = encoder
    mesh = default_mesh(jax.devices()[:devices])
    assert mesh.shape[AXIS_DATA] == devices
    r0, a0 = counted()
    whole = predict_model(model, params, enc, mesh=mesh)
    assert whole.shape[0] == 150
    assert counted() == (r0 + 150, a0)              # one chunk: nothing ahead
    # 64 + 64 + 22: a ragged tail, padded to its own rung and to the axis
    sliced = predict_model(model, params, iter(cut(enc, 64)), mesh=mesh)
    assert counted() == (r0 + 300, a0 + 128)
    np.testing.assert_allclose(sliced, whole, rtol=1e-5, atol=1e-6)
    # a dict is cut at batch_size as it always was, and now runs ahead too
    chunked = predict_model(model, params, enc, mesh=mesh, batch_size=64)
    assert counted() == (r0 + 450, a0 + 256)
    assert chunked.tobytes() == sliced.tobytes()
    # rows in order: each row alone answers its own row of the table
    for i in (0, 63, 64, 127, 128, 149):
        one = predict_model(model, params,
                            {k: v[i:i + 1] for k, v in enc.items()}, mesh=mesh)
        np.testing.assert_allclose(one[0], sliced[i], rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# dispatch ahead, seen from a producer and a forward that log their calls
# ---------------------------------------------------------------------------

class LoggedResult:
    """What a forward returns, as far as ``_batched_apply`` uses it."""

    def __init__(self, i, values, log):
        self.i, self.values, self.log = i, values, log

    def copy_to_host_async(self):
        self.log.append(("copy", self.i))

    def __array__(self, dtype=None, copy=None):
        self.log.append(("read", self.i))
        return self.values


def logged_run(sizes, bs=8):
    from alink_tpu.dl.sharding import batch_sharding
    from alink_tpu.parallel.mesh import default_mesh

    mesh, log = default_mesh(), []
    tables = [np.arange(100 * i, 100 * i + m, dtype=np.float32).reshape(m, 1)
              for i, m in enumerate(sizes)]

    def producer():
        for i, x in enumerate(tables):
            log.append(("asked", i))
            yield {"x": x}

    def forward(params, batch):
        i = sum(e[0] == "forward" for e in log)
        log.append(("forward", i))
        return LoggedResult(i, np.asarray(batch["x"]) * 2.0, log)

    got = _batched_apply(forward, None, producer(), mesh,
                         lambda a: batch_sharding(mesh, a.ndim, seq_axis=None),
                         bs)
    return got, np.concatenate(tables) * 2.0, log


def test_the_next_slice_is_asked_for_before_a_result_is_read():
    sizes = [8, 8, 8, 8, 8, 8, 5]
    r0, a0 = counted()
    got, want, log = logged_run(sizes)
    assert counted() == (r0 + sum(sizes), a0 + sum(sizes[:-1]))
    assert got.tobytes() == want.tobytes()          # in order, padding trimmed
    at = {e: log.index(e) for e in log}
    for i in range(len(sizes)):
        assert at[("asked", i)] < at[("forward", i)] < at[("copy", i)] \
            < at[("read", i)]
        if i + 1 < len(sizes):
            assert at[("asked", i + 1)] < at[("read", i)]
    assert [e for e in log if e[0] == "read"] \
        == [("read", i) for i in range(len(sizes))]
    out = most = 0
    for kind, _ in log:
        out += (kind == "forward") - (kind == "read")
        most = max(most, out)
    assert most == train._SLICES_OUT < len(sizes)
    # nothing was read until the limit made it: the first read follows the
    # producer's hand-over of the slice after the last one allowed out
    assert at[("asked", train._SLICES_OUT)] < at[("read", 0)] \
        < at[("forward", train._SLICES_OUT)]


def test_a_slice_larger_than_the_batch_size_is_cut_and_one_slice_runs_alone():
    got, want, log = logged_run([20], bs=8)         # 8 + 8 + 4
    assert got.tobytes() == want.tobytes()
    assert [e[0] for e in log] == ["asked"] + ["forward", "copy"] * 3 \
        + ["read"] * 3
    r0, a0 = counted()
    got, want, log = logged_run([6])
    assert got.tobytes() == want.tobytes()
    assert [e[0] for e in log] == ["asked", "forward", "copy", "read"]
    assert counted() == (r0 + 6, a0)


# ---------------------------------------------------------------------------
# the BERT mapper: tokenise a slice when predict_model asks for it
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def model_table(tmp_path_factory):
    from benchmark import gen

    from alink_tpu.operator.batch.base import TableSourceBatchOp
    from alink_tpu.operator.batch.dl import BertTextClassifierTrainBatchOp

    with open(os.path.join(ROOT, "benchmark", "tests", "data", "configs",
                           "toy_cls.json")) as f:
        cfg = json.load(f)
    path = str(tmp_path_factory.mktemp("toy_ckpt"))
    gen.write_checkpoint(path, cfg, gen.make_weights(cfg, 7),
                         gen.make_vocab(cfg["vocab_size"]))
    train_t = MTable({"text": DOCS[:16], "label": [i % 2 for i in range(16)]})
    return BertTextClassifierTrainBatchOp(
        textCol="text", labelCol="label", maxSeqLength=16, batchSize=8,
        numEpochs=1, learningRate=1e-3, checkpointFilePath=path,
        randomSeed=3).link_from(TableSourceBatchOp(train_t)).collect()


def load_mapper(model_table):
    from alink_tpu.common.params import Params
    from alink_tpu.operator.batch.dl import BertTextModelMapper

    return BertTextModelMapper(
        model_table.schema, MTable({"text": DOCS[:1]}).schema,
        Params(predictionCol="pred", predictionDetailCol="detail")
    ).load_model(model_table)


def slice_sizes(n):
    return [min(PREDICT_SLICE, n - s) for s in range(0, n, PREDICT_SLICE)]


@pytest.mark.parametrize("n", [130, 40])
def test_the_mapper_answers_what_row_at_a_time_encoding_answers(
        model_table, monkeypatch, n):
    from alink_tpu.mapper.base import softmax_np

    sizes = slice_sizes(n)      # 32 x 4 + 2 and 32 + 8 at 32 rows a slice
    monkeypatch.setenv("ALINK_TRACING", "on")
    mapper = load_mapper(model_table)
    rows = [mapper.tokenizer.encode(d, None, 16) for d in DOCS[:n]]
    enc = {k: np.asarray([r[j] for r in rows], np.int32)
           for j, k in enumerate(KEYS)}
    want = softmax_np(predict_model(mapper.model, mapper.params, enc,
                                    precision=mapper._policy))
    tracer.clear()
    r0, a0 = counted()
    pred, _, detail = mapper.predict_block(MTable({"text": DOCS[:n]}))
    assert counted() == (r0 + n, a0 + n - sizes[-1])
    labels = mapper.meta["labels"]
    got = np.asarray([[json.loads(d)[str(l)] for l in labels] for d in detail])
    np.testing.assert_allclose(got, want, atol=2e-3)
    sure = np.abs(want[:, 0] - want[:, 1]) > 1e-2
    assert sure.sum() > n // 2
    assert [str(p) for p in pred[sure]] \
        == [str(labels[i]) for i in want.argmax(axis=1)[sure]]
    # one bert.tokenize a slice, with its rows; a dispatch and a read a slice
    spans = tracer.spans()
    # (and one more, of no rows, that drops the call's memo)
    assert [s["attrs"]["rows"] for s in spans
            if s["name"] == "bert.tokenize"] == sizes + [0]
    assert sum(s["name"] == "dl.predict.apply" for s in spans) == 2 * len(sizes)


LEAVES = ("serving.build_table", "mapper.load_model", "bert.tokenize",
          "dl.predict.place_params", "dl.predict.apply", "bert.postprocess")


def test_a_served_batchs_leaf_spans_lie_beside_each_other(model_table,
                                                          monkeypatch):
    from alink_tpu.pipeline import BertTextClassifierModel, PipelineModel
    from alink_tpu.serving import ModelServer, ServingConfig

    monkeypatch.setenv("ALINK_TRACING", "on")
    stage = BertTextClassifierModel(predictionCol="pred",
                                    predictionDetailCol="detail")
    stage.set_model_data(model_table)
    server = ModelServer(ServingConfig(max_batch_rows=128, queue_depth=256,
                                       flush_deadline_s=0.5))
    try:
        server.load("toy", PipelineModel(stage), "text string",
                    warmup_rows=[(DOCS[0],)])
        tracer.clear()
        r0, a0 = counted()
        for _ in range(3):
            futs = [server.submit("toy", (d,)) for d in DOCS[:128]]
            assert len([f.result(120) for f in futs]) == 128
            time.sleep(0.02)
    finally:
        server.close()
    k = len(slice_sizes(128))
    assert counted() == (r0 + 3 * 128, a0 + 3 * (128 - PREDICT_SLICE))
    spans = tracer.spans()
    by_id = {s["span_id"]: s for s in spans}
    batches = [s for s in spans if s["name"] == "serving.batch"]
    assert [b["attrs"]["rows"] for b in batches] == [128] * 3
    leaves = sorted((s for s in spans if s["name"] in LEAVES),
                    key=lambda s: s["start_perf"])
    for s in leaves:                    # no leaf has a leaf above it
        up = by_id.get(s["parent_id"])
        while up is not None:
            assert up["name"] not in LEAVES, (s["name"], up["name"])
            up = by_id.get(up["parent_id"])
    for a, b in zip(leaves, leaves[1:]):            # nor overlaps the next
        assert a["start_perf"] + a["wall_s"] <= b["start_perf"] + 1e-6
    per_batch = [s["name"] for s in leaves
                 if s["start_perf"] >= batches[-1]["start_perf"]]
    # each slice tokenised and dispatched in turn, the tokenizer's memo
    # dropped while the last forward runs, then the reads
    assert per_batch == ["serving.build_table"] \
        + ["bert.tokenize", "dl.predict.apply"] * k + ["bert.tokenize"] \
        + ["dl.predict.apply"] * k + ["bert.postprocess"]
    # every leaf lies inside its batch and none is counted twice, so leaves
    # and what no leaf names tile the batch. (How much no leaf names is a
    # matter of size: 2-3 ms a batch of DAG scheduling and rows taken out, a
    # quarter of a toy batch here and under 1% of the benchmark's, whose
    # eight shares are read on the chip.)
    for s in leaves:
        batch = max((b for b in batches if b["start_perf"] <= s["start_perf"]),
                    key=lambda b: b["start_perf"])
        assert s["start_perf"] + s["wall_s"] \
            <= batch["start_perf"] + batch["wall_s"] + 1e-5
    assert sum(s["wall_s"] for s in leaves) \
        <= sum(b["wall_s"] for b in batches) * 1.0001
