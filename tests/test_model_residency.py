"""The served model stays loaded (ISSUE 27): a ``ModelMapBatchOp`` keeps the
mapper it last loaded and runs the next execute through it when the model
table is the same object, the data schema is equal and the op's params read as
they did at the load; anything else loads anew. A planned ``LocalPredictor``
therefore loads once and answers bit for bit what a plan rebuilt per call
answers. ``predict_model`` given what ``prepare_params`` made of a tree places
nothing and equals the one-call form bit for bit, fp32, bf16 and int8."""

import json
import os
import sys
import threading

import numpy as np
import pytest

from alink_tpu.common import quant
from alink_tpu.common.metrics import metrics
from alink_tpu.common.mtable import MTable
from alink_tpu.common.tracing import tracer
from alink_tpu.operator.batch.base import TableSourceBatchOp
from alink_tpu.operator.batch.dl import (BertTextClassifierPredictBatchOp,
                                         BertTextClassifierTrainBatchOp)
from alink_tpu.operator.batch.linear import (LogisticRegressionPredictBatchOp,
                                             LogisticRegressionTrainBatchOp)
from alink_tpu.pipeline import (BertTextClassifierModel, LocalPredictor,
                                LogisticRegressionModel, PipelineModel)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOCS = [f"tok{i % 7} tok{i % 5} tok{i % 3} number {i}" for i in range(24)]
FEATS = ["f0", "f1", "f2"]


class Kind:
    """One model family: its fitted model table, rows to predict, the
    predict op and the pipeline stage that serves it."""

    def __init__(self, model, data, op_cls, op_params, stage_cls, extra_col):
        self.model, self.data = model, data
        self.op_cls, self.op_params = op_cls, op_params
        self.stage_cls, self.extra_col = stage_cls, extra_col

    def op(self, model=None, data=None):
        return self.op_cls(**self.op_params).link_from(
            TableSourceBatchOp(model if model is not None else self.model),
            TableSourceBatchOp(data if data is not None else self.data))

    def predictor(self, **kw):
        stage = self.stage_cls(**self.op_params)
        stage.set_model_data(self.model)
        return LocalPredictor(PipelineModel(stage), self.data.schema, **kw)


@pytest.fixture(scope="module")
def linear():
    rng = np.random.default_rng(5)
    X = rng.normal(size=(48, 3))
    cols = {f: X[:, i] for i, f in enumerate(FEATS)}
    train = MTable(dict(cols, label=(X @ [1.0, -2.0, 0.5] > 0).astype(np.int64)))
    model = LogisticRegressionTrainBatchOp(
        featureCols=FEATS, labelCol="label", maxIter=20).link_from(
            TableSourceBatchOp(train)).collect()
    return Kind(model, MTable(cols), LogisticRegressionPredictBatchOp,
                {"predictionCol": "pred", "predictionDetailCol": "detail"},
                LogisticRegressionModel, ("extra", np.arange(48.0)))


@pytest.fixture(scope="module")
def bert(tmp_path_factory):
    from benchmark import gen

    with open(os.path.join(ROOT, "benchmark", "tests", "data", "configs",
                           "toy_cls.json")) as f:
        cfg = json.load(f)
    path = str(tmp_path_factory.mktemp("toy_ckpt"))
    gen.write_checkpoint(path, cfg, gen.make_weights(cfg, 7),
                         gen.make_vocab(cfg["vocab_size"]))
    train = MTable({"text": DOCS[:16], "label": [i % 2 for i in range(16)]})
    model = BertTextClassifierTrainBatchOp(
        textCol="text", labelCol="label", maxSeqLength=16, batchSize=8,
        numEpochs=1, learningRate=1e-3, checkpointFilePath=path,
        randomSeed=3).link_from(TableSourceBatchOp(train)).collect()
    return Kind(model, MTable({"text": DOCS[:8]}),
                BertTextClassifierPredictBatchOp,
                {"predictionCol": "pred", "predictionDetailCol": "detail"},
                BertTextClassifierModel, ("extra", np.arange(8.0)))


@pytest.fixture(params=["linear", "bert"])
def kind(request):
    return request.getfixturevalue(request.param)


class Counted:
    """Growth of the two counters over a ``with`` block."""

    def __enter__(self):
        self._at = self._read()
        return self

    def __exit__(self, *exc):
        now = self._read()
        self.loads, self.reuses = (n - a for n, a in zip(now, self._at))

    @staticmethod
    def _read():
        return (metrics.counter("mapper.model_loads"),
                metrics.counter("mapper.model_reuses"))


def rearm(op):
    """What ``LocalPredictor`` does to each op of its plan before a batch."""
    stack = [op]
    while stack:
        o = stack.pop()
        o._executed, o._output, o._side_tables = False, None, []
        stack.extend(o._inputs)


def test_same_table_schema_and_params_twice_is_one_load_and_one_reuse(kind):
    op = kind.op()
    with Counted() as c:
        first = op.collect()
        mapper = op._kept_mapper[3]
        rearm(op)
        second = op.collect()
    assert (c.loads, c.reuses) == (1, 1)
    assert op._kept_mapper[3] is mapper and op._kept_mapper[0] is kind.model
    assert first == second == kind.op().collect()


def _new_table(kind, op):
    copy = MTable({n: kind.model.col(n) for n in kind.model.names},
                  kind.model.schema)
    assert copy == kind.model and copy is not kind.model
    op._inputs[0]._table = copy
    return kind.op(model=copy)


def _changed_schema(kind, op):
    wider = kind.data.with_column(*kind.extra_col)
    op._inputs[1]._table = wider
    return kind.op(data=wider)


def _stamped_precision(kind, op):
    op.get_params().set(quant.PRECISION_KEY, "bf16")
    fresh = kind.op()
    fresh.get_params().set(quant.PRECISION_KEY, "bf16")
    return fresh


@pytest.mark.parametrize("change", [_new_table, _changed_schema,
                                    _stamped_precision])
def test_a_change_of_table_schema_or_params_loads_anew(kind, change):
    op = kind.op()
    op.collect()
    mapper = op._kept_mapper[3]
    fresh = change(kind, op)        # an op that never saw the first state
    rearm(op)
    with Counted() as c:
        got = op.collect()
    assert (c.loads, c.reuses) == (1, 0)
    assert op._kept_mapper[3] is not mapper
    assert got == fresh.collect()
    if change is _stamped_precision:
        assert op._kept_mapper[3]._policy == quant.BF16
        # the stamp taken off again (the fp32 fallback) is a load as well
        op.get_params().remove(quant.PRECISION_KEY)
        rearm(op)
        with Counted() as c:
            back = op.collect()
        assert (c.loads, c.reuses) == (1, 0)
        assert op._kept_mapper[3]._policy is None and back == kind.op().collect()
    rearm(op)
    with Counted() as c:            # and the new state is kept in its turn
        assert op.collect() == (back if change is _stamped_precision else got)
    assert (c.loads, c.reuses) == (0, 1)


def test_a_planned_predictor_loads_once_and_equals_a_plan_rebuilt_per_call(kind):
    planned, rebuilt = kind.predictor(), kind.predictor(cache_plan=False)
    n = kind.data.num_rows
    sizes = (n, 3, 1, n, 5)
    with Counted() as c:
        got = [planned.predict_table(kind.data.head(k)) for k in sizes]
    assert (c.loads, c.reuses) == (1, len(sizes) - 1)
    with Counted() as c:
        want = [rebuilt.predict_table(kind.data.head(k)) for k in sizes]
    assert (c.loads, c.reuses) == (len(sizes), 0)
    assert got == want
    if kind.op_cls is BertTextClassifierPredictBatchOp:
        details = [list(t.col("detail")) for t in got]
        assert details == [list(t.col("detail")) for t in want]
        # the probabilities are a document's own, whatever batch it came in
        assert details[1] == details[0][:3] and details[3] == details[0]


def test_threads_on_one_predictor_share_one_load(linear):
    planned = linear.predictor()
    want = linear.predictor(cache_plan=False).predict_table(linear.data)
    wrong, interval = [], sys.getswitchinterval()

    def work():
        for _ in range(10):
            if planned.predict_table(linear.data) != want:
                wrong.append(1)

    threads = [threading.Thread(target=work) for _ in range(16)]
    sys.setswitchinterval(1e-5)
    try:
        with Counted() as c:
            for t in threads:
                t.start()
            for t in threads:
                t.join(120)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads) and not wrong
    assert (c.loads, c.reuses) == (1, 159)


@pytest.mark.parametrize("precision", [None, "bf16", "int8"])
def test_the_prepared_form_places_nothing_and_equals_the_one_call_form(
        bert, precision, monkeypatch):
    from alink_tpu.dl.train import (PreparedParams, predict_model,
                                    prepare_params)

    monkeypatch.setenv("ALINK_TRACING", "on")
    op = bert.op()
    op.collect()
    mapper = op._kept_mapper[3]
    assert mapper.params is None and isinstance(mapper._placed, PreparedParams)
    fresh = type(mapper)(mapper.model_schema, mapper.data_schema,
                         mapper.get_params()).load_model(bert.model)
    tree, model = fresh.params, fresh.model
    batches = [fresh.tokenizer.encode_batch(DOCS[s:s + 8], None, max_len=16)
               for s in (0, 8, 16)]

    def placements():
        return sum(s["name"] == "dl.predict.place_params"
                   for s in tracer.spans())

    tracer.clear()
    one_call = [predict_model(model, tree, b, precision=precision)
                for b in batches]
    assert placements() == 3
    tracer.clear()
    prepared = prepare_params(model, tree, precision=precision)
    assert placements() == 1
    assert prepare_params(model, prepared) is prepared
    assert (prepared.apply.kernel_id == "dl.apply_logits.int8") \
        == (precision == "int8")
    kept = [predict_model(model, prepared, b) for b in batches]
    assert placements() == 1
    for a, b in zip(one_call, kept):
        assert a.dtype == b.dtype and a.tobytes() == b.tobytes()
    if precision is None:
        # the mapper's own prepared tree is the same values
        again = predict_model(model, mapper._placed, batches[0])
        assert again.tobytes() == one_call[0].tobytes()
    else:
        fp32 = predict_model(model, tree, batches[0])
        assert fp32.tobytes() != one_call[0].tobytes()


def test_another_mesh_places_the_prepared_tree_again(bert):
    import jax

    from alink_tpu.dl.train import predict_model, prepare_params
    from alink_tpu.parallel.mesh import default_mesh

    op = bert.op()
    op.collect()
    mapper = op._kept_mapper[3]
    fresh = type(mapper)(mapper.model_schema, mapper.data_schema,
                         mapper.get_params()).load_model(bert.model)
    enc = fresh.tokenizer.encode_batch(DOCS[:8], None, max_len=16)
    whole = prepare_params(fresh.model, fresh.params, precision="bf16")
    half = default_mesh(jax.devices()[:4])
    moved = prepare_params(fresh.model, whole, mesh=half)
    assert moved is not whole and moved.mesh == half
    assert moved.apply is whole.apply
    leaf = jax.tree_util.tree_leaves(moved.params)[0]
    assert set(leaf.sharding.device_set) <= set(jax.devices()[:4])
    want = predict_model(fresh.model, fresh.params, enc, mesh=half,
                         precision="bf16")
    assert predict_model(fresh.model, moved, enc, mesh=half).tobytes() \
        == want.tobytes()
