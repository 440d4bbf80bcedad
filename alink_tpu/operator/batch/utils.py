"""Generic mapper-wrapping batch operators.

Capability parity with reference operator/batch/utils/ModelMapBatchOp.java:62
(model broadcast at :64,175) and MapBatchOp.java. The model "broadcast" is
trivial here — the mapper loads the model MTable once and the batched jit
kernel is replicated by XLA as needed.

"Once" holds across executes of one op: a ``ModelMapBatchOp`` keeps the mapper
it last loaded, and runs the next execute through it when the model table is
the same object, the data schema is equal and the op's params read as they
did at the load. A batch DAG executes each op once and sees no difference; a
``LocalPredictor`` plan, whose ops live as long as the predictor, loads on its
first batch and never again. Anything else (another table object, another
schema, a param set or removed since) loads anew and takes the slot, so the
contract on a mapper is the reference's: ``load_model`` once, ``map_table``
many times, and ``map_table`` leaves alone what ``load_model`` set up.
"""

from __future__ import annotations

from typing import Type

from ...common.exceptions import AkIllegalOperationException
from ...common.metrics import metrics
from ...common.model import MODEL_SCHEMA
from ...common.mtable import MTable, TableSchema
from ...common.tracing import trace_span
from ..base import AlgoOperator
from .base import BatchOperator


class MapBatchOp(BatchOperator):
    """Wrap a stateless Mapper class as an operator."""

    _min_inputs = 1
    _max_inputs = 1

    mapper_cls: Type = None

    # mapper-chain fusion contract (common/executor.py): linear runs of
    # mapper ops collapse into one scheduled FusedMapperChain unit; the data
    # edge is input[_fusion_data_index], and _fusion_mapper builds the ready-
    # to-run mapper once upstream deps are evaluated. Ops whose mapper is
    # side-effectful or not row-wise set _fusable = False.
    _fusable = True
    _fusion_data_index = 0

    def __init__(self, params=None, **kwargs):
        super().__init__(params, **kwargs)

    def _fusion_mapper(self, data_schema):
        return self._make_mapper(data_schema)

    def _make_mapper(self, data_schema):
        # cached per input schema: foreign-model mappers (modelpredict) load
        # and convert whole model files, so schema access + execute must
        # share one instance
        key = data_schema.to_str()
        cached = getattr(self, "_mapper_cache", None)
        if cached is not None and cached[0] == key:
            return cached[1]
        mapper = self.mapper_cls(data_schema, self.get_params())
        self._mapper_cache = (key, mapper)
        return mapper

    def _execute_impl(self, t: MTable) -> MTable:
        return self._make_mapper(t.schema).map_table(t)

    def _out_schema(self, in_schema: TableSchema) -> TableSchema:
        return self._make_mapper(in_schema).output_schema(in_schema)


class ModelMapBatchOp(BatchOperator):
    """Wrap a ModelMapper class; ``link_from(model_op, data_op)``."""

    _min_inputs = 2
    _max_inputs = 2

    mapper_cls: Type = None

    _fusable = True
    _fusion_data_index = 1  # input[0] is the model table

    def __init__(self, params=None, **kwargs):
        super().__init__(params, **kwargs)

    def _make_mapper(self, model_schema, data_schema):
        return self.mapper_cls(model_schema, data_schema, self.get_params())

    def _loaded_mapper(self, model: MTable, data_schema):
        # one slot: the mapper last loaded and what it was loaded for. The
        # slot holds the model table itself, so its identity cannot be
        # recycled; the params are kept as they read at the load, because a
        # mapper takes its settings in load_model (ModelServer stamps the
        # precision policy onto a plan's ops after the plan exists)
        with self._eval_lock:
            params = self.get_params().to_json()
            kept = getattr(self, "_kept_mapper", None)
            if (kept is not None and kept[0] is model
                    and kept[1] == data_schema and kept[2] == params):
                metrics.incr("mapper.model_reuses")
                return kept[3]
            mapper = self._make_mapper(model.schema, data_schema)
            with trace_span("mapper.load_model", mapper=type(mapper).__name__):
                mapper.load_model(model)
            metrics.incr("mapper.model_loads")
            self._kept_mapper = (model, data_schema, params, mapper)
            return mapper

    def _fusion_mapper(self, data_schema):
        # deps are evaluated before a fused unit runs, so the model read is
        # a memoized fetch — same load path as _execute_impl
        return self._loaded_mapper(self._inputs[0]._evaluate(), data_schema)

    def _execute_impl(self, model: MTable, t: MTable) -> MTable:
        mapper = self._loaded_mapper(model, t.schema)
        with trace_span("mapper.map_table", mapper=type(mapper).__name__):
            return mapper.map_table(t)

    def _out_schema(self, model_schema: TableSchema,
                    data_schema: TableSchema) -> TableSchema:
        # the mapper's schema decisions (pred type etc.) read model meta;
        # model-producing ops declare it statically (reference analog:
        # ModelMapper.prepareIoSchema works off the model *schema* alone)
        meta = self._inputs[0]._static_model_meta() if self._inputs else None
        mapper = self._make_mapper(model_schema, data_schema)
        if meta is not None:
            mapper.meta = meta
        try:
            return mapper.output_schema(data_schema)
        except (AttributeError, KeyError) as e:
            raise AkIllegalOperationException(
                f"{type(self).__name__}: static schema needs model meta that "
                f"{type(self._inputs[0]).__name__ if self._inputs else '?'} "
                f"does not declare ({e!r})"
            ) from e


class ModelTrainOpMixin:
    """Train ops emit the canonical model table; schema is a constant.

    Static model meta: once executed the real meta row wins; before that,
    ``_static_meta_keys(in_schema)`` supplies the keys the paired
    ModelMapper's schema decisions need (labelType etc.)."""

    def _out_schema(self, *in_schemas: TableSchema) -> TableSchema:
        return MODEL_SCHEMA

    def _static_model_meta(self):
        meta = AlgoOperator._static_model_meta(self)
        if meta is not None:
            return meta
        in_schema = self._inputs[0]._static_schema() if self._inputs else None
        return self._static_meta_keys(in_schema)

    def _static_meta_keys(self, in_schema: TableSchema) -> dict:
        return {}


class TrainInfoBatchOp(BatchOperator):
    """(name, value) rows of the scalar training diagnostics stored in a
    model's meta — loss, gradNorm, numIters, inertia, logLikelihood, ...
    (reference: the per-algorithm *TrainInfoBatchOp / *ModelInfoBatchOp
    family, e.g. operator/batch/classification/LogisticRegressionTrainInfo
    via lazyPrintTrainInfo, operator/batch/clustering/KMeansModelInfoBatchOp)."""

    _min_inputs = 1
    _max_inputs = 1

    def _execute_impl(self, model: MTable) -> MTable:
        from ...common.model import table_to_model
        from ...common.mtable import AlinkTypes
        import numpy as np

        meta, _ = table_to_model(model)
        rows = [(k, float(v)) for k, v in sorted(meta.items())
                if isinstance(v, (int, float)) and not isinstance(v, bool)]
        return MTable(
            {"name": np.asarray([r[0] for r in rows], object),
             "value": np.asarray([r[1] for r in rows], np.float64)},
            self._out_schema())

    def _out_schema(self, *in_schemas) -> TableSchema:
        from ...common.mtable import AlinkTypes

        return TableSchema(["name", "value"],
                           [AlinkTypes.STRING, AlinkTypes.DOUBLE])


class LinearModelTrainInfoBatchOp(TrainInfoBatchOp):
    pass
