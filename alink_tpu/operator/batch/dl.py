"""DL train/predict operators: KerasSequential + BERT text classify/regress.

Capability parity:
- KerasSequentialClassifier/Regressor (reference: operator/batch/classification/
  KerasSequentialClassifierTrainBatchOp.java, regression/
  KerasSequentialRegressorTrainBatchOp.java → common/dl/
  BaseKerasSequentialTrainBatchOp.java:82 → DLLauncherBatchOp → akdl
  keras_sequential).
- BertTextClassifier/Regressor, pair variants (reference: operator/batch/
  classification/BertTextClassifierTrainBatchOp.java →
  BaseEasyTransferTrainBatchOp.java → akdl easytransfer).

TPU re-design: no DL launcher, no TF cluster, no mmap queue — the flax model
trains in-process on the mesh (dp over `data`, optional tp over `model`, ring
attention over `seq`). The trained model serializes into the standard model
table: flax params as msgpack bytes + tokenizer vocab + config JSON, so DL
models flow through the same .ak persistence / Pipeline machinery as every
classical model.
"""

from __future__ import annotations

from typing import Dict, List, Optional

import numpy as np

from ...common.exceptions import (AkIllegalArgumentException,
                                  AkIllegalDataException)
from ...common.model import model_to_table, table_to_model
from ...common.mtable import AlinkTypes, MTable
from ...common.params import InValidator, MinValidator, ParamInfo
from ...common.tracing import trace_span
from ...mapper import (
    HasFeatureCols,
    HasPredictionCol,
    HasPredictionDetailCol,
    HasReservedCols,
    HasVectorCol,
    RichModelMapper,
    detail_json,
    get_feature_block,
    merge_feature_params,
    np_labels,
    resolve_feature_cols,
    softmax_np,
)
from .base import BatchOperator
from .utils import ModelMapBatchOp, ModelTrainOpMixin


def _params_to_bytes(params) -> np.ndarray:
    from flax import serialization

    return np.frombuffer(serialization.to_bytes(params), dtype=np.uint8).copy()


def _params_from_bytes(buf: np.ndarray, template):
    from flax import serialization

    return serialization.from_bytes(template, buf.tobytes())


class HasDLTrainParams:
    NUM_EPOCHS = ParamInfo("numEpochs", int, default=10, validator=MinValidator(1))
    BATCH_SIZE = ParamInfo("batchSize", int, default=32, validator=MinValidator(1))
    LEARNING_RATE = ParamInfo("learningRate", float, default=1e-3)
    VALIDATION_SPLIT = ParamInfo("validationSplit", float, default=0.0)
    EARLY_STOPPING_PATIENCE = ParamInfo("earlyStoppingPatience", int, default=0)
    RANDOM_SEED = ParamInfo("randomSeed", int, default=0)


# ---------------------------------------------------------------------------
# KerasSequential
# ---------------------------------------------------------------------------


class BaseKerasSequentialTrainBatchOp(ModelTrainOpMixin, BatchOperator,
                                      HasDLTrainParams,
                                      HasFeatureCols, HasVectorCol):
    """(reference: common/dl/BaseKerasSequentialTrainBatchOp.java:82)"""

    LAYERS = ParamInfo("layers", list, optional=False,
                       desc='e.g. ["Dense(64)", "Relu()", "Dropout(0.1)"]')
    LABEL_COL = ParamInfo("labelCol", str, optional=False)

    _min_inputs = 1
    _max_inputs = 1

    _regression = False

    def _static_meta_keys(self, in_schema):
        return {
            "regression": self._regression,
            "labelType": in_schema.type_of(self.get(self.LABEL_COL)),
        }

    def _execute_impl(self, t: MTable) -> MTable:
        from ...dl.modules import KerasSequential
        from ...dl.train import TrainConfig, train_model

        label_col = self.get(self.LABEL_COL)
        vec_col = self.get(HasVectorCol.VECTOR_COL)
        feature_cols = (
            None if vec_col else resolve_feature_cols(t, self, exclude=[label_col])
        )
        X = get_feature_block(t, self, exclude=[label_col]).astype(np.float32)
        y_raw = t.col(label_col)

        if self._regression:
            y = np.asarray(y_raw, np.float32)
            labels, out_dim = None, 1
        else:
            labels = sorted(set(np.asarray(y_raw).tolist()), key=str)
            lab_to_idx = {v: i for i, v in enumerate(labels)}
            y = np.asarray([lab_to_idx[v] for v in y_raw], np.int32)
            out_dim = len(labels)

        model = KerasSequential(tuple(self.get(self.LAYERS)), out_dim=out_dim)
        cfg = TrainConfig(
            num_epochs=self.get(self.NUM_EPOCHS),
            batch_size=self.get(self.BATCH_SIZE),
            learning_rate=self.get(self.LEARNING_RATE),
            eval_ratio=self.get(self.VALIDATION_SPLIT),
            early_stopping_patience=self.get(self.EARLY_STOPPING_PATIENCE),
            seed=self.get(self.RANDOM_SEED),
        )
        params, history = train_model(
            model, {"x": X}, y, cfg, mesh=self.env.mesh,
            regression=self._regression, seq_axis=None,
        )
        meta = {
            "modelName": "KerasSequentialModel",
            "layers": list(self.get(self.LAYERS)),
            "outDim": out_dim,
            "regression": self._regression,
            "vectorCol": vec_col,
            "featureCols": feature_cols,
            "labelCol": label_col,
            "labelType": t.schema.type_of(label_col),
            "labels": labels,
            "dim": int(X.shape[1]),
            "finalLoss": history.get("final_loss"),
        }
        with trace_span("train.export_model"):
            return model_to_table(meta, {"params": _params_to_bytes(params)})


class KerasSequentialClassifierTrainBatchOp(BaseKerasSequentialTrainBatchOp):
    _regression = False


class KerasSequentialRegressorTrainBatchOp(BaseKerasSequentialTrainBatchOp):
    _regression = True


class KerasSequentialModelMapper(RichModelMapper, HasFeatureCols, HasVectorCol):
    def load_model(self, model: MTable):
        import jax

        from ...dl.modules import KerasSequential

        self.meta, arrays = table_to_model(model)
        self.model = KerasSequential(
            tuple(self.meta["layers"]), out_dim=int(self.meta["outDim"])
        )
        template = self.model.init(
            jax.random.PRNGKey(0), np.zeros((1, self.meta["dim"]), np.float32)
        )
        self.params = _params_from_bytes(arrays["params"], template)
        return self

    def _pred_type(self) -> str:
        if self.meta["regression"]:
            return AlinkTypes.DOUBLE
        return self.meta.get("labelType", AlinkTypes.STRING)

    def predict_block(self, t: MTable):
        from ...dl.train import predict_model

        meta = self.meta
        p = merge_feature_params(self.get_params(), meta)
        X = get_feature_block(t, p, vector_size=meta["dim"]).astype(np.float32)
        logits = predict_model(self.model, self.params, {"x": X}, seq_axis=None)
        detail = None
        if meta["regression"]:
            return logits[:, 0].astype(np.float64), AlinkTypes.DOUBLE, None
        probs = softmax_np(logits)
        idx = probs.argmax(axis=1)
        labels = meta["labels"]
        pred = np_labels(labels, meta.get("labelType", AlinkTypes.STRING), idx)
        if self.get(HasPredictionDetailCol.PREDICTION_DETAIL_COL):
            detail = detail_json(labels, probs)
        return pred, self._pred_type(), detail


class KerasSequentialClassifierPredictBatchOp(ModelMapBatchOp, HasPredictionCol,
                                              HasPredictionDetailCol,
                                              HasReservedCols):
    mapper_cls = KerasSequentialModelMapper


class KerasSequentialRegressorPredictBatchOp(ModelMapBatchOp, HasPredictionCol,
                                             HasReservedCols):
    mapper_cls = KerasSequentialModelMapper


# ---------------------------------------------------------------------------
# BERT text classifier / regressor
# ---------------------------------------------------------------------------


class BaseBertTextTrainBatchOp(ModelTrainOpMixin, BatchOperator, HasDLTrainParams):
    """(reference: common/dl/BaseEasyTransferTrainBatchOp.java; params
    params/tensorflow/bert/*)"""

    TEXT_COL = ParamInfo("textCol", str, optional=False)
    TEXT_PAIR_COL = ParamInfo("textPairCol", str)
    LABEL_COL = ParamInfo("labelCol", str, optional=False)
    MAX_SEQ_LENGTH = ParamInfo("maxSeqLength", int, default=128)
    VOCAB_SIZE = ParamInfo("vocabSize", int, default=8000)
    HIDDEN_SIZE = ParamInfo("hiddenSize", int, default=256)
    NUM_LAYERS = ParamInfo("numLayers", int, default=4)
    NUM_HEADS = ParamInfo("numHeads", int, default=4)
    INTERMEDIATE_SIZE = ParamInfo("intermediateSize", int, default=1024)
    BERT_SIZE = ParamInfo(
        "bertSize", str, default="custom",
        desc="custom (use hidden/layers params) | base | tiny",
    )
    SEQ_SHARDS = ParamInfo("seqShards", int, default=1,
                           desc="sequence-parallel shards (ring attention)")
    ATTENTION_BLOCK_SIZE = ParamInfo(
        "attentionBlockSize", int, default=0, validator=MinValidator(0),
        desc="0 = full attention; >0 = single-device memory-efficient "
             "blockwise attention with this K/V block (long documents "
             "beyond the reference's 512-token ceiling)")
    # pretrained ingest (reference: HasBertModelName + BertResources.java;
    # checkpoint consumed by BaseEasyTransferTrainBatchOp.java)
    BERT_MODEL_NAME = ParamInfo(
        "bertModelName", str,
        desc="pretrained model resolved from the plugin dir, e.g. "
             "'base-uncased' (see dl.pretrained.MODEL_NAME_DIRS)")
    CHECKPOINT_FILE_PATH = ParamInfo(
        "checkpointFilePath", str,
        desc="explicit pretrained checkpoint directory (HF layout or "
             "google-research TF ckpt); overrides bertModelName")
    POOLING_STRATEGY = ParamInfo(
        "poolingStrategy", str, default="auto",
        validator=InValidator("auto", "cls", "mean"),
        desc="auto | cls | mean — auto uses cls for pretrained checkpoints "
             "(the reference BERT pooler convention; NSP trains the CLS "
             "slot) and mean for from-scratch or NSP-less in-framework "
             "checkpoints")

    _min_inputs = 1
    _max_inputs = 1

    _regression = False

    def _static_meta_keys(self, in_schema):
        return {
            "regression": self._regression,
            "labelType": in_schema.type_of(self.get(self.LABEL_COL)),
        }

    def _resolve_pooling(self, pretrained: bool) -> str:
        """poolingStrategy with 'auto' resolved: cls for pretrained
        checkpoints (NSP trains the CLS slot), mean for in-framework /
        from-scratch models — exactly what the param doc promises."""
        pool = self.get(self.POOLING_STRATEGY)
        if pool == "auto":
            return "cls" if pretrained else "mean"
        return pool

    def _bert_config(self, vocab_size: int, num_labels: int):
        from ...dl.modules import BertConfig

        size = self.get(self.BERT_SIZE)
        common = dict(
            vocab_size=vocab_size,
            max_position=self.get(self.MAX_SEQ_LENGTH),
            num_labels=num_labels,
            regression=self._regression,
            pool=self._resolve_pooling(pretrained=False),
            use_ring_attention=self.get(self.SEQ_SHARDS) > 1,
            attention_block_size=self.get(self.ATTENTION_BLOCK_SIZE),
        )
        if size == "base":
            return BertConfig.base(**common)
        if size == "tiny":
            return BertConfig.tiny(**{**common, "vocab_size": vocab_size})
        return BertConfig(
            hidden_size=self.get(self.HIDDEN_SIZE),
            num_layers=self.get(self.NUM_LAYERS),
            num_heads=self.get(self.NUM_HEADS),
            intermediate_size=self.get(self.INTERMEDIATE_SIZE),
            **common,
        )

    def _resolve_pretrained(self):
        """Checkpoint dir from checkpointFilePath / bertModelName, or None."""
        path = self.get(self.CHECKPOINT_FILE_PATH)
        if path:
            return path
        name = self.get(self.BERT_MODEL_NAME)
        if not name:
            return None
        from ...dl.pretrained import resolve_bert_resource

        return resolve_bert_resource(name)

    def _execute_impl(self, t: MTable) -> MTable:
        from ...dl.modules import BertConfig, TransformerEncoder
        from ...dl.tokenizer import Tokenizer
        from ...dl.train import TrainConfig, train_model

        text_col = self.get(self.TEXT_COL)
        pair_col = self.get(self.TEXT_PAIR_COL)
        label_col = self.get(self.LABEL_COL)
        max_len = self.get(self.MAX_SEQ_LENGTH)

        texts = [str(v) for v in t.col(text_col)]
        pairs = [str(v) for v in t.col(pair_col)] if pair_col else None

        y_raw = t.col(label_col)
        if self._regression:
            y = np.asarray(y_raw, np.float32)
            labels, num_labels = None, 1
        else:
            labels = sorted(set(np.asarray(y_raw).tolist()), key=str)
            lab_to_idx = {v: i for i, v in enumerate(labels)}
            y = np.asarray([lab_to_idx[v] for v in y_raw], np.int32)
            num_labels = len(labels)

        pre_dir = self._resolve_pretrained()
        pre_subtree = None
        if pre_dir:
            from ...dl.pretrained import load_bert_checkpoint, load_vocab_file

            with trace_span("train.ingest_checkpoint", part="read"):
                ckpt_cfg, pre_subtree = load_bert_checkpoint(pre_dir)
            do_lower = ckpt_cfg.pop("do_lower_case", True)
            vocab_list = load_vocab_file(pre_dir)
            if len(vocab_list) != ckpt_cfg["vocab_size"]:
                # nn.Embed clamps out-of-range ids silently; a vocab/config
                # mismatch must fail loudly, not map words to the last row
                raise AkIllegalArgumentException(
                    f"vocab.txt has {len(vocab_list)} entries but the "
                    f"checkpoint config says vocab_size="
                    f"{ckpt_cfg['vocab_size']} ({pre_dir})")
            tok = Tokenizer.from_list(vocab_list, do_lower)
            if max_len > ckpt_cfg["max_position"]:
                raise AkIllegalArgumentException(
                    f"maxSeqLength={max_len} exceeds the pretrained "
                    f"checkpoint's max_position={ckpt_cfg['max_position']}")
            pool = self._resolve_pooling(pretrained=True)
            cfg = BertConfig(
                num_labels=num_labels, regression=self._regression,
                pool=pool, dropout=0.1,
                use_ring_attention=self.get(self.SEQ_SHARDS) > 1,
                attention_block_size=self.get(self.ATTENTION_BLOCK_SIZE),
                **ckpt_cfg)
        else:
            tok = Tokenizer.build(
                texts + (pairs or []), vocab_size=self.get(self.VOCAB_SIZE)
            )
            cfg = self._bert_config(tok.vocab_size, num_labels)
        with trace_span("train.tokenize", rows=len(texts)):
            enc = tok.encode_batch(texts, pairs, max_len=max_len)
        if cfg.use_ring_attention:
            # mesh with a seq axis for ring attention (dp fills the rest)
            from ...dl.sharding import make_dl_mesh

            mesh = make_dl_mesh(sp=self.get(self.SEQ_SHARDS))
        else:
            mesh = self.env.mesh
        model = TransformerEncoder(cfg, mesh=mesh if cfg.use_ring_attention else None)
        tc = TrainConfig(
            num_epochs=self.get(self.NUM_EPOCHS),
            batch_size=self.get(self.BATCH_SIZE),
            learning_rate=self.get(self.LEARNING_RATE),
            eval_ratio=self.get(self.VALIDATION_SPLIT),
            early_stopping_patience=self.get(self.EARLY_STOPPING_PATIENCE),
            seed=self.get(self.RANDOM_SEED),
            weight_decay=0.01,
        )
        init_params = None
        if pre_subtree is not None:
            from ...dl.pretrained import init_from_pretrained

            sample = {k: v[:1] for k, v in enc.items()}
            with trace_span("train.ingest_checkpoint", part="init"):
                init_params = init_from_pretrained(
                    model, cfg, pre_subtree, sample,
                    seed=self.get(self.RANDOM_SEED))
        params, history = train_model(
            model, enc, y, tc, mesh=mesh, regression=self._regression,
            init_params=init_params,
        )
        import dataclasses

        cfg_dict = {
            k: v for k, v in dataclasses.asdict(cfg).items() if k != "dtype"
        }
        meta = {
            "modelName": "BertTextModel",
            "bertConfig": cfg_dict,
            "textCol": text_col,
            "textPairCol": pair_col,
            "labelCol": label_col,
            "labelType": t.schema.type_of(label_col),
            "labels": labels,
            "regression": self._regression,
            "maxSeqLength": max_len,
            "vocab": tok.to_list(),
            "doLowerCase": tok.do_lower_case,
            "pretrainedFrom": pre_dir,
            "finalLoss": history.get("final_loss"),
        }
        with trace_span("train.export_model"):
            return model_to_table(meta, {"params": _params_to_bytes(params)})


class BertTextClassifierTrainBatchOp(BaseBertTextTrainBatchOp):
    _regression = False


class BertTextRegressorTrainBatchOp(BaseBertTextTrainBatchOp):
    _regression = True


class BertTextPairClassifierTrainBatchOp(BaseBertTextTrainBatchOp):
    _regression = False
    TEXT_PAIR_COL = ParamInfo("textPairCol", str, optional=False)


class BertTextModelMapper(RichModelMapper):
    TEXT_COL = ParamInfo("textCol", str)
    TEXT_PAIR_COL = ParamInfo("textPairCol", str)

    def load_model(self, model: MTable):
        import jax
        import jax.numpy as jnp

        from ...dl.modules import BertConfig, TransformerEncoder
        from ...dl.tokenizer import Tokenizer

        self.meta, arrays = table_to_model(model)
        cfg = BertConfig(dtype=jnp.bfloat16, **self.meta["bertConfig"])
        self.cfg = cfg
        self.model = TransformerEncoder(cfg)
        # models serialized before the BERT-spec tokenizer carry no
        # doLowerCase key; serve them with the legacy \w+ tokenization their
        # vocab was built with
        self.tokenizer = Tokenizer.from_list(
            self.meta["vocab"], self.meta.get("doLowerCase", True),
            legacy="doLowerCase" not in self.meta)
        max_len = int(self.meta["maxSeqLength"])
        sample = {
            "input_ids": np.zeros((1, max_len), np.int32),
            "attention_mask": np.ones((1, max_len), np.int32),
            "token_type_ids": np.zeros((1, max_len), np.int32),
        }
        template = self.model.init(jax.random.PRNGKey(0), **sample)
        self.params = _params_from_bytes(arrays["params"], template)
        from ...common import quant

        self._policy = quant.policy_of(self.get_params())
        self._placed = None
        return self

    def _pred_type(self) -> str:
        if self.meta["regression"]:
            return AlinkTypes.DOUBLE
        return self.meta.get("labelType", AlinkTypes.STRING)

    def predict_block(self, t: MTable):
        from ...dl.train import PREDICT_SLICE, predict_model, prepare_params

        meta = self.meta
        text_col = self.get(self.TEXT_COL) or meta["textCol"]
        pair_col = self.get(self.TEXT_PAIR_COL) or meta.get("textPairCol")
        n = t.num_rows
        encoded = self.tokenizer.encode_slices(
            t.col(text_col), t.col(pair_col) if pair_col else None,
            max_len=int(meta["maxSeqLength"]), rows=PREDICT_SLICE)

        def slices():
            # predict_model asks for a slice once the forward of the one
            # before is on its way: the rows are tokenised under it. One
            # span a slice, a leaf beside dl.predict.apply
            for s in range(0, n, PREDICT_SLICE):
                with trace_span("bert.tokenize",
                                rows=min(PREDICT_SLICE, n - s)):
                    enc = next(encoded)
                yield enc
            # asked once more when the last slice's forward is on its way:
            # the call's memo (tens of thousands of words a batch) is dropped
            # under that forward, not after the answer
            with trace_span("bert.tokenize", rows=0):
                encoded.close()

        # the first predict applies the policy and places the parameters;
        # from then on they stay where the forward program takes them (only
        # another default mesh places them again) and the host tree goes
        self._placed = prepare_params(
            self.model, self.params if self._placed is None else self._placed,
            precision=self._policy)
        self.params = None
        logits = predict_model(self.model, self._placed, slices())
        with trace_span("bert.postprocess"):
            if meta["regression"]:
                return (logits[:, 0].astype(np.float64), AlinkTypes.DOUBLE,
                        None)
            probs = softmax_np(logits)
            idx = probs.argmax(axis=1)
            labels = meta["labels"]
            pred = np_labels(labels,
                             meta.get("labelType", AlinkTypes.STRING), idx)
            detail = None
            if self.get(HasPredictionDetailCol.PREDICTION_DETAIL_COL):
                detail = detail_json(labels, probs)
            return pred, self._pred_type(), detail


class BertTextClassifierPredictBatchOp(ModelMapBatchOp, HasPredictionCol,
                                       HasPredictionDetailCol, HasReservedCols):
    mapper_cls = BertTextModelMapper


class BertTextRegressorPredictBatchOp(ModelMapBatchOp, HasPredictionCol,
                                      HasReservedCols):
    mapper_cls = BertTextModelMapper
