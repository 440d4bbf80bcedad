"""Text generation with a causal language model, and its continued training.

``CausalLMGenerateBatchOp`` continues each row's prompt by ``maxNewTokens``
tokens, greedily and with no stop token. The model is an HF-layout checkpoint
directory named by ``modelPath`` (``config.json``, sharded bfloat16
safetensors, ``vocab.txt``; ``alink_tpu.dl.lm`` has the block), read straight
to the device: billions of parameters do not travel as a model table, so this
is a ``MapBatchOp`` with a path, as the ingest ops of ``modelpredict.py`` are.

Columns in: ``selectedCol``, the prompt (STRING). Columns out, appended to the
reserved ones: ``predictionCol`` (STRING), the continuation's word pieces
joined into text; ``predictionDetailCol`` (STRING, optional), one JSON object
``{"prompt_tokens": n, "ids": [maxNewTokens ids], "logprobs": [maxNewTokens
log-probabilities, each of the id chosen at that step under the model's own
distribution]}``; of a model with expert layers also ``"expert_load"``: per
expert layer, how many of the row's tokens' assignments each expert held in
this process served.

The op keeps its mapper, the mapper its placed parameters and its state cache
(``stateSlots`` sequences): a ``LocalPredictor`` or ``ModelServer`` loads the
model in its first batch and never again. A table of more rows than
``stateSlots`` is generated in groups of that many. Rows of one group may
differ in prompt length; none of a row's answer depends on its neighbours.
A prompt of any length is taken whole where the model's memory is a state of
fixed size: the prefill program runs once per chunk of positions
(``dl.lm.PREFILL_CHUNK``). A model with layers whose cache grows with the
sequence (mla) has ``cachePositions`` positions a slot, sized at load; a row
whose prompt and new tokens pass them is refused.

``CausalLMTrainBatchOp`` continues the training of such a checkpoint on a
table's ``textCol``: the documents are tokenised with the checkpoint's
vocabulary, packed into rows of ``maxSeqLength`` tokens (``[SEP]`` after each
document, no padding, a row attended whole) and trained on through
``dl.train.train_model`` with AdamW as the BERT train op sets it, every
position's target the row's next token. It writes the trained model to
``outputPath`` in the layout it read (bfloat16 shards, ``config.json``,
``vocab.txt``), which ``CausalLMGenerateBatchOp`` serves, and returns one row
that names it: ``model_path`` and ``meta`` (JSON: steps, rows, tokens, each
epoch's last loss).
"""

from __future__ import annotations

import json
from typing import List

import numpy as np

from ...common.exceptions import AkIllegalArgumentException
from ...common.metrics import metrics
from ...common.quant import bf16_cast
from ...common.mtable import AlinkTypes, MTable, TableSchema
from ...common.params import MinValidator, ParamInfo
from ...common.tracing import trace_span
from ...mapper import (HasPredictionCol, HasPredictionDetailCol,
                       HasReservedCols, HasSelectedCol, Mapper)
from .base import BatchOperator
from .dl import HasDLTrainParams
from .utils import MapBatchOp


class HasCausalLMParams(HasSelectedCol, HasPredictionCol,
                        HasPredictionDetailCol, HasReservedCols):
    MODEL_PATH = ParamInfo(
        "modelPath", str, optional=False,
        desc="HF-layout checkpoint directory: config.json, safetensors "
        "(sharded or single), vocab.txt")
    MAX_NEW_TOKENS = ParamInfo(
        "maxNewTokens", int, default=128, validator=MinValidator(1),
        desc="tokens generated for every row (greedy, no stop token)")
    STATE_SLOTS = ParamInfo(
        "stateSlots", int, default=16, validator=MinValidator(1),
        desc="sequences the state cache holds, so the rows generated "
        "together; rounded up to a rung of the row ladder")
    CACHE_POSITIONS = ParamInfo(
        "cachePositions", int, default=4096, validator=MinValidator(1),
        desc="positions a slot's latent cache holds, prompt and new tokens "
        "together, where the model has layers whose cache grows (mla); "
        "allocated at load, and a longer row is refused")


class CausalLMGenerateMapper(Mapper, HasCausalLMParams):
    def __init__(self, data_schema=None, params=None, **kw):
        super().__init__(data_schema, params, **kw)
        self._lm = None
        self._tokenizer = None

    def _ensure_loaded(self):
        if self._lm is not None:
            return
        from ...dl.lm import load_causal_lm
        from ...dl.tokenizer import Tokenizer

        with trace_span("lm.load_model"):
            self._lm, vocab = load_causal_lm(
                self.get(self.MODEL_PATH), slots=self.get(self.STATE_SLOTS),
                positions=self.get(self.CACHE_POSITIONS))
            self._tokenizer = Tokenizer.from_list(vocab)
        metrics.incr("lm.model_loads")

    def output_schema(self, input_schema: TableSchema) -> TableSchema:
        names, types = [self.get(self.PREDICTION_COL)], [AlinkTypes.STRING]
        if self.get(self.PREDICTION_DETAIL_COL):
            names.append(self.get(self.PREDICTION_DETAIL_COL))
            types.append(AlinkTypes.STRING)
        return self._append_result_schema(input_schema, names, types)

    def _encode(self, text: str) -> List[int]:
        tok = self._tokenizer
        unk = tok.vocab["[UNK]"]
        return [tok.vocab.get(p, unk) for p in tok.tokenize(text)] or [unk]

    def _decode(self, ids) -> str:
        words: List[str] = []
        for i in ids:
            piece = self._tokenizer.inv[int(i)]
            if not piece.startswith("##"):
                words.append(piece)
            elif words:
                words[-1] += piece[2:]
            else:
                words.append(piece[2:])
        return " ".join(words)

    def map_table(self, t: MTable) -> MTable:
        col = self.get(self.SELECTED_COL)
        if not col:
            raise AkIllegalArgumentException("set selectedCol (the prompt)")
        self._ensure_loaded()
        pred, detail = self.get(self.PREDICTION_COL), \
            self.get(self.PREDICTION_DETAIL_COL)
        out_cols, out_types = {pred: []}, {pred: AlinkTypes.STRING}
        if detail:
            out_cols[detail], out_types[detail] = [], AlinkTypes.STRING
        if t.num_rows:
            with trace_span("lm.tokenize", rows=t.num_rows):
                prompts = [self._encode(str(v)) for v in t.col(col)]
            ids, logprobs = self._lm.generate(
                prompts, self.get(self.MAX_NEW_TOKENS))
            with trace_span("lm.detokenize", rows=t.num_rows):
                out_cols[pred] = [self._decode(row) for row in ids]
                if detail:
                    load = self._lm.expert_load
                    out_cols[detail] = [
                        json.dumps(dict(
                            {"prompt_tokens": len(p), "ids": row.tolist(),
                             "logprobs": np.round(
                                 lp.astype(np.float64), 6).tolist()},
                            **({} if load is None
                               else {"expert_load": load[i].tolist()})))
                        for i, (p, row, lp) in enumerate(zip(prompts, ids,
                                                             logprobs))]
        return self._append_result(t, out_cols, out_types)


class CausalLMGenerateBatchOp(MapBatchOp, HasCausalLMParams):
    __doc__ = __doc__

    mapper_cls = CausalLMGenerateMapper
    # a mapper with a model on the device and a state cache is no link of a
    # fused row-wise chain
    _fusable = False


class CausalLMTrainBatchOp(BatchOperator, HasDLTrainParams):
    """Continued training of a causal language model checkpoint on a text
    column (see the module's docstring)."""

    TEXT_COL = ParamInfo("textCol", str, optional=False)
    CHECKPOINT_FILE_PATH = ParamInfo(
        "checkpointFilePath", str, optional=False,
        desc="HF-layout checkpoint directory the training starts from")
    OUTPUT_PATH = ParamInfo(
        "outputPath", str, optional=False,
        desc="directory the trained checkpoint is written to, same layout")
    MAX_SEQ_LENGTH = ParamInfo("maxSeqLength", int, default=8192,
                               validator=MinValidator(2),
                               desc="tokens a packed row")

    _min_inputs = 1
    _max_inputs = 1

    def _execute_impl(self, t: MTable) -> MTable:
        import os
        import shutil

        import jax

        from ...dl import lm
        from ...dl.data import pack_rows
        from ...dl.pretrained import (load_vocab_file,
                                      write_safetensors_shards)
        from ...dl.tokenizer import Tokenizer
        from ...dl.train import TrainConfig, train_model

        src, out = self.get(self.CHECKPOINT_FILE_PATH), self.get(self.OUTPUT_PATH)
        seq = self.get(self.MAX_SEQ_LENGTH)
        with open(os.path.join(src, "config.json")) as f:
            cfg = lm.CausalLMConfig.from_hf(json.load(f))
        vocab_list = load_vocab_file(os.path.join(src, "vocab.txt"))
        if len(vocab_list) != cfg.vocab_size:
            raise AkIllegalArgumentException(
                f"vocab.txt has {len(vocab_list)} entries but the checkpoint "
                f"config says vocab_size={cfg.vocab_size} ({src})")
        tok = Tokenizer.from_list(vocab_list)
        unk, eod = tok.vocab["[UNK]"], tok.vocab["[SEP]"]
        with trace_span("train.tokenize", rows=t.num_rows):
            docs = [[tok.vocab.get(p, unk) for p in tok.tokenize(str(v))]
                    for v in t.col(self.get(self.TEXT_COL))]
        with trace_span("train.pack", documents=len(docs)):
            rows = pack_rows(docs, seq, eod)
        if not len(rows):
            raise AkIllegalArgumentException(
                f"the table's {sum(map(len, docs))} tokens fill no row of "
                f"{seq}")
        with trace_span("train.ingest_checkpoint", part="read"):
            variables = lm.training_variables(cfg, src)
        tc = TrainConfig(
            num_epochs=self.get(self.NUM_EPOCHS),
            batch_size=self.get(self.BATCH_SIZE),
            learning_rate=self.get(self.LEARNING_RATE),
            seed=self.get(self.RANDOM_SEED), weight_decay=0.01, loss="rows")
        lo, hi = cfg.experts_held
        expert_layers = cfg.ffn_types.count("experts")
        seen = np.zeros((expert_layers, max(cfg.num_experts, 1)), np.int64)
        steps_an_epoch = -(-len(rows) // min(tc.batch_size, len(rows)))

        def on_epoch(epoch, variables):
            # the one read an epoch of what the step counted on the device
            nonlocal seen
            total = np.asarray(variables["router"]["load"], np.int64)
            load, seen = total - seen, total
            metrics.incr("moe.assignments", int(load.sum()))
            metrics.incr("moe.assignments_held", int(load[:, lo:hi].sum()))
            for held in load[:, lo:hi]:
                if held.sum():
                    metrics.observe("moe.expert_load_max_over_mean",
                                    float(held.max() / held.mean()),
                                    buckets=lm.LOAD_BUCKETS)
            metrics.incr("train.tokens", len(rows) * seq)
            metrics.incr("moe.bias_updates", steps_an_epoch * expert_layers)

        variables, history = train_model(
            lm.CausalLMTrainer(cfg), {"tokens": rows},
            np.zeros(len(rows), np.int32), tc, mesh=self.env.mesh,
            init_params=variables, on_epoch=on_epoch)
        with trace_span("train.export_model"):
            os.makedirs(out, exist_ok=True)
            for name in ("config.json", "vocab.txt"):
                shutil.copyfile(os.path.join(src, name), os.path.join(out, name))
            written = write_safetensors_shards(out, (
                [(n, bf16_cast(a)) for n, a in shard]
                for shard in lm.hf_tensors(cfg, variables)))
        meta = {"steps": tc.num_epochs * steps_an_epoch, "rows": int(len(rows)),
                "tokens": int(rows.size), "epochLoss": history["loss"],
                "bytes": int(written), "pretrainedFrom": src}
        return MTable({"model_path": [out], "meta": [json.dumps(meta)]})
