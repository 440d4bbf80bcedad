"""DL subsystem (L8) — in-process JAX replaces the reference's entire
process-orchestration stack.

The reference forms a TF cluster inside Flink TaskManagers (reference:
core/src/main/java/com/alibaba/alink/common/dl/DLLauncherBatchOp.java:68,
DLRunner.java:61, flink-ai-extended gRPC node/AM services + mmap SpscOffHeapQueue
JVM<->Python data plane) and trains via TF Estimator (akdl/engine/train.py).
On TPU none of that machinery exists: data is already in host memory next to
the chips, the model is a flax module, and distribution is a `jax.sharding.Mesh`
with dp/tp/sp axes — the deliberate architectural deletion documented in
SURVEY.md §7.

Public surface:
- :mod:`modules`   — flax models: TransformerEncoder (BERT family), KerasSequential
- :mod:`attention` — full + ring (sequence-parallel) attention
- :mod:`sharding`  — parameter partition rules over the (data, model, seq) mesh
- :mod:`train`     — async device-fed optax train loop (ProgramCache step,
  donated buffers, bucketed batches), eval, checkpoints
- :mod:`pretrain`  — in-framework MLM pretraining producing HF-layout checkpoints
- :mod:`tokenizer` — WordPiece-style tokenizer with corpus-built vocab
- :mod:`data`      — loaders for the shipped real-text corpora + the
  block-scheduled streaming corpus iterator (:class:`~alink_tpu.dl.data.
  CorpusStream`) for corpora larger than host RAM
"""

from .attention import (blockwise_attention, full_attention,
                        ring_attention)
from .data import (CorpusStream, load_reviews, load_sst2, scheduled_order,
                   sst2_split)
from .modules import BertConfig, TransformerEncoder, KerasSequential, parse_layers
from .pretrain import pretrain_and_save, pretrain_mlm
from .sharding import param_shardings, make_dl_mesh
from .train import (TrainConfig, train_model, predict_model,
                    prepare_params)
from .tokenizer import Tokenizer

__all__ = [
    "BertConfig",
    "TransformerEncoder",
    "KerasSequential",
    "parse_layers",
    "blockwise_attention",
    "full_attention",
    "ring_attention",
    "param_shardings",
    "make_dl_mesh",
    "TrainConfig",
    "train_model",
    "predict_model",
    "prepare_params",
    "pretrain_mlm",
    "pretrain_and_save",
    "load_reviews",
    "load_sst2",
    "sst2_split",
    "CorpusStream",
    "scheduled_order",
    "Tokenizer",
]
