"""The real-text BERT story on the shipped corpora, end to end:

1. MLM-pretrain a tiny encoder on ``data/reviews_unlabeled.txt`` (async
   device-fed loop, per-epoch checkpoints with crash-resume);
2. export it as an HF-layout checkpoint dir (config.json +
   model.safetensors + vocab.txt);
3. fine-tune through ``BertTextClassifierTrainBatchOp`` with
   ``checkpointFilePath`` on the ``data/sst2_mini.csv`` train split;
4. report holdout accuracy on the held-out rows (``dl.data.sst2_split``,
   the split ``tests/test_train_async.py`` pins).

A CPU demo: runs in a few minutes at this size; the full-budget version is
``tests/test_train_async.py::test_pretrain_finetune_real_text_e2e``.
"""

import argparse
import os
import tempfile
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--reviews", type=int, default=1500,
                    help="pretraining sentences (0 = full corpus)")
    ap.add_argument("--epochs", type=int, default=3, help="MLM epochs")
    ap.add_argument("--finetune-epochs", type=int, default=8)
    ap.add_argument("--checkpoint-dir", default=None,
                    help="pretrain checkpoint dir (enables crash-resume); "
                         "default: a temp dir")
    args = ap.parse_args()

    from alink_tpu.common.mtable import MTable
    from alink_tpu.dl.data import load_reviews, sst2_split
    from alink_tpu.dl.pretrain import pretrain_and_save
    from alink_tpu.operator.batch.base import TableSourceBatchOp
    from alink_tpu.operator.batch.dl import (
        BertTextClassifierPredictBatchOp, BertTextClassifierTrainBatchOp)

    stage = args.checkpoint_dir or tempfile.mkdtemp(prefix="alink_bert_pre_")

    # -- 1+2: pretrain on the unlabeled reviews, export HF layout ---------
    t0 = time.perf_counter()
    texts = load_reviews(limit=args.reviews or None)
    summary = pretrain_and_save(
        texts, stage, vocab_size=2000, hidden_size=96, num_layers=2,
        num_heads=4, intermediate_size=192, max_len=32, epochs=args.epochs,
        batch_size=64, learning_rate=3e-4, seed=0,
        # feed="async" is the default: masking + transfers run on the
        # transfer pool, double-buffered ahead of the jitted MLM step
        checkpoint_dir=os.path.join(stage, "_resume"))
    print(f"[1] pretrained on {len(texts)} sentences in "
          f"{time.perf_counter() - t0:.1f}s — MLM loss "
          f"{summary['initial_loss']} -> {summary['final_loss']}")
    print(f"[2] HF checkpoint at {stage}: "
          f"{sorted(f for f in os.listdir(stage) if not f.startswith('_'))}")

    # -- 3: fine-tune from the checkpoint on the sst2 train split ---------
    t1 = time.perf_counter()
    tr_t, tr_y, ho_t, ho_y = sst2_split(seed=0)
    model = BertTextClassifierTrainBatchOp(
        textCol="text", labelCol="label", checkpointFilePath=stage,
        maxSeqLength=32, numEpochs=args.finetune_epochs, batchSize=32,
        learningRate=5e-4, randomSeed=0,
        poolingStrategy="mean",  # NSP-less checkpoint: CLS slot untrained
    ).link_from(TableSourceBatchOp(MTable({"text": tr_t, "label": tr_y})))

    # -- 4: holdout accuracy on rows neither stage ever saw ---------------
    pred = BertTextClassifierPredictBatchOp(predictionCol="pred").link_from(
        model, TableSourceBatchOp(MTable({"text": ho_t, "label": ho_y}))
    ).collect()
    acc = float((np.asarray(pred.col("pred")) == ho_y).mean())
    print(f"[3] fine-tuned on {len(tr_t)} rows in "
          f"{time.perf_counter() - t1:.1f}s")
    print(f"[4] real-text holdout accuracy on {len(ho_t)} rows: {acc:.4f} "
          f"(coin flip = 0.50)")


if __name__ == "__main__":
    main()
