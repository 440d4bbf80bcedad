"""BERT training hot path (dl/train.py + dl/pretrain.py): async device-fed
loop vs the synchronous reference feed (bit-identity), ProgramCache-resident
train step (zero steady-state retraces, cross-job program sharing, preserved
buffer donation), exact zero-weight tail padding, and the real-text
pretrain -> checkpoint -> fine-tune story on the shipped corpora.

Counters are process-monotonic (jit.trace / jit.program_hit), so every
assertion here measures DELTAS — tests stay order-independent."""

import numpy as np
import pytest

from alink_tpu.common.metrics import metrics

pytestmark = pytest.mark.training


def _traces() -> int:
    return metrics.counter("jit.trace")


def _tree_equal(a, b) -> bool:
    import jax

    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    return len(la) == len(lb) and all(
        bool(np.array_equal(np.asarray(x), np.asarray(y)))
        for x, y in zip(la, lb))


def _xor_data(n=300, d=6, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, d)).astype(np.float32)
    y = ((X[:, 0] > 0) ^ (X[:, 1] > 0)).astype(np.int32)
    return X, y


def _mlp(h1=12, h2=7):
    from alink_tpu.dl.modules import KerasSequential

    return KerasSequential(
        (f"Dense({h1}, activation=relu)", f"Dense({h2}, activation=relu)"),
        out_dim=2)


# ---------------------------------------------------------------------------
# async feed == sync feed, bit for bit
# ---------------------------------------------------------------------------

def test_async_feed_bit_identical_to_sync():
    from alink_tpu.dl.train import TrainConfig, train_model

    X, y = _xor_data()
    model = _mlp(12, 7)
    # batch 100 -> bs 96 on dp=8, tail of 12 rows pads to the bucket: the
    # parity covers full batches AND the zero-weight padded tail
    pa, ha = train_model(model, {"x": X}, y,
                         TrainConfig(num_epochs=2, batch_size=100, seed=3,
                                     feed="async"), seq_axis=None)
    ps, hs = train_model(model, {"x": X}, y,
                         TrainConfig(num_epochs=2, batch_size=100, seed=3,
                                     feed="sync"), seq_axis=None)
    assert _tree_equal(pa, ps)
    assert ha["loss"] == hs["loss"]
    assert ha["feed"]["mode"] == "async"
    assert ha["feed"]["batches"] == 2 * -(-len(y) // 96)


def test_feed_rejects_unknown_mode():
    from alink_tpu.dl.train import _feed

    with pytest.raises(ValueError):
        list(_feed(lambda s: [np.zeros(1)], lambda a: a, 1, mode="turbo"))


# ---------------------------------------------------------------------------
# steady-state zero retraces + cross-job program sharing
# ---------------------------------------------------------------------------

def test_steady_loop_zero_traces_and_shared_program():
    from alink_tpu.dl.train import TrainConfig, train_model

    X, y = _xor_data(n=280)
    cfg = TrainConfig(num_epochs=3, batch_size=64, seed=0, feed="async")
    t0 = _traces()
    train_model(_mlp(11, 5), {"x": X}, y, cfg, seq_axis=None)
    first_job = _traces() - t0
    # one trace for the train step — the padded tail batch reuses the
    # full-batch program (shape-bucketed), every later step is warm
    assert first_job == 1, first_job

    # an independent job of the SAME config family (fresh model/optimizer
    # instances) must reuse the compiled program: zero new traces
    h0 = metrics.counter("jit.program_hit")
    t1 = _traces()
    train_model(_mlp(11, 5), {"x": X}, y, cfg, seq_axis=None)
    assert _traces() - t1 == 0
    assert metrics.counter("jit.program_hit") > h0


def test_train_step_donation_preserved():
    """The cached step still donates params/opt_state: the lowered HLO
    carries input->output aliasing (the ProgramCache migration must not
    silently drop `donate_argnums`)."""
    import jax
    import optax

    from alink_tpu.dl.train import _loss_fn, make_train_step

    model = _mlp(9, 4)
    X = np.zeros((16, 6), np.float32)
    y = np.zeros(16, np.int32)
    params = model.init(jax.random.PRNGKey(0), x=X[:1], deterministic=True)
    tx = optax.adamw(1e-3)
    opt = tx.init(params["params"])
    step = make_train_step(model, tx, _loss_fn("softmax", False))
    lowered = step.lower(params, opt, {"x": X}, y)
    # donated params/opt_state lower to input->output buffer aliases
    assert "tf.aliasing_output" in lowered.as_text()


# ---------------------------------------------------------------------------
# zero-weight tail padding is exact
# ---------------------------------------------------------------------------

def test_weighted_loss_matches_unweighted_on_real_rows():
    from alink_tpu.dl.train import _loss_fn

    rng = np.random.default_rng(0)
    logits = rng.normal(size=(8, 3)).astype(np.float32)
    y = rng.integers(0, 3, 8).astype(np.int32)
    for kind, reg in (("softmax", False), ("mse", True),
                      ("gaussian_nll", True)):
        lo = logits[:, :1] if kind == "mse" else \
            logits[:, :2] if kind == "gaussian_nll" else logits
        plain = _loss_fn(kind, reg)(lo, y)
        # padded batch: real rows weight 1, pad rows (copies) weight 0
        pad_lo = np.concatenate([lo, lo[-2:]])
        pad_y = np.concatenate([y, y[-2:]])
        w = np.concatenate([np.ones(8, np.float32), np.zeros(2, np.float32)])
        weighted = _loss_fn(kind, reg, weighted=True)(pad_lo, pad_y, w)
        assert float(plain) == pytest.approx(float(weighted), abs=0.0), kind


def test_pad_tail_repeats_last_row():
    from alink_tpu.dl.train import _pad_tail

    a = np.arange(6, dtype=np.float32).reshape(3, 2)
    (p,) = _pad_tail([a], 5)
    assert p.shape == (5, 2)
    assert np.array_equal(p[:3], a)
    assert np.array_equal(p[3], a[-1]) and np.array_equal(p[4], a[-1])
    assert _pad_tail([a], 3)[0] is a


# ---------------------------------------------------------------------------
# MLM pretraining: feed parity, checkpoint/resume, program residency
# ---------------------------------------------------------------------------

def _tiny_pretrain(texts, **kw):
    from alink_tpu.dl.pretrain import pretrain_mlm

    args = dict(vocab_size=300, hidden_size=32, num_layers=1, num_heads=2,
                intermediate_size=64, max_len=24, epochs=2, batch_size=32,
                seed=0)
    args.update(kw)
    return pretrain_mlm(texts, **args)


def test_pretrain_async_matches_sync_and_learns():
    from alink_tpu.dl.data import load_reviews

    texts = load_reviews(limit=96)
    _, pa, _, ha = _tiny_pretrain(texts, feed="async")
    _, ps, _, hs = _tiny_pretrain(texts, feed="sync")
    assert _tree_equal(pa, ps)
    assert ha == hs
    assert ha[-1] < ha[0]  # the MLM objective moves


def test_train_model_resume_replays_exact_schedule(tmp_path, monkeypatch):
    """Crash-resume on the fine-tune loop: epoch shuffles come from
    per-(seed, epoch) generators, so a run crashed right after the epoch-1
    checkpoint and resumed trains epochs 2..3 on the SAME batch orders the
    uninterrupted run used — params land bit-identical."""
    from alink_tpu.dl import checkpoint as ckpt_mod
    from alink_tpu.dl.train import TrainConfig, train_model

    X, y = _xor_data(n=200)
    kw = dict(num_epochs=4, batch_size=64, seed=5, eval_ratio=0.0)
    straight, _ = train_model(_mlp(10, 4), {"x": X}, y, TrainConfig(**kw),
                              seq_axis=None)

    d = str(tmp_path / "ckpt")
    real_save = ckpt_mod.TrainCheckpointManager.save
    saves = {"n": 0}

    def crashing_save(self, step, params, opt_state, extra):
        real_save(self, step, params, opt_state, extra)
        saves["n"] += 1
        if saves["n"] == 2:
            raise RuntimeError("injected crash after epoch-1 checkpoint")

    monkeypatch.setattr(ckpt_mod.TrainCheckpointManager, "save",
                        crashing_save)
    with pytest.raises(RuntimeError, match="injected crash"):
        train_model(_mlp(10, 4), {"x": X}, y,
                    TrainConfig(checkpoint_dir=d, **kw), seq_axis=None)
    monkeypatch.setattr(ckpt_mod.TrainCheckpointManager, "save", real_save)

    resumed, hist = train_model(_mlp(10, 4), {"x": X}, y,
                                TrainConfig(checkpoint_dir=d, **kw),
                                seq_axis=None)
    assert _tree_equal(straight, resumed)
    assert len(hist["loss"]) == 2  # only epochs 2..3 ran after resume


def test_pretrain_checkpoint_resume_bit_identical(tmp_path):
    from alink_tpu.dl.data import load_reviews

    texts = load_reviews(limit=64)
    _, straight, _, _ = _tiny_pretrain(texts, epochs=2)
    d = str(tmp_path / "ckpt")
    _tiny_pretrain(texts, epochs=1, checkpoint_dir=d)
    _, resumed, _, hist = _tiny_pretrain(texts, epochs=2, checkpoint_dir=d)
    assert _tree_equal(straight, resumed)
    assert len(hist) == 1  # only the second epoch ran after resume


# ---------------------------------------------------------------------------
# the real-text story: pretrain -> HF checkpoint -> fine-tune via the op
# ---------------------------------------------------------------------------

def _finetune_acc(ckpt_dir, tr_t, tr_y, ho_t, ho_y, **kw):
    from alink_tpu.common.mtable import MTable
    from alink_tpu.operator.batch.base import TableSourceBatchOp
    from alink_tpu.operator.batch.dl import (
        BertTextClassifierPredictBatchOp, BertTextClassifierTrainBatchOp)

    args = dict(textCol="text", labelCol="label",
                checkpointFilePath=ckpt_dir, maxSeqLength=24, numEpochs=3,
                batchSize=32, learningRate=5e-4, randomSeed=0,
                poolingStrategy="mean")
    args.update(kw)
    m = BertTextClassifierTrainBatchOp(**args).link_from(
        TableSourceBatchOp(MTable({"text": tr_t, "label": tr_y})))
    pred = BertTextClassifierPredictBatchOp(predictionCol="p").link_from(
        m, TableSourceBatchOp(MTable({"text": ho_t, "label": ho_y}))
    ).collect()
    return float((np.asarray(pred.col("p")) == np.asarray(ho_y)).mean())


def test_pretrain_finetune_real_text_smoke(tmp_path):
    """Fast tier-1 drill of the full story on the shipped corpora:
    reviews MLM pretrain -> HF-layout checkpoint on disk -> the BERT op
    ingests it via checkpointFilePath -> holdout predictions on sst2."""
    from alink_tpu.dl.data import load_reviews, sst2_split
    from alink_tpu.dl.pretrain import pretrain_and_save

    d = str(tmp_path / "pre")
    summary = pretrain_and_save(
        load_reviews(limit=192), d, vocab_size=400, hidden_size=32,
        num_layers=1, num_heads=2, intermediate_size=64, max_len=24,
        epochs=6, batch_size=32, seed=0)
    # epoch means over freshly masked batches are noisy (the first two
    # read 6.070 and 6.075): compare across enough epochs for the trend to
    # clear that noise
    assert summary["final_loss"] < summary["initial_loss"] - 0.1

    tr_t, tr_y, ho_t, ho_y = sst2_split(seed=0)
    acc = _finetune_acc(d, tr_t[:128], tr_y[:128], ho_t[:64], ho_y[:64])
    assert 0.0 <= acc <= 1.0
    # a learning signal even under the tiny budget: clear of degenerate
    # single-class collapse on the balanced holdout
    assert acc >= 0.4, acc


@pytest.mark.slow
def test_pretrain_finetune_real_text_e2e(tmp_path):
    """The full-budget configuration: full
    reviews corpus, 5 MLM epochs, 14 fine-tune epochs — real-text holdout
    accuracy must clearly beat the 0.5 coin-flip floor."""
    from alink_tpu.dl.data import load_reviews, sst2_split
    from alink_tpu.dl.pretrain import pretrain_and_save

    d = str(tmp_path / "pre")
    pretrain_and_save(
        load_reviews(), d, vocab_size=2000, hidden_size=96, num_layers=2,
        num_heads=4, intermediate_size=192, max_len=32, epochs=5,
        batch_size=64, learning_rate=3e-4, seed=0)
    tr_t, tr_y, ho_t, ho_y = sst2_split(seed=0)
    acc = _finetune_acc(d, tr_t, tr_y, ho_t, ho_y, maxSeqLength=32,
                        numEpochs=14)
    assert acc >= 0.65, acc
