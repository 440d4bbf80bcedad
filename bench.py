"""Benchmark driver. Prints ONE JSON line:
{"metric": ..., "value": N, "unit": ..., "vs_baseline": N, "extras": {...}}

Primary metric (north star, BASELINE.json): BERT-base fine-tune training
throughput in samples/sec/chip, seq len 128, batch 32, bf16 compute — this
framework's flagship path (flax TransformerEncoder + optax adamw, one jit).
vs_baseline compares against the commonly reported A100 BERT-base fine-tune
figure of ~210 samples/sec (seq128, fp16, bs32) — the driver-named target;
the reference itself publishes no numbers ("published": {}).

"extras" carries every other measurable BASELINE config:
- #1 kmeans_iris: Pipeline fit+transform wall-clock on an iris-shaped table
  (150x4, 3 clusters) + cluster quality.
- #2 softmax_mnist: SoftmaxTrainBatchOp (L-BFGS, one compiled program) on
  MNIST-shaped data (784 features, 10 classes) — samples/sec + accuracy.
- #3 resnet50_predict: ResNet-50 (defined in torch, ingested via
  torch.export -> StableHLO -> jit) batch inference rows/sec;
  resnet50_savedmodel is the metric-of-record TF SavedModel path
  (SavedModelBundle replacement), on-device rows/sec at bf16 + fp32.
- #5 torch_stream_predict: TorchModelPredictStreamOp rows/sec on a micro-
  batch stream.
- gbdt_train: histogram GBDT training throughput (riskiest perf item).
- bert_text_quality: REAL-TEXT holdout accuracy (the metric of record since
  r6): MLM pretrain on data/reviews_unlabeled.txt -> HF checkpoint ->
  fine-tune on the data/sst2_mini.csv train split -> holdout accuracy.
- bert_mfu: achieved TFLOPs/chip + MFU for the primary metric, plus the
  in-process gates: mfu vs the recorded floor (MFU_FLOOR), async-vs-sync
  feed perf_gate, and the steady-loop jit.trace delta (must be 0).
- serving: online serving tier drill — sustained concurrent clients against
  one loaded model (rows/s, batch-fill ratio, request p50/p90/p99, jit trace
  delta after warmup) plus a past-capacity load-shedding probe.
- coldstart: zero-cold-start gate — kmeans_iris in two fresh interpreters
  handed one JAX_COMPILATION_CACHE_DIR; the second process must reach its
  first result on persist-hits, bit-identical, judged by benchstats
  (run standalone via ``python bench.py --only coldstart``).
- profiling: performance observatory drill — per-kernel XLA cost/roofline
  table, profiling off-vs-on overhead delta + bit-parity, benchstats perf
  gate smoke (same-config no-change; synthetic 20% slowdown flagged).
- train_scale: corpus-scale training drill — streaming-ingestion rows/s vs
  the in-memory feed (bit-parity + bounded-resident-rows gates), gradient-
  accumulation overhead at equal effective batch (micro vs fused parity),
  and the 2-process data-parallel pretrain drill (bit-identical to
  single-process accum_steps=2; scaling row informational on CPU meshes).
- aps: pod-scale sparse-embedding exchange — owner-routed pull/push rows/s
  on the sharded-skipgram pattern, per-device comm-bytes-per-step at M=1
  vs the full model axis (the regression-gated O(B·D) claim), and a
  perf_gate verdict of routed vs the legacy all-gather step.

``python bench.py --compare OLD.json NEW.json`` runs the variance-hardened
regression gate over two BENCH round files instead of benchmarking (exit
code 1 when a significant regression is flagged); see docs/bench_schema.md.
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

A100_BERT_BASE_SAMPLES_PER_SEC = 210.0

PER_CHIP_BATCH = 32  # matches the baseline's per-device batch
SEQ = 128
WARMUP_STEPS = 3
TIMED_STEPS = 30
FEED_GATE_STEPS = 8   # steps per thunk in the async-vs-sync feed gate
# the in-process gate flags any round where the measured MFU lands below
# this floor (whether the floor stands is ROADMAP D1's)
MFU_FLOOR = 0.74


def bench_bert():
    import jax
    import optax

    from alink_tpu.dl.modules import BertConfig, TransformerEncoder
    from alink_tpu.dl.sharding import batch_sharding, param_shardings
    from alink_tpu.dl.train import make_train_step
    from alink_tpu.parallel.mesh import default_mesh

    n_chips = len(jax.devices())
    mesh = default_mesh()
    batch = PER_CHIP_BATCH * n_chips  # global batch scales with chips
    cfg = BertConfig.base(num_labels=2, dropout=0.0)  # bf16 compute by default
    model = TransformerEncoder(cfg)

    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg.vocab_size, (batch, SEQ)).astype(np.int32)
    amask = np.ones((batch, SEQ), np.int32)
    y = rng.randint(0, 2, batch).astype(np.int32)

    params = model.init(jax.random.PRNGKey(0), ids[:1], amask[:1])
    params = jax.device_put(params, param_shardings(params, mesh))
    tx = optax.adamw(2e-5, weight_decay=0.01)
    opt_state = tx.init(params["params"])

    def ce(logits, yy):
        return optax.softmax_cross_entropy_with_integer_labels(logits, yy).mean()

    train_step = make_train_step(model, tx, ce)

    ids = jax.device_put(ids, batch_sharding(mesh, 2))
    amask = jax.device_put(amask, batch_sharding(mesh, 2))
    y = jax.device_put(y, batch_sharding(mesh, 1))
    batch_args = {"input_ids": ids, "attention_mask": amask}

    def run(steps):
        nonlocal params, opt_state
        t0 = time.perf_counter()
        for _ in range(steps):
            params, opt_state, l = train_step(params, opt_state, batch_args, y)
        _ = float(l)  # force full materialization through the runtime
        return time.perf_counter() - t0

    from alink_tpu.common.benchstats import (measure_interleaved, perf_gate,
                                             trimmed_mean)
    from alink_tpu.common.metrics import metrics as _metrics

    run(WARMUP_STEPS)  # compile + cache warm
    # delta between two run lengths cancels dispatch/sync overhead.
    # Variance hardening: the two run lengths are measured INTERLEAVED hi,lo,hi,lo,... via
    # benchstats, so shared-container contention during the window charges
    # both lengths equally instead of corrupting the subtraction, and the
    # trimmed mean rejects interference outliers on each side
    eff_steps = TIMED_STEPS - TIMED_STEPS // 3
    tr0 = _metrics.counter("jit.trace")
    # repeats must be >= 5: trimmed(trim=0.2) drops int(n*0.2) per side, so
    # 4 samples would trim NOTHING and one contention spike would ride the
    # plain mean straight into the headline number
    samples = measure_interleaved(
        {"hi": lambda: run(TIMED_STEPS), "lo": lambda: run(TIMED_STEPS // 3)},
        repeats=5, warmup=1)
    # the steady-state loop must not retrace: any growth here means the hot
    # path lost shape stability (CI pins the same invariant on the real
    # train loop in tests/test_train_async.py)
    steady_trace_delta = _metrics.counter("jit.trace") - tr0
    dt = max(trimmed_mean(samples["hi"]) - trimmed_mean(samples["lo"]), 1e-9)

    samples_per_sec = batch * eff_steps / dt
    per_chip = samples_per_sec / n_chips

    # async device feed vs synchronous reference feed on the SAME compiled
    # step, fresh host batches every step (the train_model hot path): the
    # gate verdict proves the async pipeline never regresses step time, and
    # on a wire-bound setup shows the overlap win
    from alink_tpu.dl.train import _feed

    rng_f = np.random.RandomState(1)
    host_batches = [
        (rng_f.randint(0, cfg.vocab_size, (batch, SEQ)).astype(np.int32),
         np.ones((batch, SEQ), np.int32),
         rng_f.randint(0, 2, batch).astype(np.int32))
        for _ in range(FEED_GATE_STEPS)
    ]
    sh2, sh1 = batch_sharding(mesh, 2), batch_sharding(mesh, 1)

    def place(arrs):
        devs = [jax.device_put(a, s) for a, s in zip(arrs, (sh2, sh2, sh1))]
        jax.block_until_ready(devs)
        return devs

    def feed_thunk(mode):
        def thunk():
            nonlocal params, opt_state
            l = None
            for _s, devs in _feed(lambda s: list(host_batches[s]), place,
                                  FEED_GATE_STEPS, mode=mode):
                params, opt_state, l = train_step(
                    params, opt_state,
                    {"input_ids": devs[0], "attention_mask": devs[1]},
                    devs[2])
            jax.block_until_ready(l)
        return thunk

    feed_gate = perf_gate(feed_thunk("sync"), feed_thunk("async"),
                          repeats=5, warmup=1)

    # achieved model FLOPs + MFU so perf work has a target (VERDICT r3 #4).
    # Train FLOPs/token ~= 6*N_matmul + 12*L*S*H (fwd 2N + attn 4LSH, bwd 2x)
    H, L, S = cfg.hidden_size, cfg.num_layers, SEQ
    n_matmul = 12 * L * H * H + H * H  # per-layer qkv/out/mlp + pooler
    flops_per_sample = S * (6 * n_matmul + 12 * L * S * H)

    # cost_analysis-derived FLOPs for the SAME compiled step, so the MFU
    # denominator is measured by the compiler, not hand-maintained (the
    # analytic formula stays as the fallback when the backend reports
    # nothing, and for trajectory continuity with earlier rounds). Lowered
    # AFTER the timed window: tracing must not perturb the measurement.
    xla_flops_per_sample = None
    try:
        from alink_tpu.common.profiling import xla_cost_analysis

        lowered = train_step.lower(params, opt_state, batch_args, y)
        step_flops = xla_cost_analysis(lowered).get("flops")
        if step_flops:
            xla_flops_per_sample = step_flops / batch
    except Exception:
        pass

    # "mfu"/"achieved_tflops_per_chip" STAY on the analytic basis — the
    # r01..r05 trajectory stores that basis, and --compare intersects shared
    # keys, so switching the denominator would read as a phantom MFU delta.
    # The cost_analysis-derived figures ride alongside under *_xla keys.
    achieved_tflops = per_chip * flops_per_sample / 1e12
    achieved_xla = (per_chip * xla_flops_per_sample / 1e12
                    if xla_flops_per_sample else None)
    # one peaks table for the whole repo (profiling.device_peaks, env
    # overrides included); CPU dev containers keep the historical mfu=None
    from alink_tpu.common.profiling import device_peaks

    peaks = device_peaks()
    kind = peaks["device_kind"]
    peak = (peaks["peak_flops_per_s"] / 1e12
            if peaks["peak_flops_per_s"] and "cpu" not in kind.lower()
            else None)
    mfu = {"device_kind": kind,
           "model_tflops_per_sample": round(flops_per_sample / 1e12, 5),
           "xla_tflops_per_sample":
               round(xla_flops_per_sample / 1e12, 5)
               if xla_flops_per_sample else None,
           "achieved_tflops_per_chip": round(achieved_tflops, 1),
           "mfu": round(achieved_tflops / peak, 3) if peak else None,
           "achieved_tflops_per_chip_xla":
               round(achieved_xla, 1) if achieved_xla else None,
           "mfu_xla": round(achieved_xla / peak, 3)
           if peak and achieved_xla else None,
           "peak_tflops_assumed": peak}
    mval = mfu["mfu"]
    mfu["mfu_gate"] = {
        "floor": MFU_FLOOR,
        # None = no device peak on record (CPU dev container): nothing to
        # gate; on an accelerator a sub-floor reading is a loud failure
        "ok": bool(mval is None or mval >= MFU_FLOOR),
    }
    mfu["steady_trace_delta"] = int(steady_trace_delta)
    mfu["feed_gate"] = dict(feed_gate,
                            async_not_slower=feed_gate["verdict"] != "regression")
    return per_chip, mfu


def bench_kmeans_iris():
    """#1: the REAL iris dataset (data/iris.csv, Fisher 1936 via sklearn)
    through the Pipeline API — wall-clock + cluster purity vs true species
    (the README quick-start workload)."""
    import os

    from alink_tpu.operator.batch.base import CsvSourceBatchOp
    from alink_tpu.pipeline import KMeans, Pipeline

    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "data", "iris.csv")
    src = CsvSourceBatchOp(
        filePath=path,
        schemaStr="sl double, sw double, pl double, pw double, species string")
    def fit_once():
        t0 = time.perf_counter()
        pipe = Pipeline(KMeans(
            k=3, maxIter=50, featureCols=["sl", "sw", "pl", "pw"],
            predictionCol="pred"))
        model = pipe.fit(src)
        out = model.transform(src).collect()
        return time.perf_counter() - t0, out

    wall, out = fit_once()          # includes compile (or cache load)
    wall_warm, _ = fit_once()       # compiled-program wall-clock
    labels = np.asarray(out.col("pred"))
    species = np.asarray(out.col("species"))
    purity = sum(
        np.unique(labels[species == s], return_counts=True)[1].max()
        for s in np.unique(species))
    return {"wall_clock_s": round(wall, 3),
            "wall_clock_warm_s": round(wall_warm, 3),
            "cluster_purity": round(purity / len(labels), 4)}


def bench_softmax_mnist():
    """#2: softmax via the distributed L-BFGS path. Throughput measures the
    MNIST-shaped workload (20k x 784, synthetic); accuracy is measured on
    the REAL handwritten-digits dataset (data/digits.csv, 1797 x 64,
    sklearn's UCI digits — the checked-in MNIST stand-in), train/test split
    so the number carries signal."""
    from alink_tpu.operator.batch import (SoftmaxPredictBatchOp,
                                          SoftmaxTrainBatchOp)
    from alink_tpu.common.mtable import MTable
    from alink_tpu.operator.batch.base import (CsvSourceBatchOp,
                                               TableSourceBatchOp)

    rng = np.random.default_rng(1)
    n, d, k = 20000, 784, 10
    W_true = rng.normal(size=(d, k)).astype(np.float32)
    X = rng.normal(size=(n, d)).astype(np.float32)
    y = (X @ W_true + 0.5 * rng.normal(size=(n, k))).argmax(1)
    cols = {f"p{i}": X[:, i] for i in range(d)}
    cols["label"] = y.astype(np.int64)
    src = TableSourceBatchOp(MTable(cols))
    feature_cols = [f"p{i}" for i in range(d)]

    def run_once():
        t0 = time.perf_counter()
        train = SoftmaxTrainBatchOp(featureCols=feature_cols,
                                    labelCol="label", maxIter=30)
        model = train.link_from(src)
        SoftmaxPredictBatchOp().link_from(model, src).collect()
        return time.perf_counter() - t0

    # cold includes compile / persistent-cache load; warm is the compiled
    # steady state (min of 2 rejects contention spikes on a shared host).
    # Warm runs hit the device staging cache (common/staging.py): the 62MB
    # feature block is pushed once and reused across jobs.
    from alink_tpu.common.staging import staging_cache_stats

    s0 = staging_cache_stats()
    wall_cold = run_once()
    s1 = staging_cache_stats()
    wall = min(run_once(), run_once())
    s2 = staging_cache_stats()
    staging = {
        "cold_wire_MB": round((s1["wire_bytes_sent"] - s0["wire_bytes_sent"]) / 1e6, 1),
        "warm_wire_MB": round((s2["wire_bytes_sent"] - s1["wire_bytes_sent"]) / 2e6, 1),
        "warm_cache_hits": s2["hits"] - s1["hits"],
        "bf16_wire_MB_saved": round((s2["wire_bytes_saved"] - s0["wire_bytes_saved"]) / 1e6, 1),
    }
    effective_samples = n * 30  # samples touched per L-BFGS data pass

    # real-data accuracy: UCI digits with an 80/20 split
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "data", "digits.csv")
    dcols = [f"p{i}" for i in range(64)]
    schema = ", ".join(f"{c} double" for c in dcols) + ", label long"
    digits = CsvSourceBatchOp(filePath=path, schemaStr=schema).collect()
    split = int(digits.num_rows * 0.8)
    shuffled = digits.shuffle(seed=0)
    tr, te = shuffled.split_at(split)
    m2 = SoftmaxTrainBatchOp(
        featureCols=dcols, labelCol="label", maxIter=60,
    ).link_from(TableSourceBatchOp(tr))
    pred = SoftmaxPredictBatchOp().link_from(
        m2, TableSourceBatchOp(te)).collect()
    acc = float((np.asarray(pred.col("pred"))
                 == np.asarray(te.col("label"))).mean())
    return {"samples_per_sec": round(effective_samples / wall, 1),
            "samples_per_sec_cold": round(effective_samples / wall_cold, 1),
            "accuracy_digits_holdout": round(acc, 4),
            "wall_clock_s": round(wall, 3),
            "wall_clock_cold_s": round(wall_cold, 3),
            "staging": staging}


def _resnet50_torch():
    import torch
    import torch.nn as nn

    class Bottleneck(nn.Module):
        def __init__(self, cin, planes, stride=1):
            super().__init__()
            cout = planes * 4
            self.conv1 = nn.Conv2d(cin, planes, 1, bias=False)
            self.bn1 = nn.BatchNorm2d(planes)
            self.conv2 = nn.Conv2d(planes, planes, 3, stride=stride,
                                   padding=1, bias=False)
            self.bn2 = nn.BatchNorm2d(planes)
            self.conv3 = nn.Conv2d(planes, cout, 1, bias=False)
            self.bn3 = nn.BatchNorm2d(cout)
            self.relu = nn.ReLU()
            self.down = None
            if stride != 1 or cin != cout:
                self.down = nn.Sequential(
                    nn.Conv2d(cin, cout, 1, stride=stride, bias=False),
                    nn.BatchNorm2d(cout))

        def forward(self, x):
            identity = self.down(x) if self.down is not None else x
            out = self.relu(self.bn1(self.conv1(x)))
            out = self.relu(self.bn2(self.conv2(out)))
            out = self.bn3(self.conv3(out))
            return self.relu(out + identity)

    class ResNet50(nn.Module):
        def __init__(self, num_classes=1000):
            super().__init__()
            self.stem = nn.Sequential(
                nn.Conv2d(3, 64, 7, stride=2, padding=3, bias=False),
                nn.BatchNorm2d(64), nn.ReLU(),
                nn.MaxPool2d(3, stride=2, padding=1))
            layers = []
            cin = 64
            for planes, blocks, stride in ((64, 3, 1), (128, 4, 2),
                                           (256, 6, 2), (512, 3, 2)):
                for b in range(blocks):
                    layers.append(Bottleneck(cin, planes,
                                             stride if b == 0 else 1))
                    cin = planes * 4
            self.layers = nn.Sequential(*layers)
            self.head = nn.Sequential(nn.AdaptiveAvgPool2d(1), nn.Flatten(),
                                      nn.Linear(2048, num_classes))

        def forward(self, x):
            return self.head(self.layers(self.stem(x)))

    torch.manual_seed(0)
    return ResNet50().eval()


def bench_resnet50(batch=256, steps=3):
    """#3: ResNet-50 batch inference rows/sec through the torch.export ->
    StableHLO ingest path (the SavedModelBundle analog on TPU). The e2e path
    models the real serving pipeline: decoded images are uint8 NHWC on the
    host (37.5KB/row on the wire — 4x less than fp32 NCHW), normalization +
    layout transpose + the model are fused into ONE XLA program, and batches
    dispatch ahead so transfer overlaps compute. Reports:
    Serving runs the bfloat16 inference policy (precision="bfloat16" on
    the ingest ops: MXU-native matmuls/convs, ~2x the fp32 on-device rate;
    fp32-agreement is covered by tests/test_ingest.py on an MLP — random-
    weight ResNet top-1 agreed 64/64 in manual runs, not a CI gate).
    - rows_per_sec: host uint8 in -> host logits out (includes transfer)
    - rows_per_sec_on_device: input pre-staged, the same fused
      normalize+model program, bf16 policy
    - rows_per_sec_on_device_fp32: ditto at fp32 (numerics-parity path)
    - h2d_MB_per_s + wire_floor_rows_per_sec: measured device_put
      bandwidth and the throughput ceiling it implies for this wire
      format."""
    import jax
    import jax.numpy as jnp
    import torch

    from alink_tpu.onnx import load_torch_fn

    model = _resnet50_torch()
    x = torch.randn(batch, 3, 224, 224)
    ep = torch.export.export(model.eval(), (x,))  # export once, trace twice
    # bf16 inference policy: MXU-native matmuls/convs, half the HBM traffic
    fn, _ = load_torch_fn(ep, dtype="bfloat16")
    fn32, _ = load_torch_fn(ep)

    mean = np.array([0.485, 0.456, 0.406], np.float32) * 255.0
    std = np.array([0.229, 0.224, 0.225], np.float32) * 255.0

    def make_serve(f):
        @jax.jit
        def serve(u8):  # uint8 NHWC in; normalize/transpose fused on device
            xf = (u8.astype(jnp.float32) - mean) / std
            return f(xf.transpose(0, 3, 1, 2))[0]

        return serve

    serve, serve32 = make_serve(fn), make_serve(fn32)

    rng = np.random.RandomState(0)
    bufs = [rng.randint(0, 256, (batch, 224, 224, 3), np.uint8)
            for _ in range(steps)]
    np.asarray(serve(bufs[0]))  # compile

    # measured wire bandwidth with a forced round trip (a dependent fetch),
    # since device_put+block_until_ready can return before the wire moves;
    # a tiny warmup probe first so the gather compile isn't in the window
    _ = float(jax.device_put(rng.randint(0, 256, (1024,), np.uint8))[0])
    probe = rng.randint(0, 256, (19_200_000,), np.uint8)
    t0 = time.perf_counter()
    _ = float(jax.device_put(probe)[0])
    mbps = 19.2 / (time.perf_counter() - t0)
    row_bytes = 224 * 224 * 3
    wire_floor = mbps * 1e6 / row_bytes

    # end-to-end through the double-buffered streamer (common/streaming.py):
    # each batch ships as 4 parallel row-chunk transfers reassembled on
    # device (where one stream cannot fill the link, aggregate bandwidth
    # scales with stream count), device_put of batch k+1 overlaps compute on
    # batch k, and logits are trimmed + concatenated ON DEVICE and fetched
    # with one host transfer
    import jax.numpy as jnp

    from alink_tpu.common.streaming import stream_map

    stream_phases = {}
    t0 = time.perf_counter()
    refs = [r for _, r in stream_map(
        serve, ((i, [b]) for i, b in enumerate(bufs)),
        depth=max(2, steps - 1), split=4, phases=stream_phases)]
    logits = np.asarray(jnp.concatenate(refs, axis=0))
    dt = time.perf_counter() - t0
    assert logits.shape == (batch * steps, 1000)

    # device-resident variants: stage once, time the SAME fused serve
    # program (bf16 policy + the fp32 numerics-parity path)
    def time_dev(f, reps=steps):
        xd = jax.device_put(bufs[0])
        np.asarray(f(xd)[:1, :1])
        t1 = time.perf_counter()
        for _ in range(reps):
            out_d = f(xd)
        _ = np.asarray(out_d[:1, :1])  # dependent fetch = real sync
        return batch * reps / (time.perf_counter() - t1)

    return {"rows_per_sec": round(batch * steps / dt, 1),
            "rows_per_sec_on_device": round(time_dev(serve), 1),
            "rows_per_sec_on_device_fp32": round(time_dev(serve32), 1),
            "h2d_MB_per_s": round(mbps, 1),
            "wire_floor_rows_per_sec": round(wire_floor, 1),
            "stream": {"wall_s": round(dt, 3),
                       "transfer_s": round(
                           stream_phases.get("transfer_s", 0.0), 3),
                       "compute_s": round(
                           stream_phases.get("compute_s", 0.0), 3),
                       "in_flight": max(2, steps - 1), "split": 4},
            "batch": batch}


def bench_resnet50_savedmodel(batch=128, steps=8):
    """#3's metric-of-record path verbatim: a TF SavedModel ResNet-50
    compiled to ONE XLA program (the SavedModelBundle replacement,
    reference: predictor-tf TFPredictorServiceImpl.java:139). On-device
    bf16 rows/sec (the serving policy; the fp32 figure lives in
    resnet50_predict, numerics vs TF are pinned by tests/test_tfsaved.py).
    Keras build + freeze + compile dominate the wall — one precision keeps
    the bench inside the driver's window."""
    import tempfile

    import jax
    import tensorflow as tf

    from alink_tpu.onnx.tfsaved import load_saved_model_fn

    model = tf.keras.applications.ResNet50(weights=None)
    d = os.path.join(tempfile.mkdtemp(), "rn50")
    tf.saved_model.save(model, d)
    x = np.random.RandomState(0).rand(batch, 224, 224, 3).astype(np.float32)

    def time_fn(jfn, reps=steps):
        xd = jax.device_put(x)
        np.asarray(jfn(xd)[0][:1, :1])  # compile + real sync
        t0 = time.perf_counter()
        for _ in range(reps):
            out = jfn(xd)
        _ = np.asarray(out[0][:1, :1])
        return batch * reps / (time.perf_counter() - t0)

    jfn16, _, _ = load_saved_model_fn(d, dtype="bfloat16")
    return {"rows_per_sec_on_device": round(time_fn(jfn16), 1),
            "batch": batch}


def bench_torch_stream(rows=16384):
    """#5: Torch model predict through the stream op, rows/sec. Micro-batches
    are pipelined (dispatch-ahead in MapStreamOp, one device round trip per
    chunk each way) and sized so device round-trip latency, not chunk count,
    sets the floor. Cold run includes the per-shape XLA compile; warm is the
    steady-state serving number."""
    import tempfile

    import torch
    import torch.nn as nn

    from alink_tpu.common.mtable import MTable
    from alink_tpu.operator.stream.base import TableSourceStreamOp
    from alink_tpu.operator.stream import TorchModelPredictStreamOp

    torch.manual_seed(0)
    model = nn.Sequential(nn.Linear(16, 64), nn.ReLU(),
                          nn.Linear(64, 1)).eval()
    ep = torch.export.export(model, (torch.randn(4, 16),))
    path = os.path.join(tempfile.mkdtemp(), "m.pt2")
    torch.export.save(ep, path)

    X = np.random.RandomState(0).randn(rows, 16).astype(np.float64)
    cols = {f"f{i}": X[:, i] for i in range(16)}
    def run():
        src = TableSourceStreamOp(MTable(cols), chunkSize=4096)
        op = TorchModelPredictStreamOp(
            modelPath=path, selectedCols=[f"f{i}" for i in range(16)],
            outputCols=["score"], predictBatchSize=4096).link_from(src)
        t0 = time.perf_counter()
        out = op.collect()
        return time.perf_counter() - t0, out

    cold, out = run()
    warm, out = run()
    assert out.num_rows == rows
    return {"rows_per_sec": round(rows / warm, 1),
            "rows_per_sec_cold": round(rows / cold, 1)}


def bench_gbdt(n=50000, d=20):
    """GBDT histogram training throughput (SURVEY's riskiest perf item).
    The whole boosting run is ONE device program; histograms are one-hot
    matmuls on the MXU. Reports the warm run (compile amortizes across jobs
    via the persistent XLA cache) plus the cold wall and per-phase split."""
    from alink_tpu.tree.grow import train_gbdt

    rng = np.random.default_rng(2)
    X = rng.normal(size=(n, d)).astype(np.float32)
    y = (X[:, 0] + 0.5 * X[:, 1] ** 2 > 0.3).astype(np.float32)
    t0 = time.perf_counter()
    train_gbdt(X, y, task="binary", num_trees=20, depth=6, num_bins=64)
    cold = time.perf_counter() - t0
    phases = {}
    t0 = time.perf_counter()
    ens = train_gbdt(X, y, task="binary", num_trees=20, depth=6,
                     num_bins=64, phase_metrics=phases)
    dt = time.perf_counter() - t0
    acc = float(((ens.raw_predict(X)[:, 0] > 0) == (y > 0)).mean())
    return {"samples_per_sec": round(n * 20 / dt, 1),
            "trees": 20, "depth": 6, "wall_clock_s": round(dt, 2),
            "cold_wall_clock_s": round(cold, 2),
            "train_accuracy": round(acc, 4), "phases": phases}


def bench_bert_quality():
    """Quality signal for the BERT path — the REAL-TEXT metric of record
    (ROADMAP open item 4; replaces the synthetic token-identity task whose
    0.88 sat pinned since r3). Runs the full in-framework story end-to-end
    on the shipped corpora: MLM-pretrain on ``data/reviews_unlabeled.txt``,
    export the HF-layout checkpoint, fine-tune through
    ``checkpointFilePath`` on the ``data/sst2_mini.csv`` train split, and
    report holdout accuracy on the held-out rows (``dl.data.sst2_split`` —
    the same split the tests pin). Random init scores ~0.5; the pretrained
    encoder must clearly beat it for the round to carry learning evidence.
    Reported under a new leaf (``real_holdout_accuracy``) so ``--compare``
    never diffs the real-text series against the old synthetic one."""
    import shutil
    import tempfile

    from alink_tpu.common.mtable import MTable
    from alink_tpu.dl.data import load_reviews, sst2_split
    from alink_tpu.dl.pretrain import pretrain_and_save
    from alink_tpu.operator.batch.base import TableSourceBatchOp
    from alink_tpu.operator.batch.dl import (
        BertTextClassifierPredictBatchOp, BertTextClassifierTrainBatchOp)

    t0 = time.perf_counter()
    ckpt_dir = tempfile.mkdtemp(prefix="alink_bench_bert_")
    try:
        pre = pretrain_and_save(
            load_reviews(), ckpt_dir, vocab_size=2000, hidden_size=96,
            num_layers=2, num_heads=4, intermediate_size=192, max_len=32,
            epochs=5, batch_size=64, learning_rate=3e-4, seed=0)
        t_pre = time.perf_counter()

        tr_t, tr_y, ho_t, ho_y = sst2_split(seed=0)
        m = BertTextClassifierTrainBatchOp(
            textCol="text", labelCol="label", checkpointFilePath=ckpt_dir,
            maxSeqLength=32, numEpochs=14, batchSize=32, learningRate=5e-4,
            randomSeed=0, poolingStrategy="mean",  # NSP-less checkpoint
        ).link_from(TableSourceBatchOp(MTable({"text": tr_t, "label": tr_y})))
        pred = BertTextClassifierPredictBatchOp(predictionCol="p").link_from(
            m, TableSourceBatchOp(MTable({"text": ho_t, "label": ho_y}))
        ).collect()
        acc = float((np.asarray(pred.col("p")) == ho_y).mean())
    finally:
        shutil.rmtree(ckpt_dir, ignore_errors=True)
    return {
        "real_holdout_accuracy": round(acc, 4),
        "task": "reviews_unlabeled MLM pretrain -> sst2_mini finetune",
        "train_rows": len(tr_t), "holdout_rows": len(ho_t),
        "pretrain": {"mlm_initial_loss": pre["initial_loss"],
                     "mlm_final_loss": pre["final_loss"],
                     "vocab_size": pre["vocab_size"],
                     "wall_clock_s": round(t_pre - t0, 2)},
        "wall_clock_s": round(time.perf_counter() - t0, 2),
    }


def bench_train_scale():
    """Corpus-scale training drill (ROADMAP item 3): streaming ingestion
    rows/s vs the in-memory feed (bit-parity gated, peak resident rows
    bounded by the stream buffer), gradient-accumulation overhead at equal
    effective batch (micro-step schedule vs the fused large-batch
    reference, bit-parity gated), and a 2-process data-parallel pretrain
    drill over a real localhost jax.distributed cluster — bit-identical to
    single-process ``accum_steps=2`` at equal global batch, with a scaling
    row (rows/s at P=1 vs P=2). On a CPU dev container the 2-process wall
    reads cluster-formation + gloo overhead with none of the
    multi-host-HBM benefit, so the scaling row is informational there
    (``wall_gate_applies`` false, the PR 12 ``huge`` convention)."""
    import hashlib
    import socket
    import subprocess
    import sys as _sys
    import tempfile
    import textwrap

    import jax

    from alink_tpu.dl.data import CorpusStream, load_reviews
    from alink_tpu.dl.pretrain import pretrain_mlm
    from alink_tpu.dl.tokenizer import Tokenizer

    def digest(params):
        leaves = jax.tree_util.tree_leaves(params)
        return hashlib.sha256(
            b"".join(np.asarray(x).tobytes() for x in leaves)).hexdigest()

    import shutil

    texts = load_reviews()
    n = len(texts)
    workdir = tempfile.mkdtemp(prefix="alink_train_scale_")
    corpus = os.path.join(workdir, "corpus.txt")
    with open(corpus, "w", encoding="utf-8") as f:
        f.write("\n".join(texts) + "\n")
    tok = Tokenizer.build(texts, vocab_size=800)
    kw = dict(hidden_size=32, num_layers=1, num_heads=2,
              intermediate_size=64, max_len=24, epochs=1, batch_size=64,
              seed=0, tokenizer=tok)
    block, buffer = 256, 512  # buffer << corpus (4.4k rows)

    # warm the MLM micro/apply programs once so neither timed run pays the
    # XLA compile (the ingestion comparison measures the FEED, not tracing)
    pretrain_mlm(texts[:256], block_rows=block, **kw)

    # -- streaming vs in-memory ingestion ---------------------------------
    cs = CorpusStream(corpus, block_rows=block, buffer_rows=buffer)
    t0 = time.perf_counter()
    _, p_stream, _, _ = pretrain_mlm(cs, **kw)
    stream_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    _, p_mem, _, _ = pretrain_mlm(texts, block_rows=block, **kw)
    mem_s = time.perf_counter() - t0
    stream_parity = digest(p_stream) == digest(p_mem)
    resident_ok = cs.max_resident_rows <= cs.buffer_rows

    # -- accumulation at equal effective batch ----------------------------
    t0 = time.perf_counter()
    _, p_a1, _, _ = pretrain_mlm(texts, block_rows=block, accum_steps=1,
                                 **kw)
    accum1_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    _, p_a4, _, _ = pretrain_mlm(texts, block_rows=block, accum_steps=4,
                                 **kw)
    accum4_s = time.perf_counter() - t0
    # micro-vs-fused bit-parity on the fine-tune loop (the CI-pinned
    # contract, re-checked here on the bench config)
    from alink_tpu.dl.modules import KerasSequential
    from alink_tpu.dl.train import TrainConfig, train_model

    rngb = np.random.default_rng(0)
    Xb = rngb.normal(size=(256, 8)).astype(np.float32)
    yb = (Xb[:, 0] > 0).astype(np.int32)

    def _job(mode):
        return train_model(
            KerasSequential(("Dense(10, activation=relu)",), out_dim=2),
            {"x": Xb}, yb,
            TrainConfig(num_epochs=1, batch_size=64, seed=1, accum_steps=4,
                        accum_mode=mode), seq_axis=None)[0]

    accum_parity = digest(_job("micro")) == digest(_job("fused"))

    # -- 2-process data-parallel drill ------------------------------------
    worker = textwrap.dedent("""
        import os, sys, json, hashlib, time
        os.environ["JAX_PLATFORMS"] = os.environ.get("ALINK_BENCH_PLATFORM", "cpu")
        sys.path.insert(0, __REPO__)
        os.environ["COORDINATOR_ADDRESS"] = __COORD__
        os.environ["NUM_PROCESSES"] = "2"
        os.environ["PROCESS_ID"] = sys.argv[1]
        import numpy as np
        import jax
        from alink_tpu.dl.data import CorpusStream
        from alink_tpu.dl.pretrain import pretrain_mlm
        from alink_tpu.dl.tokenizer import Tokenizer
        texts = [t for t in open(__CORPUS__, encoding="utf-8")
                     .read().splitlines() if t.strip()]
        tok = Tokenizer.build(texts, vocab_size=800)
        cs = CorpusStream(__CORPUS__, block_rows=256, buffer_rows=512)
        t0 = time.perf_counter()
        _, params, _, _ = pretrain_mlm(
            cs, hidden_size=32, num_layers=1, num_heads=2,
            intermediate_size=64, max_len=24, epochs=1, batch_size=64,
            seed=0, tokenizer=tok)
        wall = time.perf_counter() - t0
        leaves = jax.tree_util.tree_leaves(params)
        dig = hashlib.sha256(
            b"".join(np.asarray(x).tobytes() for x in leaves)).hexdigest()
        print(json.dumps({"pid": int(sys.argv[1]), "digest": dig,
                          "train_wall_s": wall, "rows": len(texts)}))
    """)
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    script = os.path.join(workdir, "worker.py")
    repo = os.path.dirname(os.path.abspath(__file__))
    with open(script, "w") as f:
        f.write(worker.replace("__REPO__", repr(repo))
                .replace("__COORD__", repr(f"127.0.0.1:{port}"))
                .replace("__CORPUS__", repr(corpus)))
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PLATFORMS", "XLA_FLAGS")}
    t0 = time.perf_counter()
    procs = [subprocess.Popen([_sys.executable, script, str(pid)],
                              stdout=subprocess.PIPE,
                              stderr=subprocess.PIPE, env=env, text=True)
             for pid in (0, 1)]
    try:
        outs = [p.communicate(timeout=300) for p in procs]
    except subprocess.TimeoutExpired:
        for p in procs:  # a hung worker must not orphan its peer
            p.kill()
        outs = [p.communicate() for p in procs]
    two_proc_wall = time.perf_counter() - t0
    two_proc = {"error": None}
    if any(p.returncode for p in procs):
        two_proc = {"error": (outs[0][1] or outs[1][1])[-300:]}
        dp_parity = False
        train_wall_2p = None
    else:
        payloads = [json.loads(o.strip().splitlines()[-1])
                    for o, _ in outs]
        # reference: single process, accum_steps = P at equal global batch
        t0 = time.perf_counter()
        _, p_ref, _, _ = pretrain_mlm(
            CorpusStream(corpus, block_rows=block, buffer_rows=buffer),
            accum_steps=2, **kw)
        ref_s = time.perf_counter() - t0
        dp_parity = (payloads[0]["digest"] == payloads[1]["digest"]
                     == digest(p_ref))
        train_wall_2p = max(p["train_wall_s"] for p in payloads)
        two_proc = {
            "train_wall_s": round(train_wall_2p, 3),
            "spawn_to_done_s": round(two_proc_wall, 3),
            "rows_per_s": round(n / train_wall_2p, 1),
            "single_proc_accum2_wall_s": round(ref_s, 3),
            "single_proc_accum2_rows_per_s": round(n / ref_s, 1),
        }
    # a CPU mesh pays gloo + double jax startup for zero HBM benefit: the
    # scaling row is informational there (same convention as `huge`)
    kind = jax.devices()[0].device_kind.lower()
    wall_gate_applies = not ("cpu" in kind or "host" in kind)

    gate = {
        "streaming_bit_parity": bool(stream_parity),
        "resident_rows_bounded": bool(resident_ok),
        "accum_bit_parity": bool(accum_parity),
        "two_proc_bit_parity": bool(dp_parity),
        "wall_gate_applies": wall_gate_applies,
    }
    gate["ok"] = all(v for k, v in gate.items()
                     if k not in ("wall_gate_applies",))
    shutil.rmtree(workdir, ignore_errors=True)
    return {
        "corpus_rows": n,
        "buffer_rows": buffer,
        "max_resident_rows": cs.max_resident_rows,
        "streaming_rows_per_s": round(n / stream_s, 1),
        "in_memory_rows_per_s": round(n / mem_s, 1),
        "streaming_wall_s": round(stream_s, 3),
        "in_memory_wall_s": round(mem_s, 3),
        "accum1_wall_s": round(accum1_s, 3),
        "accum4_wall_s": round(accum4_s, 3),
        "accum_overhead_pct": round((accum4_s / max(accum1_s, 1e-9) - 1)
                                    * 100, 1),
        "two_proc": two_proc,
        "gate": gate,
    }


def bench_executor(rows=2_000_000):
    """Pipelined DAG executor (common/executor.py): two independent branches
    off one shared source run concurrently on the DAG pool, and a 3-op
    row-wise mapper chain fuses into a single jitted unit. Reports the
    engine's own per-node trace (the same records BENCH readers should use
    to diagnose scheduling regressions): node wall times, the transfer/
    compute phase split where nodes report one, fused-chain count, and the
    concurrency win vs the old serial walk (node_wall_sum ≈ what depth-first
    evaluation would have cost)."""
    from alink_tpu.common.metrics import executor_trace, metrics
    from alink_tpu.common.mtable import AlinkTypes, MTable
    from alink_tpu.mapper.base import BlockKernelMapper
    from alink_tpu.operator.batch import TableSourceBatchOp
    from alink_tpu.operator.batch.utils import MapBatchOp

    def affine_op(col, out, a, b):
        class _M(BlockKernelMapper):
            def kernel(self, schema):
                def fn(X):
                    return X * a + b

                return ([col], [out], [AlinkTypes.DOUBLE], fn)

        class _Op(MapBatchOp):
            mapper_cls = _M

        return _Op()

    rng = np.random.RandomState(0)
    src = TableSourceBatchOp(
        MTable({"x": rng.rand(rows), "y": rng.rand(rows)}))

    def branch(col):
        def work(t):
            v = np.asarray(t.col(col))
            for _ in range(4):  # real host work, ~O(0.5s) per branch
                v = np.sort(v)[::-1].copy()
            return MTable({col: v})

        return src.apply_func(work, out_schema=f"{col} double")

    chain = affine_op("x", "x1", 2.0, 1.0).link_from(src)
    chain = affine_op("x1", "x2", 0.5, -3.0).link_from(chain)
    chain = affine_op("x2", "x3", 4.0, 0.25).link_from(chain)

    n0 = len(executor_trace())
    sink: dict = {}
    branch("x").lazy_collect(lambda t: sink.setdefault("a", t.num_rows))
    branch("y").lazy_collect(lambda t: sink.setdefault("b", t.num_rows))
    chain.lazy_collect(lambda t: sink.setdefault("c", t.num_rows))
    t0 = time.perf_counter()
    src.execute()
    wall = time.perf_counter() - t0
    assert sink == {"a": rows, "b": rows, "c": rows}

    trace = executor_trace()[n0:]
    node_wall = sum(r.get("wall_s", 0.0) for r in trace)
    run = metrics.last("executor.run") or {}
    return {
        "wall_s": round(wall, 3),
        "node_wall_sum_s": round(node_wall, 3),
        "speedup_vs_serial": round(node_wall / wall, 2) if wall > 0 else None,
        "nodes": run.get("nodes"),
        "scheduled_units": run.get("units"),
        "fused_chains": run.get("fused_chains"),
        "trace": sorted(trace, key=lambda r: -r.get("wall_s", 0.0))[:6],
    }


def bench_resilience(rows=20_000):
    """Fault-tolerant runtime (common/resilience.py, common/faults.py):
    run a multi-branch DAG under a seeded 30% transient unit-fault rate and
    a Kafka memory-broker round trip under 2 injected transient IO faults,
    assert both produce output identical to the fault-free run, and report
    the resilience counters (retries absorbed, defusions, dead-letter
    volume) — the same readout long-running jobs should watch."""
    from alink_tpu.common import faults
    from alink_tpu.common.mtable import MTable
    from alink_tpu.common.resilience import resilience_summary
    from alink_tpu.io.kafka import MemoryKafkaBroker
    from alink_tpu.operator.batch import TableSourceBatchOp
    from alink_tpu.operator.stream import (KafkaSinkStreamOp,
                                           KafkaSourceStreamOp,
                                           TableSourceStreamOp)

    rng = np.random.RandomState(0)
    t = MTable({"x": rng.rand(rows), "y": rng.rand(rows)})

    def run_dag_job():
        src = TableSourceBatchOp(t)
        a = src.apply_func(
            lambda m: MTable({"x": np.sort(np.asarray(m.col("x")))}),
            out_schema="x double")
        b = src.apply_func(
            lambda m: MTable({"y": np.asarray(m.col("y")) * 2.0}),
            out_schema="y double")
        got = {}
        a.lazy_collect(lambda m: got.setdefault("a", np.asarray(m.col("x"))))
        b.lazy_collect(lambda m: got.setdefault("b", np.asarray(m.col("y"))))
        src.execute()
        return got

    def run_kafka_job(tag):
        rows_in = MTable.from_rows(
            [(i, float(i) * 0.5) for i in range(512)], "k long, v double")
        MemoryKafkaBroker.named(f"bench-res-{tag}")  # fresh broker
        sink = KafkaSinkStreamOp(
            bootstrapServers=f"memory://bench-res-{tag}", topic="t",
        ).link_from(TableSourceStreamOp(rows_in, chunkSize=128))
        for _ in sink._stream():
            pass
        out = []
        src = KafkaSourceStreamOp(
            bootstrapServers=f"memory://bench-res-{tag}", topic="t",
            schemaStr="k long, v double", maxMessages=512,
            idleTimeoutMs=200)
        for chunk in src._stream():
            out.extend(chunk.rows())
        return out

    faults.clear()
    clean_dag = run_dag_job()
    clean_kafka = run_kafka_job("clean")
    t0 = time.perf_counter()
    # widen the attempt budget under the 30% rate so the drill never
    # exhausts retries by seed luck (0.3^8 per unit)
    prev_attempts = os.environ.get("ALINK_RETRY_MAX_ATTEMPTS")
    os.environ["ALINK_RETRY_MAX_ATTEMPTS"] = "8"
    faults.install(faults.FaultSpec.parse(
        "unit:rate=0.3,kinds=transient;io:count=2", seed=7))
    try:
        faulty_dag = run_dag_job()
        faulty_kafka = run_kafka_job("faulty")
    finally:
        faults.clear()
        if prev_attempts is None:
            os.environ.pop("ALINK_RETRY_MAX_ATTEMPTS", None)
        else:
            os.environ["ALINK_RETRY_MAX_ATTEMPTS"] = prev_attempts
    wall = time.perf_counter() - t0
    dag_parity = all(
        np.array_equal(clean_dag[k], faulty_dag[k]) for k in ("a", "b"))
    return {
        "dag_parity_under_30pct_unit_faults": dag_parity,
        "kafka_parity_under_io_faults": clean_kafka == faulty_kafka,
        "faulted_wall_s": round(wall, 3),
        "counters": resilience_summary(),
    }


def bench_recovery(rows=50_000):
    """Exactly-once recovery runtime (common/recovery.py): a stateful
    windowed pipeline under epoch snapshotting — report the checkpoint tax
    (per-epoch snapshot/commit overhead vs. the raw drain), then kill the
    job mid-stream with an injected crash and report restore latency,
    chunks replayed, and bit-parity with the fault-free run. These numbers
    track the cost of the exactly-once tier across PRs."""
    import tempfile

    from alink_tpu.common import faults
    from alink_tpu.common.metrics import metrics
    from alink_tpu.common.mtable import MTable
    from alink_tpu.common.recovery import (RecoverableStreamJob,
                                           recovery_summary,
                                           run_with_recovery)
    from alink_tpu.common.resilience import RetryPolicy
    from alink_tpu.io.kafka import MemoryKafkaBroker
    from alink_tpu.operator.stream import (KafkaSinkStreamOp,
                                           TableSourceStreamOp)
    from alink_tpu.operator.stream.windows import TumbleTimeWindowStreamOp

    rng = np.random.RandomState(0)
    t = MTable({"ts": np.arange(rows, dtype=np.float64), "v": rng.rand(rows)})
    chunk, epoch_chunks = 512, 4

    def make_window():
        return TumbleTimeWindowStreamOp(
            timeCol="ts", windowTime=float(chunk * 2),
            clause="sum(v) as sv, count(*) as c")

    def job(tag, ckdir):
        return RecoverableStreamJob(
            source=TableSourceStreamOp(t, chunkSize=chunk),
            chains=[([make_window()],
                     [KafkaSinkStreamOp(
                         bootstrapServers=f"memory://bench-rec-{tag}",
                         topic="w")])],
            checkpoint_dir=ckdir, epoch_chunks=epoch_chunks)

    # raw drain of the same pipeline INCLUDING the sink (row encoding +
    # publish), so the tax ratio isolates the checkpoint machinery itself
    # rather than charging sink serialization to it; one un-timed warmup
    # drain first so the GroupBy/jit cold start doesn't masquerade as tax
    def raw_drain(tag, table):
        MemoryKafkaBroker.named(f"bench-rec-{tag}")
        sink = KafkaSinkStreamOp(
            bootstrapServers=f"memory://bench-rec-{tag}", topic="w")
        it = sink._stream_impl(make_window()._stream_impl(
            TableSourceStreamOp(table, chunkSize=chunk)._stream_impl()))
        return sum(1 for _ in it)

    raw_drain("warm", t.slice(0, chunk * 2))
    t0 = time.perf_counter()
    raw_out = raw_drain("raw", t)
    raw_wall = time.perf_counter() - t0

    faults.clear()
    MemoryKafkaBroker.named("bench-rec-clean")
    ck_clean = tempfile.mkdtemp(prefix="alink-rec-")
    t0 = time.perf_counter()
    clean = run_with_recovery(
        lambda: job("clean", ck_clean),
        RetryPolicy(max_attempts=3, base_delay=0.01))
    clean_wall = time.perf_counter() - t0

    MemoryKafkaBroker.named("bench-rec-crash")
    ck_crash = tempfile.mkdtemp(prefix="alink-rec-")
    mid_chunk = (rows // chunk) // 2
    faults.install(faults.FaultSpec.parse(
        f"recovery:count=1,kinds=crash,match=chunk{mid_chunk}", seed=7))
    t0 = time.perf_counter()
    try:
        crashed = run_with_recovery(
            lambda: job("crash", ck_crash),
            RetryPolicy(max_attempts=5, base_delay=0.01))
    finally:
        faults.clear()
    crash_wall = time.perf_counter() - t0

    parity = (MemoryKafkaBroker.named("bench-rec-clean")._topics.get("w")
              == MemoryKafkaBroker.named("bench-rec-crash")._topics.get("w"))
    snap = metrics.timer_stats("recovery.snapshot_s") or {}
    commit = metrics.timer_stats("recovery.commit_s") or {}
    restore = metrics.timer_stats("recovery.restore_s") or {}
    return {
        "rows": rows, "windows_emitted": raw_out,
        "raw_wall_s": round(raw_wall, 3),
        "recovered_wall_s": round(clean_wall, 3),
        "checkpoint_tax": round(clean_wall / raw_wall, 3)
        if raw_wall > 0 else None,
        "epochs": clean.get("epochs"),
        "snapshot_ms_per_epoch": round(snap.get("mean_s", 0.0) * 1e3, 3),
        "commit_ms_per_epoch": round(commit.get("mean_s", 0.0) * 1e3, 3),
        "crash_parity_bit_identical": parity,
        "crashed_wall_s": round(crash_wall, 3),
        "restore_latency_ms": round(restore.get("mean_s", 0.0) * 1e3, 3),
        "chunks_replayed_on_restart": crashed.get("replayed_chunks"),
        "counters": recovery_summary(),
    }


def bench_elastic(rows=24_000):
    """Elastic streaming (common/elastic.py): a sustained keyed windowed
    stream under a load spike. The spike is injected into the
    backpressure SIGNAL (a scripted queue-lag schedule standing in for a
    live source's backlog — the data path, epoch runtime, and rescale
    machinery are all real): the controller scales 2→4 under sustained
    lag and back in when the spike passes. Reports rescale latency
    (barrier→resume), chunks replayed, throughput before/during/after
    the elastic window, and a bit-parity bit vs the fixed-parallelism
    run."""
    import tempfile

    from alink_tpu.common import faults
    from alink_tpu.common.elastic import (BackpressureController,
                                          ElasticStreamJob, elastic_summary)
    from alink_tpu.common.metrics import metrics
    from alink_tpu.common.mtable import MTable
    from alink_tpu.common.recovery import run_with_recovery
    from alink_tpu.common.resilience import RetryPolicy
    from alink_tpu.io.kafka import MemoryKafkaBroker
    from alink_tpu.operator.stream import (KafkaSinkStreamOp,
                                           TableSourceStreamOp)
    from alink_tpu.operator.stream.windows import TumbleTimeWindowStreamOp

    rng = np.random.RandomState(0)
    t = MTable({"ts": np.arange(rows, dtype=np.float64),
                "user": rng.randint(0, 64, rows).astype(np.int64),
                "v": rng.rand(rows)})
    chunk, epoch_chunks = 256, 4
    spike_epochs = (5, 9)  # lag injected on these epochs (inclusive lo)

    def chain():
        return [TumbleTimeWindowStreamOp(
            timeCol="ts", windowTime=float(chunk * 2), groupCols=["user"],
            clause="sum(v) as sv, count(*) as c")]

    def lag_fn(stats):
        lo, hi = spike_epochs
        if lo <= stats["epoch"] < hi:
            return 5.0    # backlog: sustained lag → scale out
        if stats["epoch"] < lo:
            return 0.02   # keeping up: in the hysteresis band, P holds
        return 0.0        # idle drain after the spike → scale back in

    def job(tag, ckdir, controller):
        return ElasticStreamJob(
            source=TableSourceStreamOp(t, chunkSize=chunk),
            chains=[(chain, [KafkaSinkStreamOp(
                bootstrapServers=f"memory://bench-el-{tag}", topic="w")])],
            checkpoint_dir=ckdir, key_col="user", parallelism=2,
            epoch_chunks=epoch_chunks, controller=controller)

    faults.clear()
    MemoryKafkaBroker.named("bench-el-fixed")
    t0 = time.perf_counter()
    run_with_recovery(
        lambda: job("fixed", tempfile.mkdtemp(prefix="alink-el-"), None),
        RetryPolicy(max_attempts=3, base_delay=0.01))
    fixed_wall = time.perf_counter() - t0

    MemoryKafkaBroker.named("bench-el-auto")
    t0 = time.perf_counter()
    summary = run_with_recovery(
        lambda: job("auto", tempfile.mkdtemp(prefix="alink-el-"),
                    BackpressureController(target_chunk_s=0.05, patience=2,
                                           cooldown_epochs=2,
                                           lag_fn=lag_fn)),
        RetryPolicy(max_attempts=3, base_delay=0.01))
    auto_wall = time.perf_counter() - t0

    parity = (MemoryKafkaBroker.named("bench-el-fixed")._topics.get("w")
              == MemoryKafkaBroker.named("bench-el-auto")._topics.get("w"))

    def seg_rows_per_s(stats, lo, hi):
        eps = [e for e in stats if lo <= e["epoch"] < hi and e["chunks"]]
        wall = sum(e["wall_s"] for e in eps)
        return round(sum(e["chunks"] for e in eps) * chunk / wall, 1) \
            if wall > 0 else None

    es = summary["epoch_stats"]
    lo, hi = spike_epochs
    resc = metrics.timer_stats("recovery.rescale_s") or {}
    return {
        "rows": rows,
        "fixed_wall_s": round(fixed_wall, 3),
        "elastic_wall_s": round(auto_wall, 3),
        "rescales": summary["rescales"],
        "rescale_latency_ms": round(resc.get("mean_s", 0.0) * 1e3, 3),
        "chunks_replayed": summary["replayed_chunks"],
        "rows_per_s_before_spike": seg_rows_per_s(es, 0, lo),
        "rows_per_s_during_spike": seg_rows_per_s(es, lo, hi + 2),
        "rows_per_s_after_spike": seg_rows_per_s(
            es, hi + 2, es[-1]["epoch"] + 1),
        "max_parallelism_reached": max(e["parallelism"] for e in es),
        "parity_bit_identical": parity,
        "counters": elastic_summary(),
    }


def bench_modelstream(rows=4_000):
    """Continuous model streaming (alink_tpu/modelstream/): an FTRL
    stream-train job publishing at every epoch barrier into a live
    ModelServer while a traffic thread keeps predicting against the
    swapping model. Reports publish→servable lag (p50/p99 of
    ``modelstream.lag_s``), hot-swap latency, publishes per epoch, the
    zero-trace bit (jit.trace delta across swaps after the first), and a
    parity bit (served row == LocalPredictor over the latest published
    blob). The gate pins parity, zero traces, every-epoch publishing,
    and the staleness bound (lag p99 within LAG_BOUND_S)."""
    import tempfile
    import threading

    from alink_tpu.common import faults
    from alink_tpu.common.metrics import metrics
    from alink_tpu.common.mtable import MTable
    from alink_tpu.common.recovery import (RecoverableStreamJob,
                                           run_with_recovery)
    from alink_tpu.common.resilience import RetryPolicy
    from alink_tpu.modelstream import ModelStreamPublisher
    from alink_tpu.operator.stream import (DatahubSinkStreamOp,
                                           FtrlTrainStreamOp,
                                           TableSourceStreamOp)
    from alink_tpu.pipeline.local_predictor import LocalPredictor
    from alink_tpu.serving.router import ModelServer

    LAG_BOUND_S = 30.0  # staleness bound: epoch start → servable swap
    rng = np.random.RandomState(0)
    t = MTable({"x0": rng.rand(rows), "x1": rng.rand(rows),
                "label": (rng.rand(rows) > 0.5).astype(np.int64)})
    schema = "x0 DOUBLE, x1 DOUBLE"
    store_dir = tempfile.mkdtemp(prefix="alink-ms-")

    server = ModelServer()
    pub = ModelStreamPublisher(store_dir, "ftrl-bench", server=server,
                               input_schema=schema, keep=3)

    stop = threading.Event()
    traffic = {"hits": 0, "misses": 0}

    def drive():
        while not stop.is_set():
            try:
                server.predict("ftrl-bench", [0.3, 0.7])
                traffic["hits"] += 1
            except Exception:
                traffic["misses"] += 1  # model not swapped in yet
            stop.wait(0.002)

    def job():
        return RecoverableStreamJob(
            source=TableSourceStreamOp(t, chunkSize=128),
            chains=[([FtrlTrainStreamOp(featureCols=["x0", "x1"],
                                        labelCol="label")],
                     [DatahubSinkStreamOp(endpoint="memory://bench-ms",
                                          topic="m")])],
            checkpoint_dir=tempfile.mkdtemp(prefix="alink-ms-ck-"),
            epoch_chunks=4, publishers=[pub])

    faults.clear()
    thread = threading.Thread(target=drive, daemon=True)
    thread.start()
    t0 = time.perf_counter()
    try:
        summary = run_with_recovery(job, RetryPolicy(max_attempts=3,
                                                     base_delay=0.01))
    finally:
        stop.set()
        thread.join(timeout=5)
    wall = time.perf_counter() - t0

    epochs = summary["epochs"]
    publishes = metrics.counter("modelstream.publishes")
    trace_delta = metrics.counter("modelstream.swap_trace_delta")
    lag = metrics.histogram("modelstream.lag_s") or {}
    swap = metrics.timer_stats("modelstream.swap_s") or {}

    latest = pub.store.latest()
    served = served_local = None
    if latest is not None:
        blob = pub.store.blob_path(latest[0])
        served = tuple(server.predict("ftrl-bench", [0.3, 0.7]))
        served_local = tuple(
            LocalPredictor(blob, schema).predict_row([0.3, 0.7]))
    parity = served is not None and served == served_local
    zero_trace = publishes >= 3 and trace_delta == 0
    lag_ok = lag.get("p99") is not None and lag["p99"] <= LAG_BOUND_S
    return {
        "rows": rows,
        "wall_s": round(wall, 3),
        "epochs": epochs,
        "publishes": publishes,
        "publishes_per_epoch": round(publishes / epochs, 3) if epochs
        else None,
        "lag_p50_ms": round(lag["p50"] * 1e3, 3) if lag.get("p50")
        is not None else None,
        "lag_p99_ms": round(lag["p99"] * 1e3, 3) if lag.get("p99")
        is not None else None,
        "swap_latency_ms": round(swap.get("mean_s", 0.0) * 1e3, 3),
        "swaps": swap.get("count", 0),
        "traffic_hits": traffic["hits"],
        "traffic_misses": traffic["misses"],
        "zero_trace_swaps": zero_trace,
        "parity_bit_identical": parity,
        "gate": {
            "ok": bool(parity and zero_trace and lag_ok
                       and publishes == epochs),
            "parity": parity,
            "zero_trace": zero_trace,
            "lag_p99_within_bound_s": LAG_BOUND_S if lag_ok else False,
            "published_every_epoch": publishes == epochs,
        },
    }


def bench_compile():
    """Shape-stable execution layer (common/jitcache.py): the compile-tax
    readout tracked across BENCH rounds. Runs the kmeans_iris pipeline and a
    digits-sized softmax predict twice each — cold wall includes trace +
    compile (or persistent-cache load), warm is pure cache-hit reuse — and
    reports the per-workload trace/compile counts plus the process-wide
    program-cache hit rate. The steady-state contract the tests enforce
    (zero new traces on a warm second run) shows up here as
    ``*_warm_compiles == 0``."""
    from alink_tpu.common.jitcache import compile_summary
    from alink_tpu.common.metrics import metrics
    from alink_tpu.common.mtable import MTable
    from alink_tpu.operator.batch import (SoftmaxPredictBatchOp,
                                          SoftmaxTrainBatchOp)
    from alink_tpu.operator.batch.base import (CsvSourceBatchOp,
                                               TableSourceBatchOp)
    from alink_tpu.pipeline import KMeans, Pipeline

    def counted(fn):
        c0 = metrics.counter("jit.compile")
        t0 = time.perf_counter()
        fn()
        return (round(time.perf_counter() - t0, 3),
                metrics.counter("jit.compile") - c0)

    def cold_warm(fn):
        cold_s, cold_c = counted(fn)
        warm_s, warm_c = counted(fn)
        return {"cold_wall_s": cold_s, "warm_wall_s": warm_s,
                "cold_compiles": cold_c, "warm_compiles": warm_c}

    out = {}
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "data", "iris.csv")
    iris = CsvSourceBatchOp(
        filePath=path,
        schemaStr="sl double, sw double, pl double, pw double, species string")

    def kmeans_fit():
        pipe = Pipeline(KMeans(k=3, maxIter=50,
                               featureCols=["sl", "sw", "pl", "pw"],
                               predictionCol="pred"))
        pipe.fit(iris).transform(iris).collect()

    dpath = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "data", "digits.csv")
    dcols = [f"p{i}" for i in range(64)]
    schema = ", ".join(f"{c} double" for c in dcols) + ", label long"
    digits = CsvSourceBatchOp(filePath=dpath, schemaStr=schema).collect()

    def softmax_fit():
        m = SoftmaxTrainBatchOp(
            featureCols=dcols, labelCol="label", maxIter=30,
        ).link_from(TableSourceBatchOp(digits))
        SoftmaxPredictBatchOp().link_from(
            m, TableSourceBatchOp(digits)).collect()

    for name, fn in (("kmeans_iris", kmeans_fit),
                     ("softmax_mnist", softmax_fit)):
        try:  # one failing workload must not sink the whole extra
            out[name] = cold_warm(fn)
        except Exception as e:
            out[name] = {"error": f"{type(e).__name__}: {e}"[:200]}

    summary = compile_summary()
    out["program_cache"] = {
        "programs": summary["programs"],
        "hit_rate": summary["hit_rate"],
        "traces": summary["counters"].get("jit.trace", 0),
        "compiles": summary["counters"].get("jit.compile", 0),
        "compile_s": (metrics.timer_stats("jitcache.compile_s")
                      or {}).get("total_s")}
    return out


_COLDSTART_CHILD = '''
import json, os, sys, time

t_start = time.perf_counter()
sys.path.insert(0, {repo!r})
import numpy as np

import alink_tpu  # noqa: F401 — enables the persistent cache from env
from alink_tpu.common.metrics import metrics
from alink_tpu.common.profiling import program_costs
from alink_tpu.operator.batch.base import CsvSourceBatchOp
from alink_tpu.pipeline import KMeans, Pipeline

t_import = time.perf_counter()
src = CsvSourceBatchOp(
    filePath={csv!r},
    schemaStr="sl double, sw double, pl double, pw double, species string")
pipe = Pipeline(KMeans(k=3, maxIter=50, featureCols=["sl", "sw", "pl", "pw"],
                       predictionCol="pred"))
out = pipe.fit(src).transform(src).collect()
t_first = time.perf_counter()
print(json.dumps({{
    "import_s": round(t_import - t_start, 3),
    "first_result_s": round(t_first - t_import, 3),
    "total_s": round(t_first - t_start, 3),
    "persist_hit": metrics.counter("jit.persist_hit"),
    "persist_miss": metrics.counter("jit.persist_miss"),
    "persist_error": metrics.counter("jit.persist_error"),
    "compiles": metrics.counter("jit.compile"),
    "traces": metrics.counter("jit.trace"),
    "profile_records": len(program_costs(resolve=False)),
    "labels": [int(x) for x in np.asarray(out.col("pred"))],
}}))
'''


def bench_coldstart():
    """Zero-cold-start gate: compiled programs must survive process death.
    Spawns the kmeans_iris workload in TWO fresh CPU interpreters handed one
    ``JAX_COMPILATION_CACHE_DIR`` (a throwaway temp dir: a deliberate
    cold/warm drill, not the chip path): the first pays real backend compiles and
    populates the cache; the second must reach its first result on
    persist-hits (``jit.persist_hit > 0``), bit-identical outputs, with the
    verdict judged by the benchstats machinery (a cold-threshold
    compare of the two first-result walls). ``ratio_vs_warm`` relates the
    second process's workload wall to this (warm) process's in-memory wall
    — the rollout latency a replica autoscale-up actually pays."""
    import shutil
    import subprocess
    import sys
    import tempfile

    from alink_tpu.common.benchstats import COLD_THRESHOLD, compare_samples
    from alink_tpu.common.jitcache import _persist_entries, persist_cap_bytes

    repo = os.path.dirname(os.path.abspath(__file__))
    csv = os.path.join(repo, "data", "iris.csv")
    cache_dir = tempfile.mkdtemp(prefix="alink-coldstart-")
    script = _COLDSTART_CHILD.format(repo=repo, csv=csv)

    def run_child(tag):
        env = dict(os.environ)
        env["JAX_COMPILATION_CACHE_DIR"] = cache_dir
        env["JAX_PLATFORMS"] = "cpu"   # the parent holds the chip, if any
        proc = subprocess.run([sys.executable, "-c", script], env=env,
                              capture_output=True, text=True, timeout=600)
        if proc.returncode != 0:
            raise RuntimeError(
                f"coldstart child {tag} failed: {proc.stderr[-1500:]}")
        return json.loads(proc.stdout.strip().splitlines()[-1])

    try:
        first = run_child("first")
        second = run_child("second")
    finally:
        # the same accounting persist_summary() reports, on an explicit dir
        on_disk = _persist_entries(cache_dir)
        entries = len(on_disk)
        cache_bytes = sum(e[2] for e in on_disk)
        shutil.rmtree(cache_dir, ignore_errors=True)

    # the warm reference: the same workload in THIS process, already
    # compiled (bench_compile warms it earlier in a full driver run)
    warm = bench_kmeans_iris()["wall_clock_warm_s"]
    gate = compare_samples([first["first_result_s"]],
                           [second["first_result_s"]],
                           noise_floor=COLD_THRESHOLD)
    bit_identical = first["labels"] == second["labels"]
    out = {
        "first_process": {k: v for k, v in first.items() if k != "labels"},
        "second_process": {k: v for k, v in second.items() if k != "labels"},
        "cold_first_result_s": first["first_result_s"],
        "second_cold_first_result_s": second["first_result_s"],
        "warm_wall_s": warm,
        "ratio_vs_warm_cold": round(first["first_result_s"] / warm, 1)
        if warm else None,
        "ratio_vs_warm_second": round(second["first_result_s"] / warm, 1)
        if warm else None,
        "persist_hits_second_process": second["persist_hit"],
        "cache_entries": entries,
        "cache_mb": round(cache_bytes / 1e6, 2),
        "cache_cap_mb": round(persist_cap_bytes() / 1e6, 1),
        "bit_identical": bit_identical,
        "second_vs_first_verdict": gate["verdict"],
        "second_vs_first_delta_pct": gate["delta_pct"],
        "gate": {
            "persist_hit_ok": second["persist_hit"] > 0,
            "no_persist_errors": second["persist_error"] == 0,
            "bit_identical": bit_identical,
            # wall verdict: on CPU containers trace time floors both
            # processes (XLA:CPU compiles these programs in ~0.1s, so the
            # skip is noise-level); the hard requirement is "never slower"
            # — the big wall win is the TPU chip's 20-40s compiles
            "second_not_slower": gate["verdict"] != "regression",
        },
    }
    out["gate"]["ok"] = all(out["gate"].values())
    return out


def bench_serving(clients=8, rows_per_client=400):
    """Online serving tier (alink_tpu/serving): sustained concurrent-client
    drill against one loaded pipeline model. ``clients`` threads submit
    single-row predict requests as fast as completions allow; the router
    coalesces them into bucket-ladder micro-batches. Reports rows/s,
    batch-fill ratio, request-latency p50/p90/p99, the jit trace delta over
    the sustained window (target: 0 after load-time warmup), and a
    past-capacity shed probe (bounded queue, counted rejections).

    A second pass re-runs the same drill against the SAME model loaded
    with ``precision="int8"`` (calibrated + accuracy-band-gated at load):
    the ``precision`` block reports fp32-vs-int8 rows/s and client-side
    p99, the load's band-gate verdict (``band_ok`` must be green), the
    label ``accuracy_delta`` / numeric ``accuracy_band`` readouts
    (directionless in ``--compare``, like ``parity_max_diff``), and the
    bit-identity gate: the precision-unset fp32 load must serve
    byte-identical rows to a serial LocalPredictor."""
    import threading

    from alink_tpu.common.metrics import metrics
    from alink_tpu.common.mtable import MTable
    from alink_tpu.pipeline import (NaiveBayes, Pipeline, StandardScaler,
                                    VectorAssembler)
    from alink_tpu.serving import (AkServingOverloadException, ModelServer,
                                   ServingConfig, serving_summary)

    rng = np.random.RandomState(0)
    X = np.concatenate([rng.normal(c, 0.4, size=(200, 4))
                        for c in [(0, 0, 0, 0), (2, 2, 2, 2)]])
    y = np.repeat(["neg", "pos"], 200)
    feats = ["f0", "f1", "f2", "f3"]
    t = MTable({f"f{i}": X[:, i] for i in range(4)}).with_column("label", y)
    model = Pipeline(
        StandardScaler(selectedCols=feats),
        VectorAssembler(selectedCols=feats, outputCol="vec"),
        NaiveBayes(vectorCol="vec", labelCol="label", predictionCol="pred"),
    ).fit(t)
    schema = "f0 double, f1 double, f2 double, f3 double"

    srv = ModelServer(ServingConfig(queue_depth=512, max_batch_rows=64,
                                    flush_deadline_s=0.002))
    try:
        t_load0 = time.perf_counter()
        load_info = srv.load("bench", model, schema,
                             warmup_rows=[tuple(X[0])])
        load_s = time.perf_counter() - t_load0

        traces0 = metrics.counter("jit.trace")
        rows = [tuple(r) for r in X]

        def drill(server, mname):
            lat: list = []
            lat_lock = threading.Lock()

            def client(cid):
                mine = []
                for i in range(rows_per_client):
                    r0 = time.perf_counter()
                    server.predict(mname,
                                   rows[(cid * 131 + i * 7) % len(rows)],
                                   timeout=120)
                    mine.append(time.perf_counter() - r0)
                with lat_lock:
                    lat.extend(mine)

            ths = [threading.Thread(target=client, args=(c,))
                   for c in range(clients)]
            w0 = time.perf_counter()
            for th in ths:
                th.start()
            for th in ths:
                th.join()
            return time.perf_counter() - w0, np.asarray(lat)

        wall, lat_f = drill(srv, "bench")
        traces_delta = metrics.counter("jit.trace") - traces0
        stats = serving_summary(srv)
        mstat = stats["models"][0]
        req_hist = stats["histograms"].get("serving.request_s") or {}

        # ---- quantized pass: same model, same drill, int8 policy --------
        from alink_tpu.pipeline import LocalPredictor

        calib_rows = [tuple(r) for r in X[::25]]  # spans both clusters
        info8 = srv.load("bench8", model, schema, warmup_rows=calib_rows,
                         precision="int8")
        band = (info8.get("precision") or {}).get("band_report") or {}
        traces8_0 = metrics.counter("jit.trace")
        wall8, lat_q = drill(srv, "bench8")
        traces8_delta = metrics.counter("jit.trace") - traces8_0
        # label agreement + bit-identity gate over one deterministic sweep
        lp = LocalPredictor(model, schema, cache_plan=False)
        serial = [lp.predict_table(
            MTable.from_rows([r], schema)).get_row(0) for r in rows[:100]]
        out_f = [srv.predict("bench", r, timeout=120) for r in rows[:100]]
        out_q = [srv.predict("bench8", r, timeout=120) for r in rows[:100]]
        agree = float(np.mean([a[-1] == b[-1]
                               for a, b in zip(out_q, out_f)]))
        total = clients * rows_per_client
        precision_block = {
            "policy": (info8.get("precision") or {}).get("policy"),
            "band_ok": band.get("ok"),
            # directionless in --compare (metric_direction → None), like
            # parity_max_diff: near-zero diffs vs the fp32 baseline
            "accuracy_delta": round(1.0 - agree, 6),
            "accuracy_band": band.get("max_rel_diff"),
            "fp32_rows_per_sec": round(total / wall, 1),
            "int8_rows_per_sec": round(total / wall8, 1),
            "fp32_request_p99_ms": round(
                float(np.percentile(lat_f, 99)) * 1e3, 3),
            "int8_request_p99_ms": round(
                float(np.percentile(lat_q, 99)) * 1e3, 3),
            "int8_traces_during_drill": traces8_delta,
            # knob-off gate: the precision-unset load serves byte-identical
            # rows to a serial LocalPredictor
            "bit_identical_fp32": out_f == serial,
        }

        # saturation probe: flood far past the queue bound with async
        # submits; shed must be counted and accepted work must complete
        srv2 = ModelServer(ServingConfig(queue_depth=32, max_batch_rows=32,
                                         flush_deadline_s=0.05))
        srv2.load("sat", model, schema, warmup_rows=[tuple(X[0])])
        futs, shed = [], 0
        for i in range(2000):
            try:
                futs.append(srv2.submit("sat", rows[i % len(rows)]))
            except AkServingOverloadException:
                shed += 1
        completed = sum(1 for f in futs if f.result(120) is not None)
        srv2.close()

        return {
            "clients": clients,
            "rows": total,
            "rows_per_sec": round(total / wall, 1),
            "load_s": round(load_s, 3),
            "warmup": load_info["warmup"],
            "batch_fill": mstat["batch_fill"],
            "batches": mstat["batches"],
            "request_p50_ms": round((req_hist.get("p50") or 0) * 1e3, 3),
            "request_p90_ms": round((req_hist.get("p90") or 0) * 1e3, 3),
            "request_p99_ms": round((req_hist.get("p99") or 0) * 1e3, 3),
            "traces_during_drill": traces_delta,  # sustained window; 0 = contract held
            "precision": precision_block,
            "saturation": {"submitted": 2000, "shed": shed,
                           "accepted_completed": completed},
        }
    finally:
        srv.close()


def bench_fleet(clients=6, rows_per_client=60):
    """Fault-tolerant serving fleet (alink_tpu/serving/fleet): multi-process
    replica scaling at N∈{1,2,4} (rows/s + request p99 per N, bit-parity vs
    the single-process ModelServer over the same rows), then a chaos drill —
    one replica killed mid-batch at load via the ``replica`` fault point —
    reporting failover count, recovery time back to full ready strength,
    and the delivery gate: every accepted request either completed with the
    serial answer or shed with a typed error; none lost. Zero-trace gate:
    replica trace deltas stay 0 (all warmup from the ``.ak.warmup.json``
    sidecar, never live traffic). Observability phase: tracing off-vs-on
    through the full frontdoor→replica path (interleaved, benchstats-judged
    delta + bit-parity) and the stitched-trace gate — the frontdoor trace
    must contain at least one replica-process-tagged span."""
    import shutil
    import tempfile
    import threading

    from alink_tpu.common.exceptions import (AkCircuitOpenException,
                                             AkDeadlineExceededException)
    from alink_tpu.common.mtable import MTable
    from alink_tpu.pipeline import (NaiveBayes, Pipeline, StandardScaler,
                                    VectorAssembler)
    from alink_tpu.serving import (AkServingOverloadException, FleetConfig,
                                   ModelServer, ServingFleet)

    rng = np.random.RandomState(0)
    X = np.concatenate([rng.normal(c, 0.4, size=(200, 4))
                        for c in [(0, 0, 0, 0), (2, 2, 2, 2)]])
    y = np.repeat(["neg", "pos"], 200)
    feats = ["f0", "f1", "f2", "f3"]
    t = MTable({f"f{i}": X[:, i] for i in range(4)}).with_column("label", y)
    model = Pipeline(
        StandardScaler(selectedCols=feats),
        VectorAssembler(selectedCols=feats, outputCol="vec"),
        NaiveBayes(vectorCol="vec", labelCol="label", predictionCol="pred"),
    ).fit(t)
    schema = "f0 double, f1 double, f2 double, f3 double"
    tmp = tempfile.mkdtemp(prefix="alink_bench_fleet_")
    rows = [tuple(r) for r in X]
    try:
        path = os.path.join(tmp, "model.ak")
        model.save(path)
        # single-process ground truth; the load also writes the warmup
        # sidecar every fleet replica warms from
        srv = ModelServer()
        srv.load("m", path, schema, warmup_rows=[tuple(X[0])])
        serial = [srv.predict("m", r) for r in rows[:32]]
        srv.close()

        typed = (AkServingOverloadException, AkCircuitOpenException,
                 AkDeadlineExceededException)

        def drill(fleet, lat, mismatches):
            shed, lost = [0], []

            def client(cid):
                for i in range(rows_per_client):
                    k = (cid * 131 + i * 7) % len(rows)
                    t0 = time.perf_counter()
                    try:
                        got = fleet.predict("m", rows[k], timeout=60)
                        lat.append(time.perf_counter() - t0)
                        if k < 32 and got != serial[k]:
                            mismatches.append(k)
                    except typed:
                        shed[0] += 1
                    except Exception as e:
                        lost.append(f"{type(e).__name__}: {e}"[:120])

            threads = [threading.Thread(target=client, args=(c,))
                       for c in range(clients)]
            t0 = time.perf_counter()
            for th in threads:
                th.start()
            for th in threads:
                th.join()
            return time.perf_counter() - t0, shed[0], lost

        def trace_deltas(fleet):
            time.sleep(3 * fleet._cfg.heartbeat_s + 0.2)  # let hbs land
            return [r["trace_delta"]
                    for r in fleet.fleet_summary()["replicas"]]

        scales, parity_ok, zero_trace, all_lost = {}, True, True, []
        for n in (1, 2, 4):
            lat, mism = [], []
            with ServingFleet(FleetConfig(
                    replicas=n, heartbeat_s=0.2,
                    heartbeat_timeout_s=1.5)) as fleet:
                fleet.load("m", path, schema)
                wall, shed, lost = drill(fleet, lat, mism)
                deltas = trace_deltas(fleet)
            total = clients * rows_per_client
            parity_ok = parity_ok and not mism
            zero_trace = zero_trace and all(d == 0 for d in deltas)
            all_lost += lost
            scales[str(n)] = {
                "rows_per_sec": round((total - shed - len(lost)) / wall, 1),
                "request_p99_ms": round(
                    float(np.percentile(lat, 99)) * 1e3, 3) if lat else None,
                "shed": shed,
                "trace_deltas": deltas,
            }

        # chaos drill: r1's first incarnation (gen 2) dies on its first
        # routed batch; the front-end re-dispatches, the supervisor
        # respawns it warm from the sidecar
        lat, mism = [], []
        with ServingFleet(FleetConfig(
                replicas=2, heartbeat_s=0.2, heartbeat_timeout_s=1.0,
                worker_env={"ALINK_FAULT_SPEC":
                            "replica:count=1,kinds=kill_mid_batch,"
                            "match=r1.g2.batch"})) as fleet:
            fleet.load("m", path, schema)
            t_drill0 = time.perf_counter()
            wall, shed, lost = drill(fleet, lat, mism)
            all_lost += lost
            recovery_s = None
            deadline = time.perf_counter() + 60
            while time.perf_counter() < deadline:
                s = fleet.fleet_summary()
                if s["states"].get("ready") == 2 and all(
                        r["synced"].get("m") for r in s["replicas"]):
                    recovery_s = time.perf_counter() - t_drill0
                    break
                time.sleep(0.1)
            for k in range(16):  # post-recovery parity
                if fleet.predict("m", rows[k], timeout=60) != serial[k]:
                    mism.append(k)
            deltas = trace_deltas(fleet)
            summary = fleet.fleet_summary()
        parity_ok = parity_ok and not mism
        zero_trace = zero_trace and all(d == 0 for d in deltas)
        counters = summary["counters"]
        respawn_loads = [ld for r in summary["replicas"]
                         for ld in (r["loads"] or []) if r["gen"] > 2]
        kill = {
            "shed": shed,
            "lost": all_lost,
            "failovers": counters.get("fleet.failovers", 0),
            "respawns": counters.get("fleet.respawns", 0),
            "recovery_s": round(recovery_s, 2) if recovery_s else None,
            "respawn_warmup": [ld.get("warmup_source")
                               for ld in respawn_loads],
        }
        # ---- observability phase: tracing off vs on through the SAME
        # frontdoor→replica path. Two fleets (workers inherit the flag at
        # spawn), thunks interleaved so container drift charges both flags
        # equally; the supervisor-side flag flips with the thunk so the
        # frontend span + wire context toggle together with the replicas.
        from alink_tpu.common.benchstats import (compare_samples,
                                                 measure_interleaved)
        from alink_tpu.common.tracing import job_report, tracer

        prev_flag = os.environ.get("ALINK_TRACING")
        tfleets, touts = {}, {}
        try:
            for flag in ("off", "on"):
                tfleets[flag] = ServingFleet(FleetConfig(
                    replicas=2, heartbeat_s=0.2, heartbeat_timeout_s=1.5,
                    worker_env={"ALINK_TRACING": flag}))
                tfleets[flag].start()
                tfleets[flag].load("m", path, schema)

            def traced(flag):
                def thunk():
                    os.environ["ALINK_TRACING"] = flag
                    touts[flag] = [tfleets[flag].predict("m", rows[k],
                                                         timeout=60)
                                   for k in range(32)]
                return thunk

            for flag in ("off", "on"):  # warmup outside both windows
                traced(flag)()
            walls = measure_interleaved(
                {"off": traced("off"), "on": traced("on")},
                repeats=5, warmup=0)
            trace_overhead = compare_samples(walls["off"], walls["on"])
            trace_parity = (touts["off"] == touts["on"]
                            and touts["off"] == serial[:32])

            # stitched-trace gate: one more traced predict, then poll the
            # frontdoor trace until a replica-proc-tagged span lands in it
            # (the replica batch spans ride the heartbeat relay)
            os.environ["ALINK_TRACING"] = "on"
            assert tfleets["on"].predict("m", rows[0],
                                         timeout=60) == serial[0]
            # newest fleet.request root, not last_trace_id(): relayed
            # replica load spans are local roots and can land right
            # after the predict, shadowing it
            tid = next(s["trace_id"] for s in reversed(tracer.spans())
                       if s["name"] == "fleet.request")

            def _stitched():
                def walk(nodes):
                    for nd in nodes:
                        yield nd
                        yield from walk(nd.get("children") or [])
                return any(nd.get("proc")
                           for nd in walk(job_report(tid).get("tree") or []))

            stitched = False
            deadline = time.perf_counter() + 20
            while time.perf_counter() < deadline:
                if _stitched():
                    stitched = True
                    break
                time.sleep(0.1)
        finally:
            for fl in tfleets.values():
                try:
                    fl.stop()
                except Exception:
                    pass
            if prev_flag is None:
                os.environ.pop("ALINK_TRACING", None)
            else:
                os.environ["ALINK_TRACING"] = prev_flag

        out = {
            "clients": clients,
            "rows_per_client": rows_per_client,
            "scales": scales,
            "kill_drill": kill,
            "tracing": {
                "overhead": trace_overhead,
                "bit_parity_on_vs_off": trace_parity,
                "stitched_trace_id": tid,
            },
            "gate": {
                "parity": parity_ok,
                "zero_trace": zero_trace,
                "clean_shed": not all_lost,
                "recovered": (recovery_s is not None
                              and kill["respawns"] >= 1
                              and kill["respawn_warmup"] == ["sidecar"]),
                "tracing_parity": trace_parity,
                "stitched": stitched,
            },
        }
        out["gate"]["ok"] = all(out["gate"].values())
        return out
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def bench_observability(repeats=3):
    """Unified tracing & telemetry layer (common/tracing.py + the metrics
    histogram/Prometheus export): run kmeans_iris with ALINK_TRACING=off vs
    on and report the overhead delta (budget: <3% wall, README-documented),
    the tracing-on vs -off bit-parity of predictions, the exported-metric
    counts by family, and the span count of the run's job_report."""
    from alink_tpu.common.metrics import metrics
    from alink_tpu.common.tracing import job_report
    from alink_tpu.operator.batch.base import CsvSourceBatchOp
    from alink_tpu.pipeline import KMeans, Pipeline

    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "data", "iris.csv")
    src = CsvSourceBatchOp(
        filePath=path,
        schemaStr="sl double, sw double, pl double, pw double, species string")

    def kmeans_once():
        pipe = Pipeline(KMeans(
            k=3, maxIter=50, featureCols=["sl", "sw", "pl", "pw"],
            predictionCol="pred"))
        out = pipe.fit(src).transform(src).collect()
        return np.asarray(out.col("pred"))

    def mapper_dag_once():
        # fallback workload when kmeans cannot run (e.g. a container whose
        # jax dropped shard_map): the same executor + jit surface without a
        # mesh — branches on the DAG pool plus a fused block-kernel chain
        from alink_tpu.common.mtable import AlinkTypes, MTable
        from alink_tpu.mapper.base import BlockKernelMapper
        from alink_tpu.operator.batch import TableSourceBatchOp
        from alink_tpu.operator.batch.utils import MapBatchOp

        def affine(col, out_col, a, b):
            class _M(BlockKernelMapper):
                def kernel(self, schema):
                    return ([col], [out_col], [AlinkTypes.DOUBLE],
                            lambda X: X * a + b)

            class _Op(MapBatchOp):
                mapper_cls = _M

            return _Op()

        rng = np.random.RandomState(0)
        t_src = TableSourceBatchOp(
            MTable({"x": rng.rand(2_000_000), "y": rng.rand(2_000_000)}))
        t_src.apply_func(
            lambda m: MTable({"y": np.asarray(m.col("y")) * 2.0}),
            out_schema="y double").lazy_collect(lambda m: None)
        chain = affine("x", "x1", 2.0, 1.0).link_from(t_src)
        chain = affine("x1", "x2", 0.5, -3.0).link_from(chain)
        chain = affine("x2", "x3", 4.0, 0.25).link_from(chain)
        return np.asarray(chain.collect().col("x3"))

    workload, run_once = "kmeans_iris", kmeans_once
    try:
        run_once()  # compile / program-cache warm, outside both windows
    except Exception:
        workload, run_once = "mapper_dag", mapper_dag_once
        run_once()

    # interleave off/on repetitions (min per flag): a block of off-runs
    # followed by a block of on-runs would charge allocator/page-cache
    # drift between the blocks to tracing
    walls = {"off": [], "on": []}
    outs = {}
    prev = os.environ.get("ALINK_TRACING")
    try:
        for _ in range(repeats):
            for flag in ("off", "on"):
                os.environ["ALINK_TRACING"] = flag
                t0 = time.perf_counter()
                outs[flag] = run_once()
                walls[flag].append(time.perf_counter() - t0)
    finally:
        if prev is None:
            os.environ.pop("ALINK_TRACING", None)
        else:
            os.environ["ALINK_TRACING"] = prev
    off_wall, on_wall = min(walls["off"]), min(walls["on"])

    parity = bool(np.array_equal(outs["off"], outs["on"]))
    report = job_report()  # the last traced run — BEFORE the span
    # microbenchmark below floods the ring with its own root spans

    # deterministic per-span microbenchmark: the end-to-end delta above
    # rides a shared container's noise floor (±5% on a 70ms workload); the
    # direct cost of one open+close is the stable number the <3% budget is
    # audited against (a job traces O(nodes) spans, so spans_per_job *
    # span_cost / wall is the true tax)
    from alink_tpu.common.tracing import trace_span

    os.environ["ALINK_TRACING"] = "on"
    try:
        for _ in range(100):
            with trace_span("bench.warm"):
                pass
        t0 = time.perf_counter()
        for _ in range(2000):
            with trace_span("bench.span"):
                pass
        span_us = (time.perf_counter() - t0) / 2000 * 1e6
    finally:
        if prev is None:
            os.environ.pop("ALINK_TRACING", None)
        else:
            os.environ["ALINK_TRACING"] = prev
    overhead = on_wall / off_wall - 1.0 if off_wall > 0 else None
    kinds: dict = {}
    for line in metrics.export_prometheus().splitlines():
        if line.startswith("# TYPE"):
            kinds[line.rsplit(" ", 1)[-1]] = \
                kinds.get(line.rsplit(" ", 1)[-1], 0) + 1
    return {
        "workload": workload,
        "tracing_off_wall_s": round(off_wall, 4),
        "tracing_on_wall_s": round(on_wall, 4),
        "overhead_pct": round(overhead * 100, 2)
        if overhead is not None else None,
        "within_3pct_budget": overhead is not None and overhead < 0.03,
        "span_cost_us": round(span_us, 2),
        "bit_parity_on_vs_off": parity,
        "exported_metrics": {"total": sum(kinds.values()), **kinds},
        "job_report": {
            "trace_id": report.get("trace_id"),
            "spans": len(report.get("spans", [])),
            "totals": report.get("totals"),
            "outcomes": report.get("outcomes"),
        },
    }


def bench_profiling(repeats=3, rows=300_000):
    """Performance observatory (common/profiling.py + common/benchstats.py):
    run a fused mapper-chain DAG with ALINK_PROFILING off vs on
    (interleaved, min per flag) and report the overhead delta plus off/on
    bit-parity — the instrumentation-never-changes-results contract — the
    per-kernel XLA cost/roofline table the observatory captured, and the
    benchstats perf gate smoked on two in-process measurements: a
    same-config pair must read no-change while a synthetic 20% slowdown
    must be flagged."""
    from alink_tpu.common.benchstats import (compare_samples,
                                             measure_interleaved, perf_gate)
    from alink_tpu.common.mtable import AlinkTypes, MTable
    from alink_tpu.common.profiling import profile_summary
    from alink_tpu.mapper.base import BlockKernelMapper
    from alink_tpu.operator.batch import TableSourceBatchOp
    from alink_tpu.operator.batch.utils import MapBatchOp

    def affine(col, out_col, a, b):
        class _M(BlockKernelMapper):
            def kernel(self, schema):
                return ([col], [out_col], [AlinkTypes.DOUBLE],
                        lambda X: X * a + b)

        class _Op(MapBatchOp):
            mapper_cls = _M

        return _Op()

    rng = np.random.RandomState(0)
    t = MTable({"x": rng.rand(rows)})

    def run_once():
        chain = affine("x", "x1", 2.0, 1.0).link_from(TableSourceBatchOp(t))
        chain = affine("x1", "x2", 0.5, -3.0).link_from(chain)
        return np.asarray(chain.collect().col("x2"))

    outs = {}

    def flagged(flag):
        def thunk():
            os.environ["ALINK_PROFILING"] = flag
            outs[flag] = run_once()

        return thunk

    prev = os.environ.get("ALINK_PROFILING")
    try:
        os.environ["ALINK_PROFILING"] = "on"
        run_once()  # trace + enqueue cost capture outside both windows
        walls = measure_interleaved(
            {"off": flagged("off"), "on": flagged("on")},
            repeats=max(repeats, 5), warmup=0)
        os.environ["ALINK_PROFILING"] = "on"
        summ = profile_summary(top=6)
    finally:
        if prev is None:
            os.environ.pop("ALINK_PROFILING", None)
        else:
            os.environ["ALINK_PROFILING"] = prev
    # judge the off-vs-on delta with the observatory's own variance-hardened
    # comparator: trimmed means + CI, so container jitter on a
    # milliseconds-scale workload reads "no-change" instead of a fake tax
    overhead = compare_samples(walls["off"], walls["on"])

    kernels = [{
        "kernel": k["kernel"],
        "calls": k["calls"],
        "flops": k["flops"],
        "bytes_accessed": k["bytes_accessed"],
        "peak_hbm_bytes": k["peak_hbm_bytes"],
        "achieved_gflops": round(k["achieved_flops_per_s"] / 1e9, 2)
        if k["achieved_flops_per_s"] else None,
        "intensity": k["roofline"]["arithmetic_intensity"],
        "bound": k["roofline"]["bound"],
    } for k in summ["kernels"]]

    gate_same = perf_gate(lambda: time.sleep(0.004),
                          lambda: time.sleep(0.004), repeats=7)
    gate_slow = perf_gate(lambda: time.sleep(0.004),
                          lambda: time.sleep(0.0048), repeats=7)
    return {
        "profiling_off_wall_s": overhead["base_mean_s"],
        "profiling_on_wall_s": overhead["cand_mean_s"],
        "overhead_pct": overhead["delta_pct"],
        "overhead_ci_pct": overhead["ci_pct"],
        "overhead_verdict": overhead["verdict"],
        "bit_parity_on_vs_off":
            bool(np.array_equal(outs["off"], outs["on"])),
        "device": summ["device"],
        "hbm_watermark": summ["hbm"],
        "kernels": kernels,
        "perf_gate": {
            "same_config_verdict": gate_same["verdict"],
            "synthetic_20pct_slowdown_verdict": gate_slow["verdict"],
            "slowdown_detail": gate_slow,
        },
    }


def bench_kernels(repeats=5):
    """Custom-kernel program (native/kernels.py + the Pallas kernels): the
    registry snapshot, the ranked roofline worst-offenders table
    (profiling.kernel_candidates), and per-kernel before/after — the fused
    SGNS block-gradient kernel vs the XLA _block_grads path and the flash
    attention kernel vs the XLA blockwise scan, each as its own cached
    program so the observatory captures both sides' roofline efficiency.
    Efficiency must move toward the ceiling and the wall must not regress
    on accelerator backends; on CPU containers both kernels run in Pallas
    interpret mode, so the verdicts report informationally
    (``wall_gate_applies`` false, the platform-aware-compare convention).
    Parity (atol 1e-5, the registry's pinned contract) gates everywhere."""
    import jax
    import jax.numpy as jnp

    from alink_tpu.common.benchstats import compare_samples, \
        measure_interleaved
    from alink_tpu.common.jitcache import cached_jit
    from alink_tpu.common.profiling import kernel_candidates, roofline
    from alink_tpu.dl.attention import blockwise_attention
    from alink_tpu.embedding.skipgram import _block_grads
    from alink_tpu.embedding.sgns_pallas import sgns_block_grads
    from alink_tpu.native.kernels import interpret_mode, registry

    platform = jax.devices()[0].platform
    wall_gate_applies = platform in ("tpu", "gpu")
    interp = interpret_mode()
    rng = np.random.RandomState(0)

    def bench_pair(kid, build, args_of, atol=1e-5):
        """Warm an XLA and a Pallas cached program of the same math, check
        parity, time interleaved, and read each side's roofline."""
        progs = {var: cached_jit(f"bench.{kid}_{var}", build, var)
                 for var in ("xla", "pallas")}
        args = args_of()
        outs = {var: jax.tree_util.tree_map(
            np.asarray, progs[var](*args)) for var in progs}
        flat_x = jax.tree_util.tree_leaves(outs["xla"])
        flat_p = jax.tree_util.tree_leaves(outs["pallas"])
        max_diff = max(float(np.abs(x - p).max())
                       for x, p in zip(flat_x, flat_p))
        walls = measure_interleaved(
            {var: (lambda v=var: jax.block_until_ready(progs[v](*args)))
             for var in progs}, repeats=repeats, warmup=1)
        delta = compare_samples(walls["xla"], walls["pallas"])
        eff = {}
        for var in progs:
            rows = [c for c in kernel_candidates(resolve=True)
                    if c["kernel"] == f"bench.{kid}_{var}"]
            eff[var] = rows[0]["efficiency"] if rows else None
        return {
            "parity_max_diff": max_diff,
            "parity_ok": bool(max_diff <= atol),
            "xla_wall_s": delta["base_mean_s"],
            "pallas_wall_s": delta["cand_mean_s"],
            "wall_delta_pct": delta["delta_pct"],
            "wall_verdict": delta["verdict"],
            "efficiency_before": eff["xla"],
            "efficiency_after": eff["pallas"],
        }

    # small enough that the interpret-mode grid emulation on CPU rounds
    # stays seconds-fast; real backends compile the Mosaic kernel
    B, negs, D = 1024, 4, 128

    def build_sgns(variant):
        def f(v, u_pos, u_neg):
            if variant == "pallas":
                return sgns_block_grads(v, u_pos, u_neg, interpret=interp)
            return _block_grads(v, u_pos, u_neg, D)

        return jax.jit(f)

    def sgns_args():
        return (jnp.asarray(rng.randn(B, D), jnp.float32),
                jnp.asarray(rng.randn(B, D), jnp.float32),
                jnp.asarray(rng.randn(B, negs, D), jnp.float32))

    b, s, h, d, blk = 4, 256, 4, 64, 128

    def build_attn(variant):
        def f(q, k, v, mask):
            prev = os.environ.get("ALINK_ATTN_PALLAS")
            # the knob is read at trace time; pin it to this variant for
            # the trace (variant is the cache-key static, so both programs
            # coexist)
            os.environ["ALINK_ATTN_PALLAS"] = \
                "1" if variant == "pallas" else "0"
            try:
                return blockwise_attention(q, k, v, mask, block_size=blk,
                                           causal=True)
            finally:
                if prev is None:
                    os.environ.pop("ALINK_ATTN_PALLAS", None)
                else:
                    os.environ["ALINK_ATTN_PALLAS"] = prev

        return jax.jit(f)

    def attn_args():
        return (jnp.asarray(rng.randn(b, s, h, d), jnp.float32),
                jnp.asarray(rng.randn(b, s, h, d), jnp.float32),
                jnp.asarray(rng.randn(b, s, h, d), jnp.float32),
                jnp.asarray((rng.rand(b, s) < 0.9).astype(np.int32)))

    prev_prof = os.environ.get("ALINK_PROFILING")
    try:
        os.environ["ALINK_PROFILING"] = "on"
        sgns = bench_pair("sgns", build_sgns, sgns_args)
        attn = bench_pair("attn", build_attn, attn_args)
        cands = kernel_candidates(top=8)
    finally:
        if prev_prof is None:
            os.environ.pop("ALINK_PROFILING", None)
        else:
            os.environ["ALINK_PROFILING"] = prev_prof

    candidates = [{
        "kernel": c["kernel"],
        "exec_total_s": c["exec_total_s"],
        "bound": c["bound"],
        "efficiency": c["efficiency"],
        "lost_s": c["lost_s"],
        "custom_kernel": c["custom_kernel"],
        "kernel_enabled": c["kernel_enabled"],
    } for c in cands]

    def eff_moved(pair):
        before, after = pair["efficiency_before"], pair["efficiency_after"]
        if before is None or after is None:
            return True   # no roofline capture — nothing to gate on
        return after >= before * 0.95   # toward the ceiling, 5% noise floor

    ok = (sgns["parity_ok"] and attn["parity_ok"]
          and (not wall_gate_applies
               or (eff_moved(sgns) and eff_moved(attn)
                   and sgns["wall_verdict"] in ("no-change", "improvement")
                   and attn["wall_verdict"] in ("no-change", "improvement"))))
    return {
        "platform": platform,
        "interpret_mode": interp,
        "wall_gate_applies": wall_gate_applies,
        "registry": {kid: {"knob": rec["knob"],
                           "enabled": rec["enabled"]}
                     for kid, rec in registry().items()},
        "sgns": sgns,
        "attention": attn,
        "candidates": candidates,
        "gate": {"ok": bool(ok)},
    }


def bench_aps(steps=20):
    """Pod-scale sparse-embedding exchange (parallel/aps.py): owner-routed
    pull/push on the sharded-skipgram exchange pattern — rows/s through a
    full pull→push cycle on the largest mesh, the per-device
    comm-bytes-per-step accounting behind the O(B·D) claim (routed bytes
    stay ~flat as the model axis grows; the legacy all-gather reference
    grows linearly), and a benchstats perf_gate verdict of the routed step
    against the all-gather step on identical inputs."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from alink_tpu.common.benchstats import perf_gate
    from alink_tpu.common.profiling import collective_bytes
    from alink_tpu.parallel.aps import (ShardedEmbedding, model_mesh, pull,
                                        pull_allgather, push, push_allgather)
    from alink_tpu.parallel.mesh import AXIS_MODEL
    from alink_tpu.parallel.shardmap import shard_map

    M = len(jax.devices())
    rows, D, B = 2048, 64, 1024     # per-shard rows / dim / per-device batch

    def build(m, routed, op):
        mesh = model_mesh(m)
        V = rows * m
        rng = np.random.default_rng(0)
        ids = rng.integers(0, V, size=(m, B)).astype(np.int32)
        grads = rng.normal(size=(m, B, D)).astype(np.float32)
        table = ShardedEmbedding(mesh, V, D)
        _pull = pull if routed else pull_allgather
        _push = push if routed else push_allgather

        def body(tl, i, g):
            if op in ("pull", "cycle"):
                v = _pull(tl, i[0], AXIS_MODEL, rows)
                if op == "pull":
                    return v
            g_eff = g[0] + v if op == "cycle" else g[0]
            return _push(tl, i[0], g_eff, AXIS_MODEL, rows, 1e-3)

        f = jax.jit(shard_map(body, mesh=mesh, in_specs=(P(AXIS_MODEL),) * 3,
                              out_specs=P(AXIS_MODEL), check_vma=False))
        args = (table.array,
                jax.device_put(ids, NamedSharding(mesh, P(AXIS_MODEL))),
                jax.device_put(grads, NamedSharding(mesh, P(AXIS_MODEL))))
        return f, args

    # -- throughput: routed pull→push cycle on the full mesh ---------------
    f, args = build(M, True, "cycle")
    f(*args).block_until_ready()                       # compile outside
    t0 = time.perf_counter()
    for _ in range(steps):
        out = f(*args)
    out.block_until_ready()
    rows_per_s = M * B * steps / (time.perf_counter() - t0)

    # -- per-device comm bytes per step, M=1 vs the full mesh --------------
    m_values = sorted({1, min(2, M), M})
    comm = {}
    for op in ("pull", "push"):
        for m in m_values:
            rf, ra = build(m, True, op)
            comm[f"{op}_routed_bytes_m{m}"] = collective_bytes(
                rf.lower(*ra).compile())
        gf, ga = build(M, False, op)
        comm[f"{op}_gather_bytes_m{M}"] = collective_bytes(
            gf.lower(*ga).compile())

    # fractional growth of routed bytes from the smallest multi-device mesh
    # to the full mesh: ~0 when per-device comm is O(B·D); an O(M·B·D)
    # regression reads ~(M/2 - 1). Named *_overhead so the round-over-round
    # bench gate treats lower-as-better and flags growth.
    m_small = min((m for m in m_values if m >= 2), default=M)
    scaling = {}
    for op in ("pull", "push"):
        small = comm[f"{op}_routed_bytes_m{m_small}"]
        big = comm[f"{op}_routed_bytes_m{M}"]
        scaling[f"{op}_comm_scaling_overhead"] = (
            round(big / small - 1.0, 4) if small else 0.0)

    # -- routed vs all-gather wall time on identical inputs ----------------
    gf, ga = build(M, False, "cycle")
    gf(*ga).block_until_ready()
    gate = perf_gate(lambda: gf(*ga).block_until_ready(),
                     lambda: f(*args).block_until_ready(), repeats=7)

    return {
        "model_axis": M,
        "rows_per_s": round(rows_per_s, 1),
        "batch_per_device": B,
        "dim": D,
        **comm,
        **scaling,
        "routed_vs_gather_wall_verdict": gate["verdict"],
        "routed_vs_gather_wall_delta_pct": gate["delta_pct"],
    }


def bench_huge(epochs=2):
    """Huge-embedding family end-to-end through the routed APS + hot-key
    cache (operator/batch/huge.py → embedding/skipgram.py →
    parallel/aps.py): deepwalk-embedding training rows/s on the sharded
    engine, per-device comm-bytes-per-step accounting for
    routed+cache vs routed vs the host all-gather reference (weak scaling:
    rows-per-shard constant, vocab grows with M), the measured hot-key
    cache traffic reduction + hit rate on a Zipf workload, and a benchstats
    perf_gate of the cached step against the uncached routed step."""
    import jax

    from alink_tpu.common.benchstats import perf_gate
    from alink_tpu.common.metrics import metrics
    from alink_tpu.embedding import (SkipGramConfig, build_vocab, make_pairs,
                                     train_skipgram_sharded)
    from alink_tpu.embedding.walks import build_csr, random_walks

    M = len(jax.devices())

    # -- the real workload: deepwalk corpus on a Zipf-degree graph ---------
    rng = np.random.default_rng(0)
    n_nodes, n_edges = 1024, 4096
    src = rng.integers(0, n_nodes, n_edges)
    dst = np.minimum(rng.zipf(1.5, n_edges) - 1, n_nodes - 1)
    indptr, indices, w = build_csr(src, dst, num_nodes=n_nodes)
    walks = random_walks(indptr, indices, w, num_walks=1, walk_length=10,
                         seed=1)
    docs = [[str(v) for v in row] for row in walks]
    vocab, counts = build_vocab(docs)
    cfg = SkipGramConfig(dim=64, window=3, negatives=4, epochs=epochs,
                         batch_size=256, seed=0)
    pairs = make_pairs(docs, vocab, counts, cfg.window, 0.0, cfg.seed)
    pairs = pairs[:20_000]    # cap the drill so the extra stays minutes-fast
    V = len(vocab)
    hot = 256

    def run(hot_rows):
        return train_skipgram_sharded(pairs, V, counts, cfg,
                                      hot_rows=hot_rows).to_numpy()

    # first calls compile (ProgramCache); the timed calls are pure runs
    h0, m0 = (metrics.counter("aps.cache_hits"),
              metrics.counter("aps.cache_misses"))
    emb_cached = run(hot)
    hits = metrics.counter("aps.cache_hits") - h0
    misses = metrics.counter("aps.cache_misses") - m0
    hit_rate = hits / max(1, hits + misses)
    emb_routed = run(0)
    bit_parity = bool(np.array_equal(emb_cached, emb_routed))

    used = (pairs.shape[0] // (cfg.batch_size * M)) * cfg.batch_size * M
    t0 = time.perf_counter()
    run(hot)
    rows_per_s = used * cfg.epochs / (time.perf_counter() - t0)

    gate = perf_gate(lambda: run(0), lambda: run(hot), repeats=3)
    # the cache optimizes WIRE BYTES (the TPU ICI cost, gated via the HLO
    # accounting below); a CPU mesh's collectives are shared-memory copies
    # — latency-bound, bytes are ~free — so the wall verdict there reads
    # the cache's fixed per-step overhead with none of its benefit. Gate
    # wall only on accelerator backends (the platform-aware-compare
    # convention from docs/bench_schema.md), advisory elsewhere.
    platform = jax.devices()[0].platform
    wall_gate_applies = platform in ("tpu", "gpu")

    # -- comm-bytes accounting: the canonical weak-scaling probe (shared
    # with tests/test_weak_scaling.py so the CI pin and this bench always
    # measure the same compiled program)
    from alink_tpu.embedding.engine import collective_bytes_probe

    m_values = sorted({1, min(2, M), M})
    comm = {}
    for m in m_values:
        comm[f"routed_bytes_m{m}"] = collective_bytes_probe(m, "sharded")
        if m >= 2:
            comm[f"cached_bytes_m{m}"] = collective_bytes_probe(
                m, "sharded", hot_rows=16)
            comm[f"gather_bytes_m{m}"] = collective_bytes_probe(m, "host")

    # fractional growth from the smallest multi-device mesh to the full
    # mesh, named *_overhead so the round-over-round gate flags growth
    m_small = min((m for m in m_values if m >= 2), default=M)
    scaling = {}
    for kind in ("routed", "cached"):
        small = comm.get(f"{kind}_bytes_m{m_small}")
        big = comm.get(f"{kind}_bytes_m{M}")
        scaling[f"{kind}_comm_scaling_overhead"] = (
            round(big / small - 1.0, 4) if small and big else 0.0)
    cache_reduction = (1.0 - comm[f"cached_bytes_m{M}"]
                       / comm[f"routed_bytes_m{M}"]) \
        if comm.get(f"routed_bytes_m{M}") else 0.0

    # on a single-device environment every comm verdict is vacuous (zero
    # collective traffic either way) — gate on what is measurable there
    ok = (hit_rate > 0 and bit_parity
          and (not wall_gate_applies
               or gate["verdict"] in ("no-change", "improvement"))
          and (M < 2 or cache_reduction > 0))
    return {
        "model_axis": M,
        "platform": platform,
        "comm_verdicts_vacuous_single_device": M < 2,
        "vocab": V,
        "pairs": int(pairs.shape[0]),
        "deepwalk_rows_per_s": round(rows_per_s, 1),
        "cache_hot_rows": hot,
        "cache_hit_rate": round(hit_rate, 4),
        "cache_bit_parity_vs_routed": bit_parity,
        "cache_traffic_reduction_pct": round(100 * cache_reduction, 2),
        **comm,
        **scaling,
        "cached_vs_routed_wall_verdict": gate["verdict"],
        "cached_vs_routed_wall_delta_pct": gate["delta_pct"],
        "wall_gate_applies": wall_gate_applies,
        "gate": {"ok": bool(ok)},
    }


def main(argv=None):
    import argparse

    ap = argparse.ArgumentParser(
        description="alink_tpu benchmark driver / BENCH regression gate")
    ap.add_argument(
        "--compare", nargs=2, metavar=("OLD", "NEW"),
        help="compare two BENCH round json files (raw driver output or the "
             "archived {parsed: ...} wrapper) and print the regression "
             "report; exit code 1 when a significant regression is found")
    ap.add_argument(
        "--threshold", type=float, default=None,
        help="override every per-metric noise threshold "
             "(fraction, e.g. 0.1 = 10%%)")
    ap.add_argument(
        "--only", default=None, metavar="NAME[,NAME...]",
        help="run only the named extras (e.g. 'coldstart' or "
             "'compile,serving') and skip the primary BERT metric; prints "
             "the same JSON shape with metric=extras_subset. Unlike the "
             "full run (where a failing extra never sinks the primary "
             "metric), this mode IS the gate: exit 1 when any selected "
             "extra errors or reports gate.ok=false, 2 on unknown names")
    ap.add_argument(
        "--trace-artifact", default=None, metavar="PATH",
        help="after the run, write the span ring as a Perfetto-loadable "
             "chrome://tracing JSON to PATH (open at ui.perfetto.dev) — "
             "the measured span waterfall feeding the kernel-candidates "
             "ranking; keep it next to the round's JSON")
    args = ap.parse_args(argv)
    if args.compare:
        from alink_tpu.common.benchstats import compare_bench_files

        report = compare_bench_files(args.compare[0], args.compare[1],
                                     threshold=args.threshold)
        print(json.dumps(report, indent=2))
        return 1 if report["regressions"] else 0

    bench_fns = (
        ("kmeans_iris", bench_kmeans_iris),
        ("softmax_mnist", bench_softmax_mnist),
        ("gbdt_train", bench_gbdt),
        ("torch_stream_predict", bench_torch_stream),
        ("resnet50_predict", bench_resnet50),
        ("resnet50_savedmodel", bench_resnet50_savedmodel),
        ("bert_text_quality", bench_bert_quality),
        ("executor", bench_executor),
        ("resilience", bench_resilience),
        ("recovery", bench_recovery),
        ("elastic", bench_elastic),
        ("modelstream", bench_modelstream),
        ("compile", bench_compile),
        ("coldstart", bench_coldstart),
        ("observability", bench_observability),
        ("profiling", bench_profiling),
        ("kernels", bench_kernels),
        ("serving", bench_serving),
        ("fleet", bench_fleet),
        ("aps", bench_aps),
        ("huge", bench_huge),
        # LAST on purpose: train_scale compiles its own program family, and
        # running it before the `compile` extra would inflate that extra's
        # cumulative program_cache.compile_s reading vs earlier rounds
        ("train_scale", bench_train_scale),
    )
    only = {n.strip() for n in args.only.split(",")} if args.only else None
    if only is not None:
        known = {n for n, _ in bench_fns}
        unknown = sorted(only - known)
        if unknown:
            # a typoed gate must fail loudly, not pass having run nothing
            print(f"unknown extras {unknown}; known: {sorted(known)}",
                  file=sys.stderr)
            return 2
    extras = {}
    for name, fn in bench_fns:
        if only is not None and name not in only:
            continue
        try:
            extras[name] = fn()
        except Exception as e:  # a failing extra must not sink the primary
            extras[name] = {"error": f"{type(e).__name__}: {e}"[:300]}

    if args.trace_artifact:
        # stderr so stdout stays the parseable BENCH JSON
        try:
            from alink_tpu.common.tracing import write_chrome_trace

            n = write_chrome_trace(args.trace_artifact)
            print(f"trace artifact: {args.trace_artifact} ({n} spans)",
                  file=sys.stderr)
        except Exception as e:
            print(f"trace artifact failed: {e}", file=sys.stderr)

    if only is not None:
        print(json.dumps({"metric": "extras_subset", "value": None,
                          "unit": None, "vs_baseline": None,
                          "extras": extras}))
        failed = any(
            isinstance(v, dict)
            and ("error" in v
                 or (isinstance(v.get("gate"), dict)
                     and not v["gate"].get("ok", True)))
            for v in extras.values())
        return 1 if failed else 0

    per_chip, mfu = bench_bert()
    extras["bert_mfu"] = mfu
    print(json.dumps({
        "metric": "bert_base_finetune_throughput_per_chip",
        "value": round(per_chip, 1),
        "unit": "samples/sec/chip (seq128, bs32, bf16)",
        "vs_baseline": round(per_chip / A100_BERT_BASE_SAMPLES_PER_SEC, 3),
        "extras": extras,
    }))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
