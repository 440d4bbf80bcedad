"""First contact with the chip: the main path, once, through the entry points a
user calls, at the full width of BERT-base.

    python chip_smoke.py          # on a machine with a TPU; one process

What it drives (every phase's failure is the run's failure — nothing here
catches an error and carries on):

1. ``device``     — jax's default backend must be ``tpu`` on every device;
                    prints platform, device kind, count, jax version and the
                    compile-cache directory in effect.
2. ``checkpoint`` — a BERT-base checkpoint (12 x 768, 12 heads, FFN 3072, the
                    30,522-row embedding) with random weights from a seed,
                    written in the HF layout the train op ingests.
3. ``train``      — ``BertTextClassifierTrainBatchOp`` fine-tunes from it on
                    rows of the shipped ``data/sst2_mini.csv`` (bf16 compute,
                    seq 128, batch 32 per chip, one batch repeated): loss
                    finite at every step and lower at the end than at the
                    start; on several chips the batch sits on all of them and
                    every chip's memory shows the step ran there.
4. ``blockwise``  — the same op with ``attentionBlockSize=128`` (depth cut to
                    2) takes its steps with no knob set, i.e. through whatever
                    attention path the kernel gate picks on this backend.
5. ``serve``      — the trained model in ``ModelServer`` with its warm-up;
                    ``predict``/``predict_many`` of mixed batch sizes answer
                    with ``BertTextClassifierPredictBatchOp``'s labels and
                    probabilities within a bf16 tolerance; zero ``jit.trace``
                    growth after the warm-up.
6. ``kernels``    — each registered Pallas kernel, compiled by Mosaic through
                    its caller at bench size with no knob set, against the XLA
                    path the knob-off route compiles.
7. ``wire``       — the staging path ships a >=4 MiB float32 block exactly (no
                    bf16 rounding) and the host->device probe reading is
                    printed.

Walls printed per phase are smoke readings (they include compilation), not
benchmark numbers. The last line of stdout is one JSON object,
``{"ok": true, "device": {"platform", "kind", "count"}}``; any failure exits
non-zero without it. ``tests/test_chip_smoke.py`` drives the same phases at toy
width on the CPU test mesh through :func:`run`.
"""

from __future__ import annotations

import dataclasses
import json
import os
import shutil
import sys
import tempfile
import time
from typing import Any, Callable, Dict, List, Tuple

import numpy as np

# probabilities computed in bf16 by two differently-tiled programs (the
# server's batch buckets against the predict op's one batch): bf16 keeps 8
# bits of mantissa, and 12 layers of it sit under the softmax
PROB_TOL = 2e-2
SEED = 0


@dataclasses.dataclass(frozen=True)
class Width:
    """Model and batch sizes of one run; ``FULL`` is BERT-base as published."""

    name: str
    vocab_size: int
    hidden_size: int
    num_layers: int
    num_heads: int
    intermediate_size: int
    seq: int
    per_chip_batch: int
    steps: int
    learning_rate: float
    serve_batch_rows: int
    block_size: int
    # kernel phase: forest rows x features, depth, bins, trees; SGNS vocab,
    # pairs, dim, negatives, batch; attention batch, seq
    forest: Tuple[int, int, int, int, int]
    sgns: Tuple[int, int, int, int, int]
    attn: Tuple[int, int]


FULL = Width(
    name="full", vocab_size=30522, hidden_size=768, num_layers=12,
    num_heads=12, intermediate_size=3072, seq=128, per_chip_batch=32,
    # 3e-6: every weight is random, so Adam's first sign-like steps move all
    # 109M of them at once; at 1e-4 the loss on the repeated batch jumps
    # from 0.56 to 2.6 after one update (chip and cpu agree to 3 digits)
    steps=12, learning_rate=3e-6, serve_batch_rows=32, block_size=128,
    forest=(50_000, 20, 6, 64, 2), sgns=(1024, 20_000, 64, 4, 256),
    attn=(8, 512))
TOY = Width(
    name="toy", vocab_size=256, hidden_size=32, num_layers=2, num_heads=4,
    intermediate_size=64, seq=16, per_chip_batch=2, steps=6,
    learning_rate=3e-3, serve_batch_rows=16, block_size=8,
    forest=(256, 4, 3, 8, 1), sgns=(64, 256, 8, 2, 8), attn=(2, 32))


# ---------------------------------------------------------------------------
# phases: each takes the shared context, returns the facts it established
# ---------------------------------------------------------------------------


def phase_device(ctx: Dict[str, Any]) -> Dict[str, Any]:
    import jax

    from alink_tpu.common.jitcache import compile_cache_dir

    devs = jax.devices()
    if ctx["require_tpu"]:
        platforms = sorted({d.platform for d in devs})
        if jax.default_backend() != "tpu" or platforms != ["tpu"]:
            raise SystemExit(
                f"chip_smoke: no chip — jax's default backend is "
                f"{jax.default_backend()!r} (devices: {platforms}); this "
                f"script only runs on a TPU")
    ctx["device"] = {"platform": devs[0].platform,
                     "kind": devs[0].device_kind, "count": len(devs)}
    return {"platform": devs[0].platform, "device_kind": devs[0].device_kind,
            "device_count": len(devs), "jax": jax.__version__,
            "compile_cache_dir": compile_cache_dir(),
            "JAX_COMPILATION_CACHE_DIR":
                os.environ.get("JAX_COMPILATION_CACHE_DIR")}


def _rows(n: int) -> Tuple[List[str], np.ndarray]:
    """``n`` rows of the shipped sentiment set, seven negative to one
    positive: the weights are random, so until the encoder tells sentences
    apart the only thing a step can learn is the label balance — an uneven
    one gives the loss a direction to fall in (from ~0.58 towards 0.38) that
    the dropout noise of a training-mode loss (~0.06 here) does not hide."""
    from alink_tpu.dl.data import load_sst2

    texts, labels = load_sst2()
    neg = iter(i for i, y in enumerate(labels) if y == 0)
    pos = iter(i for i, y in enumerate(labels) if y == 1)
    pick = [next(pos) if j % 8 == 7 else next(neg) for j in range(n)]
    return [texts[i] for i in pick], np.asarray([labels[i] for i in pick])


def _write_checkpoint(w: Width, layers: int, path: str) -> int:
    """Random weights from a seed, in the layout a user's pretrained
    checkpoint has (HF: config.json + model.safetensors + vocab.txt); the
    fine-tune op reads it from disk like any other. Returns the parameter
    count."""
    import jax

    from alink_tpu.dl.data import load_sst2
    from alink_tpu.dl.modules import BertConfig, TransformerEncoder
    from alink_tpu.dl.pretrained import save_bert_checkpoint
    from alink_tpu.dl.tokenizer import Tokenizer

    vocab = Tokenizer.build(load_sst2()[0], vocab_size=w.vocab_size).to_list()
    vocab += [f"[unused{i}]" for i in range(w.vocab_size - len(vocab))]
    cfg = BertConfig(vocab_size=w.vocab_size, hidden_size=w.hidden_size,
                     num_layers=layers, num_heads=w.num_heads,
                     intermediate_size=w.intermediate_size,
                     max_position=max(w.seq, 128), pool="cls")
    sample = np.zeros((1, w.seq), np.int32)
    shapes = jax.eval_shape(
        lambda: TransformerEncoder(cfg).init(
            jax.random.PRNGKey(SEED), sample, sample, sample))["params"]
    rng = np.random.default_rng(SEED)

    def fill(path, leaf):
        name = path[-1].key
        if name == "scale":
            return np.ones(leaf.shape, np.float32)
        if name == "bias":
            return np.zeros(leaf.shape, np.float32)
        return rng.normal(0.0, 0.02, leaf.shape).astype(np.float32)

    params = jax.tree_util.tree_map_with_path(fill, shapes)
    save_bert_checkpoint(params, cfg, path, vocab)
    return sum(int(np.prod(x.shape))
               for x in jax.tree_util.tree_leaves(params))


def phase_checkpoint(ctx: Dict[str, Any]) -> Dict[str, Any]:
    w: Width = ctx["width"]
    ckpt = os.path.join(ctx["workdir"], "bert-checkpoint")
    ctx["n_params"] = _write_checkpoint(w, w.num_layers, ckpt)
    ctx["checkpoint"] = ckpt
    return {"params": ctx["n_params"], "vocab_rows": w.vocab_size,
            "layers": w.num_layers, "hidden": w.hidden_size,
            "bytes": os.path.getsize(os.path.join(ckpt, "model.safetensors"))}


def _train(ctx: Dict[str, Any], **overrides) -> Tuple[Any, List[float]]:
    """One fine-tune through the batch op; returns the (executed) op and the
    loss of every step. Rows == batch, so every step sees the same batch."""
    import jax

    from alink_tpu.common.metrics import metrics
    from alink_tpu.common.mtable import MTable
    from alink_tpu.operator.batch.base import TableSourceBatchOp
    from alink_tpu.operator.batch.dl import BertTextClassifierTrainBatchOp

    w: Width = ctx["width"]
    batch = w.per_chip_batch * len(jax.devices())
    texts, labels = _rows(batch)
    src = TableSourceBatchOp(MTable({"text": texts, "label": labels}))
    params = dict(textCol="text", labelCol="label", maxSeqLength=w.seq,
                  checkpointFilePath=ctx["checkpoint"], batchSize=batch,
                  numEpochs=w.steps, learningRate=w.learning_rate,
                  randomSeed=SEED)
    params.update(overrides)
    seen = len(metrics.series("dl.train"))
    op = BertTextClassifierTrainBatchOp(**params).link_from(src)
    op.collect()
    losses = [float(r["loss"]) for r in metrics.series("dl.train")[seen:]]
    if len(losses) != params["numEpochs"]:
        raise RuntimeError(f"expected {params['numEpochs']} recorded steps, "
                           f"got {len(losses)}")
    if not all(np.isfinite(losses)):
        raise RuntimeError(f"non-finite loss: {losses}")
    return op, losses


def phase_train(ctx: Dict[str, Any]) -> Dict[str, Any]:
    import jax

    from alink_tpu.common.env import MLEnvironmentFactory
    from alink_tpu.dl.sharding import batch_sharding

    w: Width = ctx["width"]
    devs = jax.devices()
    op, losses = _train(ctx)
    if not losses[-1] < losses[0]:
        raise RuntimeError(f"loss did not fall on the repeated batch: "
                           f"{losses}")
    ctx["train_op"] = op
    facts: Dict[str, Any] = {
        "steps": len(losses), "loss_first": round(losses[0], 4),
        "loss_last": round(losses[-1], 4),
        "global_batch": w.per_chip_batch * len(devs)}
    # placement: the batch the trainer ships goes through this sharding on
    # the session mesh, and the step must have run on every device
    mesh = MLEnvironmentFactory.get_default().mesh
    batch = jax.device_put(
        np.zeros((w.per_chip_batch * len(devs), w.seq), np.int32),
        batch_sharding(mesh, 2, seq_axis=1))
    on = {s.device for s in batch.addressable_shards}
    rows = {s.data.shape[0] for s in batch.addressable_shards}
    if on != set(devs) or rows != {w.per_chip_batch}:
        raise RuntimeError(f"batch is not spread over the devices: "
                           f"{len(on)} of {len(devs)} hold rows {rows}")
    facts["batch_shards_on_devices"] = len(on)
    stats = [d.memory_stats() for d in devs]
    if all(s is not None for s in stats):
        # every device held the replicated fp32 weights and ran the step
        param_bytes = 4 * ctx["n_params"]
        peaks = [int(s["peak_bytes_in_use"]) for s in stats]
        if min(peaks) < param_bytes:
            raise RuntimeError(
                f"a device never held the model: peak bytes {peaks}, "
                f"weights alone are {param_bytes}")
        facts["peak_bytes_in_use_per_device"] = peaks
    elif ctx["require_tpu"]:
        raise RuntimeError("the TPU backend reported no memory_stats()")
    return facts


def phase_blockwise(ctx: Dict[str, Any]) -> Dict[str, Any]:
    """``attentionBlockSize`` with no knob set: on one chip that is the
    flash kernel with its XLA backward, on several the XLA scan."""
    from alink_tpu.dl.attn_pallas import use_attn_pallas
    from alink_tpu.native.kernels import interpret_mode

    w: Width = ctx["width"]
    cut = os.path.join(ctx["workdir"], "bert-checkpoint-2-layers")
    _write_checkpoint(w, 2, cut)
    _, losses = _train(ctx, checkpointFilePath=cut, numEpochs=3,
                       attentionBlockSize=w.block_size)
    return {"steps": len(losses), "loss_first": round(losses[0], 4),
            "loss_last": round(losses[-1], 4),
            "flash_kernel": use_attn_pallas(),
            "interpreted": interpret_mode()}


def phase_serve(ctx: Dict[str, Any]) -> Dict[str, Any]:
    from alink_tpu.common.metrics import metrics
    from alink_tpu.common.mtable import MTable
    from alink_tpu.operator.batch.base import TableSourceBatchOp
    from alink_tpu.operator.batch.dl import BertTextClassifierPredictBatchOp
    from alink_tpu.pipeline import BertTextClassifierModel, PipelineModel
    from alink_tpu.serving import ModelServer, ServingConfig

    w: Width = ctx["width"]
    model_table = ctx["train_op"].collect()
    sizes = [1, 1, 5, w.serve_batch_rows // 2 + 1, w.serve_batch_rows]
    texts, _ = _rows(sum(sizes))
    stage = BertTextClassifierModel(
        predictionCol="pred", predictionDetailCol="detail")
    stage.set_model_data(model_table)
    server = ModelServer(ServingConfig(max_batch_rows=w.serve_batch_rows,
                                       default_timeout_s=300.0))
    try:
        info = server.load("bert", PipelineModel(stage), "text string",
                           warmup_rows=[(texts[0],), (texts[1],)])
        traces = metrics.counter("jit.trace")
        served: List[tuple] = []
        at = 0
        for n in sizes:
            chunk = [(t,) for t in texts[at:at + n]]
            served += [server.predict("bert", chunk[0])] if n == 1 \
                else server.predict_many("bert", chunk)
            at += n
        grew = metrics.counter("jit.trace") - traces
        stats = server.stats()["models"][0]
    finally:
        server.close()
    if grew:
        raise RuntimeError(f"{grew} new jit traces after the server's "
                           f"warm-up")
    ref = BertTextClassifierPredictBatchOp(
        predictionCol="pred", predictionDetailCol="detail").link_from(
        ctx["train_op"], TableSourceBatchOp(MTable({"text": texts}))
    ).collect()
    names = list(ref.schema.names)
    ref_rows = [ref.get_row(i) for i in range(ref.num_rows)]
    if len(served) != len(ref_rows):
        raise RuntimeError("the server answered a different number of rows")
    ip, idt = names.index("pred"), names.index("detail")
    worst, close_calls = 0.0, 0
    for got, want in zip(served, ref_rows):
        pg, pw = json.loads(got[idt]), json.loads(want[idt])
        diff = max(abs(pg[k] - pw[k]) for k in pw)
        worst = max(worst, diff)
        margin = abs(np.subtract(*sorted(pw.values())[-2:]))
        if got[ip] != want[ip]:
            if margin > 2 * PROB_TOL:
                raise RuntimeError(
                    f"server label {got[ip]!r} != predict op's "
                    f"{want[ip]!r} at margin {margin:.4f}: {pg} vs {pw}")
            close_calls += 1   # a coin-flip row inside the tolerance
    if worst > PROB_TOL:
        raise RuntimeError(f"probabilities differ by {worst:.4g} "
                           f"(tolerance {PROB_TOL})")
    return {"warmup": info["warmup"], "requests": len(sizes),
            "rows": len(served), "batches": stats["batches"],
            "jit_trace_growth_after_warmup": grew,
            "prob_max_abs_diff_vs_predict_op": worst,
            "bit_equal_to_predict_op": worst == 0.0,
            "label_flips_inside_tolerance": close_calls}


def phase_kernels(ctx: Dict[str, Any]) -> Dict[str, Any]:
    """Each registered kernel through its caller, default gate against
    knob-off. The XLA side runs at ``highest`` matmul precision: on a TPU
    XLA's default rounds fp32 matmul operands to bf16, which is a property
    of that path and not the difference this phase is after."""
    import jax
    import jax.numpy as jnp

    from alink_tpu.dl.attention import blockwise_attention, packed_attention
    from alink_tpu.dl.attn_pallas import use_attn_pallas
    from alink_tpu.dl.retention import phi_dim, retention_chunk
    from alink_tpu.dl.retention_pallas import use_retention_pallas
    from alink_tpu.embedding import SkipGramConfig, train_skipgram_sharded
    from alink_tpu.embedding.sgns_pallas import use_sgns_pallas
    from alink_tpu.native.kernels import interpret_mode, kernel_ids
    from alink_tpu.tree import grow
    from alink_tpu.tree.pallas_hist import use_pallas_hist

    w: Width = ctx["width"]
    rng = np.random.default_rng(SEED)
    facts: Dict[str, Any] = {"interpreted": interpret_mode()}
    if ctx["require_tpu"] and interpret_mode():
        raise RuntimeError("kernels would be interpreted on the chip path")

    n, d, depth, bins, trees = w.forest
    X = rng.normal(size=(n, d)).astype(np.float32)
    y = (X[:, 0] + 0.5 * X[:, 1] ** 2 > 0.3).astype(np.float32)

    def forest():
        grow._level_fn.cache_clear()   # the level program captures the gate
        return grow.train_forest(
            X, y, task="binary", num_trees=trees, depth=depth, num_bins=bins,
            bootstrap=False, feature_fraction=1.0).raw_predict(X)

    V, n_pairs, dim, negs, bsz = w.sgns
    pairs = rng.integers(0, V, (n_pairs, 2)).astype(np.int32)
    counts = np.ones(V)
    sg = SkipGramConfig(dim=dim, negatives=negs, epochs=1, batch_size=bsz,
                        seed=SEED)

    def sgns():
        return train_skipgram_sharded(pairs, V, counts, sg).to_numpy()

    b, s = w.attn
    h, hd = FULL.num_heads, FULL.hidden_size // FULL.num_heads
    q, k, v = (jnp.asarray(rng.normal(size=(b, s, h, hd)), jnp.float32)
               for _ in range(3))
    mask = jnp.asarray(rng.integers(0, 2, (b, s)), jnp.int32).at[:, 0].set(1)

    def attn():
        # a fresh jit per call: the gate is read while tracing. Both of the
        # module's kernels: the block update under blockwise attention, and
        # the fused core of the encoder's default attention (which takes
        # the XLA path by itself at the toy width's length)
        def both(q, k, v, m):
            o = blockwise_attention(q, k, v, m, block_size=w.block_size)
            qkv = jnp.stack([q, k, v], axis=2).reshape(b, s, 3, h * hd)
            return jnp.concatenate(
                [o.reshape(b, s, h * hd),
                 packed_attention(qkv, m, num_heads=h)], axis=-1)

        return np.asarray(jax.jit(both)(q, k, v, mask))

    rd, rt = 128, 16          # a retention head is 128 wide at any width
    rq, rk, rv, rs = (jnp.asarray(rng.normal(size=shape), jnp.float32)
                      for shape in ((2, rt, 4, rd), (2, rt, 2, rd),
                                    (2, rt, 2, rd), (2, 2, phi_dim(rd), rd)))
    rz = jnp.abs(rs[..., 0]) + 1.0
    rg = jnp.asarray(-rng.uniform(0.0, 0.1, (2, rt, 2)), jnp.float32)
    rvalid = jnp.arange(rt)[None, :] < jnp.asarray([rt, rt - 5])[:, None]

    def retention():
        # a prompt chunk on an inherited state, one row ending inside it
        out = jax.jit(lambda *a: retention_chunk(*a, eps=1e-6))(
            rq, rk, rv, rg, rvalid, rs, rz)
        return np.concatenate([np.asarray(x).ravel() for x in out])

    checks = {
        "tree.pallas_hist": ("ALINK_GBDT_PALLAS", use_pallas_hist, forest,
                             1e-5),
        "embedding.sgns_pallas": ("ALINK_SGNS_PALLAS", use_sgns_pallas, sgns,
                                  5e-5),
        "dl.attn_pallas": ("ALINK_ATTN_PALLAS", use_attn_pallas, attn, 1e-5),
        # sums over 8,256 products in another order, values up to 5
        "dl.retention_pallas": ("ALINK_RETENTION_PALLAS",
                                use_retention_pallas, retention, 1e-4),
    }
    if set(checks) != set(kernel_ids()):
        raise RuntimeError(f"registered kernels {kernel_ids()} are not the "
                           f"ones this phase checks")
    with jax.default_matmul_precision("highest"):
        for kid, (knob, gate, fn, atol) in checks.items():
            if knob in os.environ:
                raise RuntimeError(f"{knob} is set: this phase checks what "
                                   f"runs with no knob set")
            on = bool(gate())
            default = fn()
            os.environ[knob] = "0"          # the XLA path
            try:
                off = fn()
            finally:
                del os.environ[knob]
            diff = float(np.abs(default - off).max())
            if not np.isfinite(default).all() or diff > atol:
                raise RuntimeError(
                    f"{kid}: default path differs from the XLA path by "
                    f"{diff:.3g} (registered tolerance {atol})")
            facts[kid] = {"default_on": on, "max_abs_diff_vs_xla": diff,
                          "atol": atol}
    grow._level_fn.cache_clear()
    return facts


def phase_wire(ctx: Dict[str, Any]) -> Dict[str, Any]:
    from alink_tpu.common.staging import (measured_wire_mbps, stage_replicated,
                                          staging_cache_stats, wire_is_slow,
                                          wire_precision)

    slow = wire_is_slow()        # takes the one-shot host->device reading
    block = np.random.default_rng(SEED).normal(
        size=(1 << 20, 2)).astype(np.float32)           # 8 MiB, fp32
    if not np.array_equal(np.asarray(stage_replicated(block)), block):
        raise RuntimeError("staging did not ship the fp32 block exactly")
    saved = staging_cache_stats()["wire_bytes_saved"]
    if saved:
        raise RuntimeError(f"bf16 wire rounding happened on this run: "
                           f"{saved} bytes saved by downcasting")
    mbps = measured_wire_mbps()
    if mbps is None:
        raise RuntimeError("the host->device probe did not run "
                           "(ALINK_ASSUME_SLOW_WIRE is set?)")
    return {"policy": wire_precision(), "probe_slow": slow,
            "host_to_device_MB_per_s": round(mbps, 1),
            "bf16_wire_bytes_saved": saved}


PHASES: List[Tuple[str, Callable[[Dict[str, Any]], Dict[str, Any]]]] = [
    ("device", phase_device),
    ("checkpoint", phase_checkpoint),
    ("train", phase_train),
    ("blockwise", phase_blockwise),
    ("serve", phase_serve),
    ("kernels", phase_kernels),
    ("wire", phase_wire),
]


def run(width: Width, *, require_tpu: bool = True) -> Dict[str, Any]:
    """Run every phase in order; the first failure propagates. Returns the
    device block of the final JSON line."""
    import alink_tpu  # noqa: F401 — places the compile cache before jax loads
    from alink_tpu.common.jitcache import persist_summary
    from alink_tpu.common.metrics import metrics

    t_start = time.perf_counter()
    workdir = tempfile.mkdtemp(prefix="chip-smoke-")
    ctx: Dict[str, Any] = {"width": width, "require_tpu": require_tpu,
                           "workdir": workdir}
    try:
        for name, phase in PHASES:
            t0 = time.perf_counter()
            facts = phase(ctx)
            print(f"[{name}] wall {time.perf_counter() - t0:.1f}s "
                  f"(smoke reading, compiles included) "
                  f"{json.dumps(facts, default=str)}", flush=True)
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    persist = persist_summary()
    print(f"[cache] dir {persist['dir']} entries {persist['entries']} "
          f"bytes {persist['bytes']} "
          f"persist_hit {metrics.counter('jit.persist_hit')} "
          f"persist_miss {metrics.counter('jit.persist_miss')} "
          f"persist_error {metrics.counter('jit.persist_error')} "
          f"jit.compile {metrics.counter('jit.compile')}", flush=True)
    print(f"[total] width {width.name} wall "
          f"{time.perf_counter() - t_start:.1f}s (smoke reading)", flush=True)
    return ctx["device"]


def main() -> int:
    device = run(FULL)
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
