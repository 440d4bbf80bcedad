"""Traffic kind ``finetune``: one call of the BERT train op over a table made
from the seed; epoch 0 is set-up (it compiles, and holds the steps that the
reference follows), the window is the run of the remaining epochs of that same
call, its ends read from the ``train.epoch`` spans the program already records
(each epoch ends on a device synchronisation, ``float(loss)``).

The op gives no view of its state between steps, so the kind interposes on the
compiled step the op builds (``dl.train.make_train_step``): a pass-through
wrapper that, for the job's first steps only, keeps the batch it was fed, the
loss it returned, Adam's first moment after step 1 (the first gradient as the
optimizer got it, copied to the host) and the parameters before step 1 and as
step 4 receives them. The compiled program, its state and its feed are the
op's own; from the fifth call on the wrapper adds one Python frame.

Two kinds of number are read on the first gradient. The gap of norms
(``| ||program|| - ||reference|| |``) averages unbiased rounding away: a float8
stand-in reads no worse there than the bfloat16 program, and half a batch can
by chance have the whole batch's norm. The norm of the difference
(``||program - reference||``, never smaller than the gap) averages nothing
away, and its median tensor is the number the lower-precision control fails.
"""

from __future__ import annotations

import math
import statistics
import threading
import time
from functools import partial
from typing import Any, Dict, List

import numpy as np


def hf_parts(path: tuple, leaf) -> Dict[str, Any]:
    """One leaf of the program's parameter tree as the published model's
    tensors, by their HF names. The program fuses query, key and value into
    one leaf (kernel (hidden, 3, hidden), bias (3, hidden)): its three parts
    are three tensors here, so that a key's bias, whose gradient is nought
    under softmax, is judged by itself. A dense kernel, (in, out) in the
    program, is turned to the published (out, in)."""
    wb = {"kernel": "weight", "bias": "bias", "scale": "weight",
          "embedding": "weight"}[path[-1]]
    if path[-1] == "kernel":
        return {n: a.T for n, a in _hf_parts(path, leaf, wb).items()}
    return _hf_parts(path, leaf, wb)


def _hf_parts(path: tuple, leaf, wb: str) -> Dict[str, Any]:
    top = path[0]
    emb = {"tok_emb": "word_embeddings", "pos_emb": "position_embeddings",
           "type_emb": "token_type_embeddings", "ln_emb": "LayerNorm"}
    if top in emb:
        return {f"bert.embeddings.{emb[top]}.{wb}": leaf}
    if top == "pooler":
        return {f"bert.pooler.dense.{wb}": leaf}
    if top == "head":
        return {f"classifier.{wb}": leaf}
    p = f"bert.encoder.layer.{top.split('_')[1]}."
    sub = path[1:-1]
    if sub == ("attention", "qkv"):
        return {p + f"attention.self.{n}.{wb}": leaf[..., j, :]
                for j, n in enumerate(("query", "key", "value"))}
    name = {("attention", "out"): "attention.output.dense",
            ("ln_att",): "attention.output.LayerNorm",
            ("mlp_in",): "intermediate.dense", ("mlp_out",): "output.dense",
            ("ln_mlp",): "output.LayerNorm"}[sub]
    return {p + f"{name}.{wb}": leaf}


def tensors_of(tree) -> Dict[str, Any]:
    """A parameter-shaped tree as {HF name: array}."""
    import jax

    out: Dict[str, Any] = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        out.update(hf_parts(tuple(k.key for k in path), leaf))
    return out


class ReadingsDone(Exception):
    """Raised by a recorder told to stop the job once the checked steps are
    kept (``benchmark/tests/readings.py``: many seeds in one process)."""


class StepRecorder:
    """Pass-through round the op's compiled train step; see the module
    docstring. ``checked`` is the number of first steps it keeps."""

    def __init__(self, step, checked: int, stop_when_kept: bool = False):
        self._step, self.checked, self._stop = step, checked, stop_when_kept
        self.calls = 0
        self.batches: List[Dict[str, np.ndarray]] = []
        self.labels: List[np.ndarray] = []
        self.losses: List[Any] = []
        self.first_mu = None
        self.p_before = self.p_after = None

    def __call__(self, variables, opt_state, batch, yb, wb, dkey=None):
        import jax

        n = self.calls
        self.calls += 1
        if n > self.checked:
            return self._step(variables, opt_state, batch, yb, wb, dkey)
        if n == 0:
            self.p_before = jax.device_get(variables["params"])
        if n == self.checked:          # the state as the next step keeps it
            self.p_after = jax.device_get(variables["params"])
            self.losses = [float(l) for l in self.losses]
            if self._stop:
                raise ReadingsDone()
            return self._step(variables, opt_state, batch, yb, wb, dkey)
        self.batches.append({k: np.asarray(v) for k, v in batch.items()})
        self.labels.append(np.asarray(yb))
        if not np.all(np.asarray(wb) == 1.0):
            raise RuntimeError("a checked step was fed padded rows")
        out = self._step(variables, opt_state, batch, yb, wb, dkey)
        self.losses.append(out[2])
        if n == 0:
            # a copy on the host: the next step donates the state
            self.first_mu = jax.device_get(next(
                s.mu for s in jax.tree.leaves(
                    out[1], is_leaf=lambda s: hasattr(s, "mu")) if hasattr(s, "mu")))
        return out


def leaf_gaps(prog_sq: Dict[str, float], ref_sq: Dict[str, float],
              skip=()) -> Dict[str, Any]:
    """Of every tensor, | ||program|| - ||reference|| | over the larger of the
    reference's norm of that tensor and of the median tensor: the worst
    tensor's gap, which tensor that is, and the median tensor's gap."""
    ref = {n: math.sqrt(v) for n, v in ref_sq.items()}
    med = statistics.median(ref.values())
    gaps = {}
    for n, sq in prog_sq.items():
        if n in skip:
            continue
        gap = abs(math.sqrt(sq) - ref[n]) / max(ref[n], med)
        gaps[n] = gap if gap == gap else float("inf")   # a NaN is the worst gap
    at = max(gaps, key=gaps.get) if gaps else None
    return {"gap": gaps[at] if gaps else 0.0, "leaf": at,
            "median_gap": statistics.median(gaps.values()) if gaps else 0.0,
            "ref_norms": ref, "median": med}


def leaf_diffs(prog: Dict[str, np.ndarray], ref: Dict[str, np.ndarray]
               ) -> Dict[str, Any]:
    """Of every tensor, ||program - reference|| over the larger of the
    reference's norm of that tensor and of the median tensor: the worst
    tensor's, which tensor that is, and the median tensor's."""
    norm = lambda a: math.sqrt(float(np.sum(np.square(a, dtype=np.float64))))
    ref_n = {n: norm(a) for n, a in ref.items()}
    med = statistics.median(ref_n.values())
    diffs = {}
    for n, a in prog.items():
        d = norm(np.asarray(a, np.float32) - ref[n]) / max(ref_n[n], med)
        diffs[n] = d if d == d else float("inf")
    at = max(diffs, key=diffs.get)
    return {"diff": diffs[at], "leaf": at,
            "median_diff": statistics.median(diffs.values())}


def run(ctx) -> Dict[str, Any]:
    import jax

    from benchmark import gen

    cfg, traffic = ctx.config, ctx.traffic
    chips = len(jax.devices())
    seq = traffic["seq_len"]
    batch = cfg["fit"]["per_chip_batch"] * chips
    checked = traffic["checked_steps"]
    epochs = traffic["window_epochs"] + 1
    target_rows = cfg["fit"]["sizing_rows_per_s_per_chip"] * chips * ctx.seconds
    steps_per_epoch = max(checked + 1, math.ceil(
        target_rows / batch / traffic["window_epochs"]))
    n_rows = steps_per_epoch * batch
    total_steps = steps_per_epoch * epochs
    random_seed = ctx.seed % (2 ** 31 - 1)
    opt = traffic["optimizer"]

    vocab, docs, labels, weights, ckpt, written = gen.seeded_inputs(ctx, n_rows)
    ctx.say(f"finetune: per-chip batch {batch // chips}, global batch {batch}, "
            f"{steps_per_epoch} steps an epoch, {epochs} epochs (epoch 0 is "
            f"set-up), {n_rows} documents of {seq} positions, checkpoint "
            f"{written} bytes, randomSeed {random_seed}")

    import alink_tpu.dl.train as dl_train
    from alink_tpu.common.metrics import metrics
    from alink_tpu.common.mtable import MTable
    from alink_tpu.common.tracing import tracer
    from alink_tpu.operator.batch.base import TableSourceBatchOp
    from alink_tpu.operator.batch.dl import BertTextClassifierTrainBatchOp

    recorders: List[StepRecorder] = []
    real_make = dl_train.make_train_step

    readings_only = bool(getattr(ctx, "readings_only", False))

    def make_recorded(*a, **kw):
        recorders.append(StepRecorder(real_make(*a, **kw), checked,
                                      stop_when_kept=readings_only))
        return recorders[-1]

    def epoch_spans():
        return sorted((s for s in tracer.spans() if s["name"] == "train.epoch"),
                      key=lambda s: s["start_perf"])

    tracer.clear()
    counters0 = ctx.snapshot()
    at_window = {}

    def watch():        # the window opens when epoch 0 closes
        while not epoch_spans() and not done.is_set():
            time.sleep(0.02)
        at_window["snap"] = ctx.snapshot()
        if ctx.trace and not done.is_set():
            ctx.trace_start()       # the traced span lies inside the window; the
            done.wait(ctx.trace_seconds)    # job is the same program as untraced
            ctx.trace_stop()

    done = threading.Event()
    watcher = threading.Thread(target=watch, name="bench-window", daemon=True)
    src = TableSourceBatchOp(MTable({"text": docs, "label": labels}))
    op = BertTextClassifierTrainBatchOp(
        textCol="text", labelCol="label", maxSeqLength=seq,
        checkpointFilePath=ckpt, batchSize=batch, numEpochs=epochs,
        learningRate=opt["learning_rate"], randomSeed=random_seed)
    dl_train.make_train_step = make_recorded
    watcher.start()
    model_table = None
    try:
        model_table = op.link_from(src).collect()
    except Exception:
        if not (readings_only and recorders and recorders[0].p_after is not None):
            raise       # anything but the stop that readings.py asked for
    finally:
        dl_train.make_train_step = real_make
        done.set()
        watcher.join()
    counters1 = ctx.snapshot()
    spans = epoch_spans()
    del model_table, op, src
    rec = recorders[0]
    rows = (epochs - 1) * n_rows
    if readings_only:       # no window: the checked steps are all that ran
        t_open = t_close = time.perf_counter()
        rate = float("nan")
    else:
        if len(spans) != epochs or len(recorders) != 1:
            raise RuntimeError(f"expected {epochs} train.epoch spans and one compiled "
                               f"step, saw {len(spans)} and {len(recorders)}")
        if rec.calls != total_steps:
            raise RuntimeError(f"the op took {rec.calls} steps, not {total_steps}")
        ends = [s["start_perf"] + s["wall_s"] for s in spans]
        t_open, t_close = ends[0], ends[-1]
        rate = rows / (t_close - t_open) / chips
        epoch_s = [round(s["wall_s"], 4) for s in spans]
        ctx.say(f"finetune: epochs took {epoch_s} s; window {t_close - t_open:.3f} "
                f"s, {rows} rows, {total_steps - steps_per_epoch} steps")

    def check() -> List[tuple]:
        import jax.numpy as jnp

        from benchmark.reference import bert

        enc = bert.encode_batch(docs, vocab, seq)
        index = {enc["input_ids"][i].tobytes(): i for i in range(n_rows)}
        unmatched = wrong_labels = 0
        ref_batches, ref_labels = [], []
        for b, y in zip(rec.batches, rec.labels):
            idx = [index.get(r.tobytes()) for r in b["input_ids"]]
            unmatched += sum(i is None for i in idx) + (
                len({i for i in idx if i is not None}) != len(idx))
            idx = [i or 0 for i in idx]
            for k in enc:
                unmatched += int(not np.array_equal(b[k], enc[k][idx]))
            wrong_labels += int(np.sum(np.asarray(y) != labels[idx]))
            ref_batches.append({k: enc[k][idx] for k in enc})
            ref_labels.append(labels[idx])
        params = {k: jnp.asarray(v) for k, v in weights.items()}
        params.update(bert.head_init(cfg, 2, random_seed))
        follow = lambda **kw: bert.follow_steps(
            params, ref_batches, ref_labels, cfg, opt, total_steps, random_seed,
            block_rows=cfg["fit"]["reference_block_rows"], **kw)
        ref = follow()
        sq_of = lambda t: {n: float(np.sum(np.square(a, dtype=np.float64)))
                           for n, a in t.items()}
        ref_grad_sq = sq_of(ref["first_grad"])
        b1 = opt["b1"]
        after, before = tensors_of(rec.p_after), tensors_of(rec.p_before)

        def program_side():
            return (rec.losses,
                    {n: np.asarray(a, np.float32) / (1 - b1)
                     for n, a in tensors_of(rec.first_mu).items()},
                    {n: float(np.sum((np.asarray(after[n], np.float64)
                                      - before[n]) ** 2)) for n in after})

        def stand_in_side(name):    # the controls: the reference, lowered or
            kw = ({"precision": name} if name in bert.PRECISIONS    # broken, in
                  else {"fault": name})                 # the program's place
            sub = follow(**kw)
            return sub["loss"], sub["first_grad"], sub["delta_sq"]

        sides = [("", program_side)] + [
            (name + ".", partial(stand_in_side, name)) for name in ctx.stand_ins]
        lim = traffic["limits"]
        rows = [("rows_unmatched", unmatched, 0), ("labels_wrong", wrong_labels, 0)]
        for prefix, side in sides:
            losses, first_grad, delta_sq = side()
            grad = leaf_gaps(sq_of(first_grad), ref_grad_sq)
            diff = leaf_diffs(first_grad, ref["first_grad"])
            del first_grad
            still = {n for n, nrm in grad["ref_norms"].items()
                     if nrm < 1e-3 * grad["median"]}
            delta = leaf_gaps(delta_sq, ref["delta_sq"], skip=still)
            ctx.say(f"check: {prefix or 'program'} losses {losses} reference "
                    f"{ref['loss']}; worst first-gradient tensor by gap of norms "
                    f"{grad['leaf']}, by norm of the difference {diff['leaf']}; "
                    f"worst change tensor {delta['leaf']}; {len(still)} tensors "
                    f"left out of the change (reference gradient under 1e-3 of "
                    f"the median tensor's)")
            read = {"loss_gap": max(abs(a - b) for a, b in zip(losses, ref["loss"])),
                    "first_grad_norm_gap": grad["gap"],
                    "first_grad_diff": diff["diff"],
                    "first_grad_diff_median": diff["median_diff"],
                    "change_norm_gap": delta["gap"],
                    "first_grad_median_gap": grad["median_gap"],
                    "change_median_gap": delta["median_gap"]}
            # a number is compared where the traffic file gives it a limit
            # (PERF.md has the readings each was set from); the rest are said
            rows += [(prefix + n, v, lim[n]) for n, v in read.items() if n in lim]
            spare = ", ".join(f"{n} {v:.6g}" for n, v in read.items() if n not in lim)
            if spare:
                ctx.say(f"check: {prefix or 'program'} read, not compared: {spare}")
        return rows

    return {
        "end_to_end": {"train_rows_per_s_per_chip": rate},
        "t_open": t_open, "window_s": t_close - t_open,
        "last_setup_part": "ingest_compile_and_epoch_0",
        "attempted": rows, "failed": 0, "check": check,
        "facts": {"rows": rows, "rows_per_s": rate * chips, "steps": total_steps
                  - steps_per_epoch, "batch": batch, "seq_len": seq, "mode": "train",
                  "counters_setup": ctx.delta(counters0, at_window.get("snap", counters1)),
                  "counters_window": ctx.delta(at_window.get("snap", counters1),
                                               counters1)},
    }
