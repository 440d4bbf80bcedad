"""Traffic kind ``pretrain_lm``: one call of ``CausalLMTrainBatchOp`` over a
table of documents made from the seed; epoch 0 is set-up (it compiles, and
holds the steps that the reference follows), the window is the run of the
remaining epochs of that same call, its ends read from the ``train.epoch``
spans the program records (each epoch ends on a device synchronisation), as
``kinds/finetune.py`` does for the encoder.

The op gives no view of its state between steps, so the kind interposes on the
compiled step the loop builds (``dl.train.make_train_step``): a pass-through
wrapper that, for the job's first steps only, keeps the rows of tokens it was
fed, the loss it returned, Adam's first moment after step 1 (the first
gradient as the optimizer got it), the parameters before step 1 and, as the
step after the last checked one receives them, the parameters and the routers'
biases. The compiled program, its state and its feed are the op's own.

``check()``, after the window and with the program's state released: the
rows fed are rows of the packed table the generator expects; the reference
(``reference/moonlight.py``), a row at a time in float32, follows the same
steps from the same checkpoint and has to give the same losses, the same
first gradient tensor by tensor (the norm of the difference over the
reference's norm: the median tensor's, and the routers' and the held experts'
tensors by themselves), the same change of parameters, and the same biases
(the share of entries that differ: they move by signs, and a token whose sixth
and seventh experts score alike in bfloat16 can tip an expert's count over the
mean). The controls stand in for the program, computed by the reference:
``fp8`` (every product in float8), ``router_grad_dropped`` (the chosen
experts' weights treated as constants) and ``bias_frozen``.
"""

from __future__ import annotations

import gc
import math
import os
import threading
import time
from functools import partial
from typing import Any, Dict, List

import numpy as np

from benchmark.kinds.finetune import ReadingsDone, leaf_diffs, leaf_gaps


class StepRecorder:
    """Pass-through round the loop's compiled train step; see the module
    docstring. ``checked`` is the number of first steps it keeps."""

    def __init__(self, step, checked: int, stop_when_kept: bool = False):
        self._step, self.checked, self._stop = step, checked, stop_when_kept
        self.calls = 0
        self.batches: List[np.ndarray] = []
        self.losses: List[Any] = []
        self.first_mu = self.before = self.after = None
        self.dispatched: List[tuple] = []   # each call's entry and return

    def __call__(self, variables, opt_state, batch, yb, wb, dkey=None):
        import jax

        n = self.calls
        self.calls += 1
        if n > self.checked:
            t_in = time.perf_counter()
            out = self._step(variables, opt_state, batch, yb, wb, dkey)
            self.dispatched.append((t_in, time.perf_counter()))
            return out
        if n == 0:
            self.before = jax.device_get(variables)
        if n == self.checked:          # the state as the next step keeps it
            self.after = jax.device_get(variables)
            self.losses = [float(l) for l in self.losses]
            if self._stop:
                raise ReadingsDone()
            return self._step(variables, opt_state, batch, yb, wb, dkey)
        self.batches.append(np.asarray(batch["tokens"]))
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


def run(ctx) -> Dict[str, Any]:
    import jax

    # the program's pieces first: a program without them fails here, at once,
    # before a checkpoint is drawn and written
    import alink_tpu.dl.train as dl_train
    from alink_tpu.dl import lm as dl_lm
    from alink_tpu.operator.batch.lm import CausalLMTrainBatchOp

    from alink_tpu.common.mtable import MTable
    from alink_tpu.common.tracing import tracer
    from alink_tpu.operator.batch.base import TableSourceBatchOp

    from benchmark import gen_moonlight

    cfg, traffic = ctx.config, ctx.traffic
    chips = len(jax.devices())
    seq = traffic["seq_len"]
    batch = cfg["fit"]["per_chip_rows_a_step"] * chips
    checked = traffic["checked_steps"]
    epochs = traffic["window_epochs"] + 1
    target_rows = cfg["fit"]["sizing_rows_per_s_per_chip"] * chips * ctx.seconds
    steps_per_epoch = max(checked + 1, math.ceil(
        target_rows / batch / traffic["window_epochs"]))
    n_rows = steps_per_epoch * batch
    total_steps = steps_per_epoch * epochs
    random_seed = ctx.seed % (2 ** 31 - 1)
    opt = traffic["optimizer"]

    vocab, docs, packed, ckpt, written = gen_moonlight.seeded_inputs(ctx, n_rows)
    ctx.say(f"pretrain_lm: {batch // chips} rows a step a chip, {batch} in all, "
            f"{steps_per_epoch} steps an epoch, {epochs} epochs (epoch 0 is "
            f"set-up), {n_rows} rows of {seq} tokens from {len(docs)} documents, "
            f"checkpoint {written} bytes, randomSeed {random_seed}")

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
    out_path = os.path.join(ctx.workdir, "trained")
    op = CausalLMTrainBatchOp(
        textCol="text", checkpointFilePath=ckpt, outputPath=out_path,
        maxSeqLength=seq, batchSize=batch, numEpochs=epochs,
        learningRate=opt["learning_rate"], randomSeed=random_seed)
    dl_train.make_train_step = make_recorded
    watcher.start()
    result = None
    try:
        result = op.link_from(TableSourceBatchOp(MTable({"text": docs}))).collect()
    except Exception:
        if not (readings_only and recorders and recorders[0].after is not None):
            raise       # anything but the stop that a reading of limits asked for
    finally:
        dl_train.make_train_step = real_make
        done.set()
        watcher.join()
    counters1 = ctx.snapshot()
    spans = epoch_spans()
    exported = sorted(os.listdir(out_path)) if os.path.isdir(out_path) else []
    del result, op
    gc.collect()            # the model and its optimizer leave the device here
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
        ctx.say(f"pretrain_lm: epochs took {epoch_s} s; window {t_close - t_open:.3f} "
                f"s, {rows} rows, {total_steps - steps_per_epoch} steps; the op "
                f"wrote {len(exported)} files")
        usual = sorted(epoch_s[1:])[len(epoch_s[1:]) // 2]
        for e, span in enumerate(spans[1:], start=1):
            if span["wall_s"] > 1.02 * usual:   # where a slow epoch's time went
                t0 = span["start_perf"]
                mine = [(a - t0, b - t0) for a, b in rec.dispatched
                        if t0 <= a <= t0 + span["wall_s"]]
                ctx.say(f"pretrain_lm: epoch {e} took {span['wall_s']:.3f} s for the "
                        f"usual {usual:.3f}, {t0 - ends[e - 1]:.3f} s after epoch "
                        f"{e - 1} closed; its steps were handed to the device at "
                        + ", ".join(f"+{a:.3f} (back +{b:.3f})" for a, b in mine))

    opened = at_window.get("snap", counters1)

    def check() -> List[tuple]:
        from benchmark.reference import moonlight
        from benchmark.reference.brumby import Checkpoint

        if ctx.trace_reduced:       # the device time by operation, loops apart
            ops = [(n, s, c) for n, s, c, _ in ctx.trace_reduced.get("ops_all", [])
                   if not n.startswith(("while", "conditional", "call"))]
            for n, s, c in ops[:48]:
                ctx.say(f"trace: op {s:.4f} s x{c} {n}")
        index = {packed[i].tobytes(): i for i in range(n_rows)}
        seen = [index.get(r.tobytes()) for b in rec.batches for r in b]
        unmatched = sum(i is None for i in seen) + (
            len({i for i in seen if i is not None}) != len(seen))
        if not readings_only:       # the checkpoint the generator would serve
            unmatched += int(not {"config.json", "vocab.txt"} <= set(exported))
        lm_cfg = dl_lm.CausalLMConfig.from_hf(gen_moonlight.hf_config(cfg))
        file = Checkpoint(ckpt)
        # as they lie in the file (bfloat16): the reference widens its own copy
        w = {n: np.asarray(file.host(n)) for n in file._where}
        names = sorted((n for n in w if n.endswith("e_score_correction_bias")),
                       key=lambda n: int(n.split(".")[2]))
        bias = np.stack([w.pop(n) for n in names]).astype(np.float32)
        b1 = opt["b1"]
        sq_of = lambda t: {n: float(np.sum(np.square(a, dtype=np.float64)))
                           for n, a in t.items()}

        def by_name(params) -> Dict[str, np.ndarray]:
            state = {"params": params, "router": {"expert_bias": bias}}
            return {n: np.asarray(a) for shard in dl_lm.hf_tensors(lm_cfg, state)
                    for n, a in shard if n in w}

        # the program's side first, and the recorder's copies of the state
        # let go before the reference makes its own (2.7 GB each)
        after, before = by_name(rec.after["params"]), by_name(rec.before["params"])
        program = (rec.losses,
                   {n: np.asarray(a, np.float32) / (1 - b1)
                    for n, a in by_name(rec.first_mu).items()},
                   {n: float(np.sum((np.asarray(after[n], np.float64)
                                     - before[n]) ** 2)) for n in after},
                   np.asarray(rec.after["router"]["expert_bias"]))
        del after, before
        rec.before = rec.after = rec.first_mu = None
        follow = lambda **kw: moonlight.follow_steps(
            w, bias, rec.batches, cfg, opt, total_steps,
            block=cfg["fit"]["reference_block"], **kw)
        ref = follow()
        ref_grad_sq = sq_of(ref["first_grad"])

        kinds = {"router": lambda n: n.endswith("mlp.gate.weight"),
                 "experts": lambda n: ".mlp.experts." in n}

        def grad_reads(first_grad) -> tuple:
            """What the comparison reads of a side's first gradient."""
            diff = leaf_diffs(first_grad, ref["first_grad"])
            single = {k: leaf_diffs({n: a for n, a in first_grad.items() if pick(n)},
                                    {n: a for n, a in ref["first_grad"].items()
                                     if pick(n)}) for k, pick in kinds.items()}
            return diff, single, leaf_gaps(sq_of(first_grad), ref_grad_sq)

        def program_side():
            losses, first_grad, delta_sq, bias_after = program
            return losses, grad_reads(first_grad), delta_sq, bias_after

        def stand_in_side(name):    # the controls: the reference, lowered or
            kw = ({"precision": name} if name in moonlight.PRECISIONS  # broken, in
                  else {"fault": name})                 # the program's place
            jax.clear_caches()      # the programs of the side before, let go
            reads = []              # read as it arrives, and not kept
            sub = follow(on_first_grad=lambda g: reads.append(grad_reads(g)), **kw)
            return sub["loss"], reads[0], sub["delta_sq"], sub["bias"]

        sides = [("", program_side)] + [
            (name + ".", partial(stand_in_side, name)) for name in ctx.stand_ins]
        lim = traffic["limits"]
        out = [("rows_unmatched", unmatched, 0)]
        for prefix, side in sides:
            gc.collect()
            losses, (diff, single, grad), delta_sq, bias_after = side()
            program = None      # its 2.7 GB are not held beside a stand-in's
            still = {n for n, nrm in grad["ref_norms"].items()
                     if nrm < 1e-3 * grad["median"]}
            delta = leaf_gaps(delta_sq, ref["delta_sq"], skip=still)
            ctx.say(f"check: {prefix or 'program'} losses {losses} reference "
                    f"{ref['loss']} (cross-entropy {ref['ce']}); worst "
                    f"first-gradient tensor by norm of the difference "
                    f"{diff['leaf']} ({diff['diff']:.4g}); worst change tensor "
                    f"{delta['leaf']}; {len(still)} tensors left out of the "
                    f"change (reference gradient under 1e-3 of the median's)")
            read = {"loss_gap": max(abs(a - b) for a, b in zip(losses, ref["loss"])),
                    "first_grad_diff_median": diff["median_diff"],
                    "first_grad_diff_router": single["router"]["diff"],
                    "first_grad_diff_experts": single["experts"]["median_diff"],
                    "first_grad_diff_worst": diff["diff"],
                    "change_norm_gap": delta["gap"],
                    "change_median_gap": delta["median_gap"],
                    "bias_diff_share": float(np.mean(bias_after != ref["bias"]))}
            # a number is compared where the traffic file gives it a limit
            # (PERF.md has the readings each was set from); the rest are said
            out += [(prefix + n, v, lim[n]) for n, v in read.items() if n in lim]
            ctx.say(f"check: {prefix or 'program'} read: " + ", ".join(
                f"{n} {v:.6g}" for n, v in read.items()))
        return out

    return {
        "end_to_end": {"train_rows_per_s_per_chip": rate},
        "t_open": t_open, "window_s": t_close - t_open,
        "last_setup_part": "ingest_compile_and_epoch_0",
        "attempted": rows, "failed": 0, "check": check,
        "facts": {"rows": rows, "rows_per_s": rate * chips,
                  "steps": total_steps - steps_per_epoch, "batch": batch,
                  "seq_len": seq, "mode": "train",
                  "counters_setup": ctx.delta(counters0, opened),
                  "counters_window": ctx.delta(opened, counters1)},
    }
