"""Traffic kind ``serve_closed``: the fitted model in one ``ModelServer``,
driven in a closed loop with a fixed number of single-document requests
outstanding. Each slot submits its next document the moment its last one is
answered; the client walks the slots in submission order, which is the order
the batcher answers them in, so it blocks on a future and never polls.

A cycle is one pass over all slots. After ``warm_cycles`` the window opens; it
closes at the first cycle boundary at or after ``--seconds``, so that it holds
whole cycles: the rate is every row answered in the window over the window's
whole wall, and the tail is over every request completed in it, each timed by
this file's clock from ``submit`` to its result.

The model table comes from the train op (the path a user takes): one step over
one batch, which at the warm-up schedule's step 0 has learning rate 0, so the
served weights are the seeded checkpoint's and the fresh head's, and the
reference rebuilds both from the seed.
"""

from __future__ import annotations

import json
import statistics
import time
from typing import Any, Dict, List

import numpy as np


class _Failed:
    """Stands for the future of a request that ``submit`` refused."""

    def __init__(self, error: BaseException):
        self._error = error

    def result(self, timeout=None):
        raise self._error


def _p(values: List[float], q: float) -> float:
    """Nearest-rank percentile of every value (no interpolation)."""
    ordered = sorted(values)
    return ordered[min(len(ordered) - 1, max(0, int(np.ceil(q * len(ordered))) - 1))]


def run(ctx) -> Dict[str, Any]:
    import jax

    from benchmark import gen

    cfg, traffic = ctx.config, ctx.traffic
    chips = len(jax.devices())
    seq, n_out = traffic["seq_len"], traffic["outstanding"]
    random_seed = ctx.seed % (2 ** 31 - 1)
    fit, scfg = traffic["fit"], traffic["server"]

    vocab, docs, labels, weights, ckpt, written = gen.seeded_inputs(
        ctx, traffic["documents"])

    from alink_tpu.common.mtable import MTable
    from alink_tpu.operator.batch.base import TableSourceBatchOp
    from alink_tpu.operator.batch.dl import BertTextClassifierTrainBatchOp
    from alink_tpu.pipeline import BertTextClassifierModel, PipelineModel
    from alink_tpu.serving import ModelServer, ServingConfig

    counters0 = ctx.snapshot()
    nb = fit["batch_size"]
    src = TableSourceBatchOp(MTable({"text": docs[:nb], "label": labels[:nb]}))
    model_table = BertTextClassifierTrainBatchOp(
        textCol="text", labelCol="label", maxSeqLength=seq,
        checkpointFilePath=ckpt, batchSize=nb, numEpochs=1,
        learningRate=fit["learning_rate"], randomSeed=random_seed
    ).link_from(src).collect()
    ctx.mark("ingest_and_one_step_fit")
    stage = BertTextClassifierModel(predictionCol="pred", predictionDetailCol="detail")
    stage.set_model_data(model_table)
    undo = ctx.annotate(traffic.get("annotate", {}))
    server = ModelServer(ServingConfig(**scfg))
    timeout = float(scfg["default_timeout_s"])
    done: List[tuple] = []          # (doc, t_submit, t_result, row or None)
    try:
        info = server.load("model", PipelineModel(stage), "text string",
                           warmup_rows=[(docs[0],), (docs[1],)])
        ctx.mark("server_load_and_ladder_warmup")
        ctx.say(f"serve_closed: {n_out} requests outstanding, batch cap "
                f"{info['max_batch_rows']}, {len(docs)} documents of {seq} "
                f"positions, checkpoint {written} bytes, randomSeed "
                f"{random_seed}; server warm-up {info['warmup']}")
        next_doc = 0

        def submit():
            nonlocal next_doc
            d, next_doc = next_doc % len(docs), next_doc + 1
            try:
                fut = server.submit("model", (docs[d],))
            except Exception as e:      # shed at the door: a failed request
                fut = _Failed(e)
            return fut, time.perf_counter(), d

        def cycle(resubmit: bool) -> float:
            for i in range(n_out):
                fut, t0, d = slots[i]
                try:
                    row = fut.result(timeout)
                except Exception as e:
                    row = None
                    ctx.say(f"serve_closed: a request failed: {e!r}"[:300])
                done.append((d, t0, time.perf_counter(), row))
                if resubmit:
                    slots[i] = submit()
            return time.perf_counter()

        slots = [submit() for _ in range(n_out)]
        for _ in range(traffic["warm_cycles"]):
            cycle(True)
        if ctx.trace:       # the profiler starts inside a cycle that is not
            ctx.trace_start(mark=False)     # counted; the window opens on the
            cycle(True)                     # next boundary
            ctx.trace_mark_open()
        for spans in ctx.host_spans.values():
            del spans[:]
        at_open = ctx.snapshot()
        n_warm = len(done)
        t_open = time.perf_counter()
        ends = [t_open]
        while ends[-1] - t_open < (ctx.trace_seconds if ctx.trace else ctx.seconds):
            ends.append(cycle(True))
        t_close = ends[-1]
        at_close = ctx.snapshot()
        if ctx.trace:
            ctx.trace_stop()
        cycle(False)        # the requests still out are answered, not counted
        stats = server.stats()["models"][0]
    finally:
        server.close()
        ctx.restore(undo)
    del server, stage, model_table, src
    window = done[n_warm:n_warm + n_out * (len(ends) - 1)]
    lat_ms = [1e3 * (t1 - t0) if row is not None else 1e3 * timeout
              for _, t0, t1, row in window]
    failed = sum(row is None for *_, row in window)
    wall = t_close - t_open
    cycles = np.diff(ends)
    ctx.say(f"serve_closed: window {wall:.3f} s held {len(cycles)} cycles of "
            f"{n_out} requests; cycle length min {cycles.min():.4f} median "
            f"{statistics.median(cycles):.4f} max {cycles.max():.4f} s (spread "
            f"of lengths {100 * (cycles.max() - cycles.min()) / statistics.median(cycles):.2f}% "
            f"of the median; each {[round(float(c), 3) for c in cycles]}); latency p50 {_p(lat_ms, 0.5):.1f} p95 "
            f"{_p(lat_ms, 0.95):.1f} max {max(lat_ms):.1f} ms; the server ran "
            f"{stats['batches']} batches in all, fill {stats['batch_fill']}; "
            f"client lateness: none (closed loop, the client blocks on the "
            f"next slot's future)")

    def check() -> List[tuple]:
        import jax.numpy as jnp

        from benchmark.reference import bert

        rng = np.random.default_rng([int(ctx.seed), 11])
        answered = [i for i, w in enumerate(window) if w[3] is not None]
        pick = sorted(rng.choice(len(answered), size=min(traffic["checked_rows"],
                                                         len(answered)),
                                 replace=False).tolist()) if answered else []
        sample = [window[answered[i]] for i in pick]
        names = ("pred", "detail")
        served, bad = [], 0
        for w in window:
            row = w[3]
            if row is None:
                continue
            detail = json.loads(row[-1])
            best = max(detail, key=detail.get)
            bad += int(str(row[-2]) != str(best) or len(row) != 1 + len(names)
                       or row[0] != docs[w[0]])
        for d, _, _, row in sample:
            detail = json.loads(row[-1])
            served.append([detail[k] for k in sorted(detail, key=int)])
        served = np.asarray(served, np.float64).reshape(len(sample), -1)
        params = {k: jnp.asarray(v) for k, v in weights.items()}
        params.update(bert.head_init(cfg, 2, random_seed))
        enc = bert.encode_batch([docs[d] for d, *_ in sample], vocab, seq)
        blk = cfg["fit"]["reference_block_rows"]
        ref = bert.predict_proba(params, enc, cfg, block_rows=blk)
        # the widest gap of a compared answer from the reference's, in units of
        # the median gap between neighbouring documents' reference answers: how
        # far answers differ at all swings eightfold with the seed's weights,
        # and the program's and the control's gaps swing with it
        between = np.abs(np.roll(ref, 1, axis=0) - ref).max(axis=1) \
            if len(sample) > 1 else np.asarray([float("nan")])
        unit = float(np.median(between))
        gap_of = lambda got: float(np.max(np.abs(got - ref))) / unit \
            if len(sample) > 1 else float("nan")
        gaps = [("prob_gap_rel", gap_of(served))]
        for name in ctx.stand_ins:      # the controls, in the program's place
            if name in bert.PRECISIONS:
                got = bert.predict_proba(params, enc, cfg, block_rows=blk,
                                         precision=name)
            elif name == "swapped":     # each answer given to its neighbour
                got = np.roll(ref, 1, axis=0)
            else:
                raise ValueError(f"no stand-in {name!r} in this kind")
            gaps.append((name + ".prob_gap_rel", gap_of(got)))
        ctx.say(f"check: {len(sample)} of {len(window)} requests of the window "
                f"compared; gap between neighbouring documents' reference "
                f"probabilities: median {unit:.6f}, largest "
                f"{float(between.max()):.6f}; widest gap of a served probability "
                f"{gaps[0][1] * unit:.6f}")
        lim = traffic["limits"]
        return [("requests_unanswered", failed, 0), ("rows_malformed", bad, 0),
                ("compared_rows_short", max(0, min(traffic["checked_rows"],
                                                   len(window)) - len(sample)), 0),
                ] + [(n, g, lim["prob_gap_rel"]) for n, g in gaps]

    rate = len(window) / wall
    return {
        # the tail is a per-layer metric for now (facts["latency_p95_s"])
        "end_to_end": {"infer_rows_per_s": rate},
        "t_open": t_open, "window_s": wall, "last_setup_part": "warm_cycles",
        "attempted": len(window), "failed": failed, "check": check,
        "facts": {"rows": len(window), "rows_per_s": rate, "batch": n_out * chips,
                  "seq_len": seq, "mode": "serve", "trace_rows": len(window),
                  "latency_p95_s": _p(lat_ms, 0.95) / 1e3,
                  "counters_setup": ctx.delta(counters0, at_open),
                  "counters_window": ctx.delta(at_open, at_close)},
    }
