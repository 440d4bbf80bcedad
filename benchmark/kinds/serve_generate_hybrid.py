"""Traffic kind ``serve_generate_hybrid``: ``serve_generate``'s closed loop for
a causal language model whose stack mixes layer kinds (KDA, latent attention)
and has an expert layer of which this chip holds a share. The loop is the
same: one ``ModelServer``, a fixed number of one-prompt requests outstanding,
each slot submitting its next prompt the moment its last one is answered, one
batch a cycle, every row prefilled and then decoded together for the traffic's
``max_new_tokens`` steps; ``warm_cycles`` cycles, then a window that closes at
the first cycle boundary at or after ``--seconds``.

The served stage is ``CausalLMGenerator`` with the seeded checkpoint's path
(``gen_ling``: one chip's share of the model in the HF layout), the cache
manager sized by the configuration's ``fit`` (``state_slots`` sequences,
``cache_positions`` positions of latent cache a slot).

``check()``: ``checked_rows`` of the window's answers, drawn from the seed.
For each, the reference (``reference/ling.py``) runs its full forward pass
over the prompt and the ids the program emitted, teacher-forced, and the
comparison is of logits, never of tokens: prefill then steps through both
kinds of cache against one forward with none. Beside it, the routing: a row's
answer carries, per expert layer, how many of its tokens' assignments each
expert held here served (``expert_load``), the reference counts the same from
its own float32 choices, and ``routing_diff_share`` is the summed difference
over the reference's count. The controls stand in for the program, computed
by the reference: ``fp8`` (every product in float8), ``swapped`` (a row
decoded after its neighbour's prompt), ``chunk_state_dropped`` (KDA state,
convolution tails and latent cache empty at the start of every prompt chunk of
the program's size, and the steps seeing the last chunk alone) and
``route_ungrouped`` (the best 8 of all 512 experts, no group limit).
"""

from __future__ import annotations

import gc
import json
import os
import statistics
import time
from typing import Any, Dict, List

import numpy as np

from benchmark.kinds.serve_closed import _Failed, _p


def run(ctx) -> Dict[str, Any]:
    import jax

    # the program's pieces first: a program without them fails here, at
    # once, before a checkpoint of ten gigabytes is drawn and written
    from alink_tpu.dl import kda, mla, moe  # noqa: F401
    from alink_tpu.dl.lm import HYBRID_PREFILL_CHUNK

    from benchmark import gen_ling

    from alink_tpu.common.metrics import metrics
    from alink_tpu.pipeline import CausalLMGenerator, PipelineModel
    from alink_tpu.serving import ModelServer, ServingConfig

    cfg, traffic = ctx.config, ctx.traffic
    # the operator's row ladder (the program's knob, read at every look-up):
    # one rung where every batch is the cap's size, so one set of programs
    os.environ["ALINK_SHAPE_BUCKETS"] = traffic["shape_buckets"]
    n_out, new = traffic["outstanding"], traffic["max_new_tokens"]
    scfg = traffic["server"]
    vocab, prompts, lengths, ckpt, written = gen_ling.seeded_inputs(
        ctx, traffic["prompts"])
    counters0 = ctx.snapshot()
    stage = CausalLMGenerator(
        modelPath=ckpt, selectedCol="prompt", predictionCol="text",
        predictionDetailCol="detail", maxNewTokens=new,
        stateSlots=cfg["fit"]["state_slots"],
        cachePositions=cfg["fit"]["cache_positions"])
    server = ModelServer(ServingConfig(**scfg))
    timeout = float(scfg["default_timeout_s"])
    done: List[tuple] = []          # (prompt, t_submit, t_result, row or None)

    def memory_line(at: str) -> None:
        m = jax.devices()[0].memory_stats() or {}
        ctx.say(f"serve_generate_hybrid: memory {at}: live {m.get('bytes_in_use')}"
                f", peak live {m.get('peak_bytes_in_use')}, program scratch "
                f"{m.get('bytes_reserved')}, peak {m.get('peak_bytes_reserved')}")

    memory_line("after the checkpoint is written")
    try:
        # the warm-up rows are one prompt of each length, the longest first,
        # so that every rung's programs see every chunk count
        by_len = {int(n): i for i, n in reversed(list(enumerate(lengths)))}
        warm = [(prompts[by_len[n]],) for n in sorted(by_len, reverse=True)]
        info = server.load("model", PipelineModel(stage), "prompt string",
                           warmup_rows=warm)
        ctx.mark("server_load_and_ladder_warmup")
        slots = metrics.gauge("lm.state_slots")
        ctx.say(f"serve_generate_hybrid: {n_out} requests outstanding, batch cap "
                f"{info['max_batch_rows']}, {len(prompts)} prompts of "
                f"{sorted(set(lengths.tolist()))} tokens (mean "
                f"{lengths.mean():.1f}), {new} new tokens a row, checkpoint "
                f"{written} bytes, cache manager {slots} slots: KDA state "
                f"{metrics.gauge('lm.kda_state_bytes')} bytes, latent cache "
                f"{metrics.gauge('lm.latent_cache_bytes')} bytes of "
                f"{metrics.gauge('lm.latent_cache_positions')} positions a "
                f"slot; server warm-up "
                f"{info['warmup']}")
        memory_line("after load and warm-up")
        next_prompt = 0

        def submit():
            nonlocal next_prompt
            d, next_prompt = next_prompt % len(prompts), next_prompt + 1
            try:
                fut = server.submit("model", (prompts[d],))
            except Exception as e:      # shed at the door: a failed request
                fut = _Failed(e)
            return fut, time.perf_counter(), d

        def cycle(resubmit: bool) -> float:
            for i in range(n_out):
                fut, t0, d = slots_out[i]
                try:
                    row = fut.result(timeout)
                except Exception as e:
                    row = None
                    ctx.say(f"serve_generate_hybrid: a request failed: {e!r}"[:300])
                done.append((d, t0, time.perf_counter(), row))
                if resubmit:
                    slots_out[i] = submit()
            return time.perf_counter()

        slots_out = [submit() for _ in range(n_out)]
        for _ in range(traffic["warm_cycles"]):
            cycle(True)
        if ctx.trace:       # the profiler starts inside a cycle that is not
            ctx.trace_start(mark=False)     # counted; the window opens on the
            cycle(True)                     # next boundary
            ctx.trace_mark_open()
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
        memory_line("after the window")
        stats = server.stats()["models"][0]
    finally:
        server.close()
    del server, stage
    gc.collect()            # the model and its state leave the device here
    window = done[n_warm:n_warm + n_out * (len(ends) - 1)]
    lat_ms = [1e3 * (t1 - t0) if row is not None else 1e3 * timeout
              for _, t0, t1, row in window]
    failed = sum(row is None for *_, row in window)
    wall = t_close - t_open
    cycles = np.diff(ends)
    prompt_tokens = int(sum(lengths[d] for d, *_ in window))
    ctx.say(f"serve_generate_hybrid: window {wall:.3f} s held {len(cycles)} cycles of "
            f"{n_out} requests, {prompt_tokens} prompt tokens and "
            f"{len(window) * new} new ones; cycle length min {cycles.min():.4f} "
            f"median {statistics.median(cycles):.4f} max {cycles.max():.4f} s "
            f"(each {[round(float(c), 3) for c in cycles]}); latency p50 "
            f"{_p(lat_ms, 0.5):.1f} p95 {_p(lat_ms, 0.95):.1f} max "
            f"{max(lat_ms):.1f} ms; the server ran {stats['batches']} batches "
            f"in all, fill {stats['batch_fill']}; client lateness: none "
            f"(closed loop, the client blocks on the next slot's future)")

    def check() -> List[tuple]:
        import jax.numpy as jnp

        from benchmark.reference import ling

        rng = np.random.default_rng([int(ctx.seed), 11])
        bad = short = 0
        parsed: Dict[int, dict] = {}
        for i, (d, _, _, row) in enumerate(window):
            if row is None:
                continue
            try:
                detail = json.loads(row[-1])
                ids = detail["ids"]
                ok = (len(row) == 3 and row[0] == prompts[d]
                      and isinstance(row[1], str)
                      and detail["prompt_tokens"] == int(lengths[d])
                      and len(detail["logprobs"]) == len(ids)
                      and all(0 <= t < cfg["vocab_size"] for t in ids))
            except Exception:
                ok, ids = False, []
            bad += int(not ok)
            short += int(ok and len(ids) < new)
            if ok and len(ids) == new:
                parsed[i] = detail
        answered = sorted(parsed)
        k = min(traffic["checked_rows"], len(answered))
        pick = sorted(rng.choice(len(answered), size=k, replace=False).tolist()) \
            if answered else []
        sample = [answered[i] for i in pick]
        tok = {t: i for i, t in enumerate(vocab)}
        rows, emitted, served = [], [], []
        for i in sample:
            d = window[i][0]
            prompt = np.asarray(ling.encode_prompt(prompts[d], tok), np.int64)
            ids = np.asarray(parsed[i]["ids"], np.int64)
            rows.append((prompt, ids))
            emitted.append(ids)
            served.append(np.asarray(parsed[i]["logprobs"], np.float64))

        def forced(prompt_of, precision="f32", chunk=None, grouped=True):
            """Logits at the positions that chose each emitted id, from the
            full forward over a prompt and the row's own emitted ids; the
            emitted ids keep the positions they had after the row's own
            prompt, whatever prompt stands before them. With ``chunk``, a
            position sees back to the start of its prompt chunk only, and an
            emitted id to the start of the prompt's last chunk."""
            batch, visible = [], None if chunk is None else []
            for r, (own, ids) in enumerate(rows):
                prompt = prompt_of(r)
                seq = np.concatenate([prompt, ids[:-1]])
                pos = np.concatenate([np.arange(len(prompt)),
                                      len(own) + np.arange(new - 1)])
                batch.append((seq, pos, len(prompt) - 1 + np.arange(new)))
                if chunk is not None:
                    t = np.minimum(np.arange(len(seq)), len(prompt) - 1)
                    visible.append((t // chunk * chunk).astype(np.int32))
            return ling.logits_at(ckpt_file, cfg, batch, precision, visible,
                                  grouped)

        if len(rows) < 2:
            gaps = [("logprob_gap_rel", float("nan")),
                    ("argmax_margin_rel", float("nan")),
                    ("routing_diff_share", float("nan"))]
            unit = float("nan")
        else:
            ckpt_file = ling.Checkpoint(ckpt)
            ref_logits, ref_chosen = forced(lambda r: rows[r][0])
            at = np.arange(new)
            logp = lambda lg: [np.asarray(jax.nn.log_softmax(jnp.asarray(l), -1))
                               for l in lg]
            ref_lp = np.stack([lp[at, e] for lp, e in zip(logp(ref_logits), emitted)])
            # the unit: the median gap between neighbouring rows' reference
            # log-probabilities (PR 25's unit for prob_gap_rel)
            between = np.abs(np.roll(ref_lp, 1, axis=0) - ref_lp).max(axis=1)
            unit = float(np.median(between))
            gap = lambda lp: float(np.max(np.abs(lp - ref_lp))) / unit
            # how far under the reference's largest logit the chosen token's lies
            margin = lambda chosen: float(max(
                (l.max(-1) - l[at, c]).max() for l, c in zip(ref_logits, chosen))
            ) / unit
            gaps = [("logprob_gap_rel", gap(np.stack(served))),
                    ("argmax_margin_rel", margin(emitted))]
            each = np.abs(np.stack(served) - ref_lp)
            ctx.say(f"check: a served log-probability's gap, by row: widest "
                    f"{np.round(each.max(1), 4).tolist()} at positions "
                    f"{each.argmax(1).tolist()}; at the prefill's token "
                    f"{np.round(each[:, 0], 4).tolist()}; over all positions "
                    f"median {np.median(each):.5f}, ninth decile "
                    f"{np.quantile(each, 0.9):.5f}")
            # routing: the held experts' counts a layer the row's answer
            # carries against the reference's own choices over the same tokens
            lo, hi = cfg["deployment"]["experts_held"]
            held = lambda chosen: np.stack([
                np.bincount(layer[(layer >= lo) & (layer < hi)] - lo,
                            minlength=hi - lo) for layer in chosen])
            ref_load = np.stack([held(c) for c in ref_chosen])
            load = np.stack([np.asarray(parsed[i].get("expert_load", ref_load[0] * 0))
                             for i in sample])
            diff = lambda got: float(np.abs(got - ref_load).sum()) / ref_load.sum()
            gaps.append(("routing_diff_share", diff(load)))
            ctx.say(f"check: routing: of {int(ref_load.sum())} assignments the "
                    f"reference gives the experts {lo}-{hi - 1} over the compared "
                    f"rows' tokens in {ref_load.shape[1]} expert layers, the "
                    f"program's counts differ by {int(np.abs(load - ref_load).sum())} "
                    f"(by row {np.abs(load - ref_load).sum((1, 2)).tolist()}); "
                    f"held share of all assignments {ref_load.sum() / max(1, sum(c.size for c in ref_chosen)):.4f}")
            for name in ctx.stand_ins:      # the controls, in the program's place
                if name in ling.PRECISIONS:
                    got, chose = forced(lambda r: rows[r][0], name)
                elif name == "swapped":     # each row's state is its neighbour's
                    got, chose = forced(lambda r: rows[(r + 1) % len(rows)][0])
                elif name == "chunk_state_dropped":
                    got, chose = forced(lambda r: rows[r][0],
                                        chunk=HYBRID_PREFILL_CHUNK)
                elif name == "route_ungrouped":
                    got, chose = forced(lambda r: rows[r][0], grouped=False)
                else:
                    raise ValueError(f"no stand-in {name!r} in this kind")
                if name != "swapped":   # another prompt's tokens: no like counts
                    gaps.append((name + ".routing_diff_share",
                                 diff(np.stack([held(c) for c in chose]))))
                got_lp = np.stack([lp[at, e] for lp, e in zip(logp(got), emitted)])
                ctx.say(f"check: stand-in {name}: widest gap by row "
                        f"{np.round(np.abs(got_lp - ref_lp).max(1), 4).tolist()}")
                gaps += [(name + ".logprob_gap_rel", gap(got_lp)),
                         (name + ".argmax_margin_rel",
                          margin([l.argmax(-1) for l in got]))]
            ctx.say(f"check: {len(rows)} of {len(window)} requests of the "
                    f"window compared over {new} positions each; gap between "
                    f"neighbouring rows' reference log-probabilities: median "
                    f"{unit:.6f}, largest {float(between.max()):.6f}; widest "
                    f"gap of a served log-probability {gaps[0][1] * unit:.6f}; "
                    f"the reference's log-probability of an emitted token: "
                    f"mean {ref_lp.mean():.4f}")
        lim = traffic["limits"]
        return [("requests_unanswered", failed, 0), ("rows_malformed", bad, 0),
                ("tokens_short", short, 0),
                ("compared_rows_short", max(0, min(traffic["checked_rows"],
                                                   len(window)) - len(rows)), 0),
                ] + [(n, g, lim[n.rsplit(".", 1)[-1]]) for n, g in gaps]

    rate = len(window) / wall
    return {
        "end_to_end": {"infer_rows_per_s": rate},
        "t_open": t_open, "window_s": wall, "last_setup_part": "warm_cycles",
        "attempted": len(window), "failed": failed, "check": check,
        "facts": {"rows": len(window), "rows_per_s": rate, "batch": n_out,
                  "mode": "serve", "trace_rows": len(window),
                  "prompt_tokens": prompt_tokens, "new_tokens": new,
                  "state_slots": slots,
                  "latent_positions": metrics.gauge("lm.latent_cache_positions"),
                  "latency_p95_s": _p(lat_ms, 0.95) / 1e3,
                  "counters_setup": ctx.delta(counters0, at_open),
                  "counters_window": ctx.delta(at_open, at_close)},
    }
