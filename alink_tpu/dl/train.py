"""Generic DL train loop — the akdl `train_estimator` analog.

Capability parity (reference: core/src/main/python/akdl/akdl/engine/train.py:16-40
TrainSpec/EvalSpec + chief SavedModel export at :34-39; early stopping
akdl/engine/early_stopping.py; dataset from mmap-queue TFRecords engine/inputs.py
— the flink-ai-extended data plane that keeps the trainer fed without host
stalls).

TPU re-design: one ProgramCache-resident train step (loss + grad + optax
update) with donated optimizer/param buffers, batches sharded over the mesh's
data axis (and seq axis for ring attention), eval on a held-out slice,
optional best-metric early stopping. No processes, no queues, no TFRecord hop.

Steady-state execution contract (the BERT hot path):

- **One compiled program per (model config, optimizer config, loss) job
  family** — :func:`make_train_step` registers the step with
  :mod:`alink_tpu.common.jitcache` instead of rebuilding ``jax.jit`` per
  call, so N fine-tune jobs share one executable and jax's dispatch cache
  survives across jobs. Buffer donation is preserved through the cache:
  params/opt_state update in place on device.
- **Shape-bucketed batches** — every step of a job runs the same padded
  batch shape (ragged tails pad by repeating the last real row with
  zero loss-weight, which is exact: padded rows contribute ``l*0`` to the
  weighted loss and zero gradient), so the steady loop performs ZERO new
  traces after the first step (pinned via ``jit.trace`` counter deltas).
- **Async device feed** — batch assembly (row gather, padding) and the
  host->device transfer run on the shared ``alink-h2d`` transfer pool via
  :func:`alink_tpu.common.streaming.stream_map`, double-buffered ahead of
  compute (``ALINK_STREAM_DEPTH``), so the jitted step never waits on the
  host. ``TrainConfig.feed="sync"`` keeps the single-threaded reference
  path; both feeds assemble identical batches, so results are
  bit-identical (CI-pinned).
"""

from __future__ import annotations

import contextlib
import dataclasses
import queue
import threading
import time
from collections import deque
from dataclasses import dataclass
from functools import partial
from typing import (Any, Callable, Dict, Iterable, Iterator, List, NamedTuple,
                    Optional, Sequence, Tuple, Union)

import numpy as np

from ..common.tracing import (slow_against, step_annotation, trace_span,
                              tracing_enabled)
from .sharding import batch_sharding, param_shardings


@dataclass
class TrainConfig:
    num_epochs: int = 3
    batch_size: int = 32
    learning_rate: float = 1e-3
    weight_decay: float = 0.0
    warmup_ratio: float = 0.1
    optimizer: str = "adamw"  # adamw | adam | sgd
    early_stopping_patience: int = 0  # 0 = off
    eval_ratio: float = 0.0  # fraction of rows held out for eval
    seed: int = 0
    # auto | softmax | mse | gaussian_nll | rows (the model's output is each
    # row's loss already: a language model sums its own over positions)
    loss: str = "auto"
    log_every: int = 0
    # mid-training checkpoint/resume (dl/checkpoint.py); None disables
    checkpoint_dir: "str | None" = None
    checkpoint_every: int = 0  # extra mid-epoch saves every N steps; 0 = only per epoch
    resume: bool = True
    # input pipeline: "async" assembles + ships batches on the transfer pool
    # (double-buffered, the device never waits on the host); "sync" is the
    # single-threaded reference feed. Bit-identical either way.
    feed: str = "async"
    feed_depth: int = 0  # in-flight batches ahead of compute; 0 = ALINK_STREAM_DEPTH
    # gradient accumulation: the optimizer step's gradient is the ORDERED
    # fp32 sum of accum_steps micro-chunk gradients over the effective
    # batch (batch_size rows; batch_size % accum_steps must be 0).
    # accum_mode="micro" runs each chunk as its own ProgramCache-resident
    # invocation (peak activation memory = one micro batch — the HBM
    # knob); "fused" runs the identical chunk scan inside ONE program (the
    # large-batch reference at equal effective batch). Both modes compute
    # the same adds on the same values in the same order, so they are
    # bit-identical by construction (CI-pinned).
    accum_steps: int = 1
    accum_mode: str = "micro"  # micro | fused
    # checkpoint retention: keep the last K checkpoints on disk (None =
    # the ALINK_CKPT_KEEP env knob, default 3; <= 0 = unbounded)
    checkpoint_keep: "int | None" = None


def _make_optimizer(cfg: TrainConfig, total_steps: int):
    import optax

    warmup = max(1, int(total_steps * cfg.warmup_ratio))
    sched = optax.warmup_cosine_decay_schedule(
        0.0, cfg.learning_rate, warmup, max(total_steps, warmup + 1)
    )
    if cfg.optimizer == "adamw":
        return optax.adamw(sched, weight_decay=cfg.weight_decay)
    if cfg.optimizer == "adam":
        return optax.adam(sched)
    if cfg.optimizer == "sgd":
        return optax.sgd(sched, momentum=0.9)
    raise ValueError(f"unknown optimizer {cfg.optimizer!r}")


def _loss_fn(kind: str, regression: bool, weighted: "bool | str" = False):
    """Scalar loss ``f(logits, y)`` — or, with ``weighted=True``, the exact
    masked form ``f(logits, y, w) = sum(l_i*w_i)/sum(w)`` used by the
    bucketed train loop (``w==1`` rows reproduce the unweighted mean
    bit-for-bit; ``w==0`` pad rows contribute exactly zero loss and
    gradient). ``weighted="sum"`` returns the UNNORMALIZED numerator
    ``sum(l_i*w_i)`` — the per-chunk form the gradient-accumulation
    programs differentiate (cotangent seed 1; the one division by the
    effective batch's total weight happens at apply time, so a chunk's
    gradient is independent of how the batch splits into chunks)."""
    import jax.numpy as jnp
    import optax

    if kind == "auto":
        kind = "mse" if regression else "softmax"
    if kind == "softmax":
        def per_row(logits, y):
            return optax.softmax_cross_entropy_with_integer_labels(
                logits, y.astype(jnp.int32))
    elif kind == "mse":
        def per_row(logits, y):
            y = y.astype(jnp.float32)
            # scalar regression ships (n, 1) logits against (n,) targets;
            # vector regression (e.g. LSTNet's direct multi-horizon head)
            # ships (n, h) against (n, h) and averages within the row
            if logits.ndim == y.ndim + 1 and logits.shape[-1] == 1:
                logits = logits.squeeze(-1)
            d = (logits - y) ** 2
            return d if d.ndim == 1 else d.mean(-1)
    elif kind == "gaussian_nll":
        # logits (n, 2) = (mu, log_sigma); probabilistic regression (DeepAR)
        def per_row(logits, y):
            mu, log_sigma = logits[..., 0], logits[..., 1]
            sigma2 = jnp.exp(2.0 * log_sigma)
            return log_sigma + 0.5 * (y.astype(jnp.float32) - mu) ** 2 / sigma2
    elif kind == "rows":
        def per_row(rows, y):
            return rows
    else:
        raise ValueError(f"unknown loss {kind!r}")

    if not weighted:
        def f(logits, y):
            return per_row(logits, y).mean()
        return f

    if weighted == "sum":
        def fs(logits, y, w):
            w = w.astype(jnp.float32)
            return (per_row(logits, y) * w).sum()
        return fs

    def fw(logits, y, w):
        w = w.astype(jnp.float32)
        return (per_row(logits, y) * w).sum() / jnp.maximum(w.sum(), 1.0)
    return fw


def _model_key(model) -> tuple:
    """Content key for a flax module: class + field repr. Two modules built
    from the same config hash equal, so fine-tune jobs constructed per run
    share one compiled train step."""
    t = type(model)
    return ("model", f"{t.__module__}.{t.__qualname__}", repr(model))


def make_train_step(model, tx, loss_of, *, weighted: bool = False,
                    cache_key: Any = None):
    """One optimizer step, resident in the process-wide ProgramCache —
    shared by train_model, the benchmark, and the multichip dryrun.
    ``loss_of(logits, y[, w]) -> scalar``.

    ``variables`` is the full flax variables dict; non-"params" collections
    (e.g. BatchNorm "batch_stats") are threaded through mutably and excluded
    from the optimizer update. The optimizer state must be built over
    ``variables["params"]`` only.

    Donation is preserved through the cache: params/opt_state buffers are
    donated (the update writes in place on device — HBM headroom for large
    models; callers rebind to the returned state, the old trees are dead).

    ``cache_key`` supplies a content descriptor (model/optimizer/loss
    config) under which DIFFERENT jobs share the compiled program; without
    it the key falls back to instance identity — same instances reuse the
    program, fresh instances compile their own (never aliased wrongly)."""
    from ..common.jitcache import cached_jit, instance_token

    def _build_train_step():
        import jax
        import optax

        def step_body(variables, opt_state, batch, y, w, dkey):
            params = variables["params"]
            stats = {k: v for k, v in variables.items() if k != "params"}
            mutable = list(stats.keys())

            def loss(p):
                kwargs = {"rngs": {"dropout": dkey}} if dkey is not None else {}
                if mutable:
                    logits, new_stats = model.apply(
                        {"params": p, **stats}, **batch,
                        deterministic=dkey is None, mutable=mutable, **kwargs
                    )
                else:
                    logits = model.apply(
                        {"params": p, **stats}, **batch,
                        deterministic=dkey is None, **kwargs
                    )
                    new_stats = {}
                with jax.named_scope("loss"):
                    l = (loss_of(logits, y, w) if weighted
                         else loss_of(logits, y))
                return l, new_stats

            (l, new_stats), g = jax.value_and_grad(loss, has_aux=True)(params)
            with jax.named_scope("optimizer"):
                updates, opt_state = tx.update(g, opt_state, params)
                new_params = optax.apply_updates(params, updates)
            return {"params": new_params, **dict(new_stats)}, opt_state, l

        if weighted:
            @partial(jax.jit, donate_argnums=(0, 1))
            def train_step(variables, opt_state, batch, y, w, dkey=None):
                return step_body(variables, opt_state, batch, y, w, dkey)
        else:
            @partial(jax.jit, donate_argnums=(0, 1))
            def train_step(variables, opt_state, batch, y, dkey=None):
                return step_body(variables, opt_state, batch, y, None, dkey)
        return train_step

    key = cache_key
    if key is None:
        key = ("inst", instance_token(model), instance_token(tx),
               instance_token(loss_of))
    return cached_jit("dl.train_step", _build_train_step,
                      key_extra=("weighted" if weighted else "plain", key))


def make_accum_programs(model, tx, loss_sum_of, accum: int, *,
                        model_key: Any = None, opt_key: Any = None):
    """The ordered-chunk gradient programs behind ``TrainConfig.
    accum_steps`` — returns ``(micro_step, apply_step, fused_step)``, all
    ProgramCache-resident.

    The gradient of an effective batch is DEFINED as the ordered fp32 sum
    of its micro-chunk gradients (each chunk differentiates the
    unnormalized ``sum(l_i*w_i)``; one division by the batch's total
    weight at apply time). Under that definition the two execution
    shapes are bit-identical by construction:

    - ``micro_step`` — one chunk per invocation, accumulating into donated
      fp32 buffers (peak activation memory = one chunk); ``apply_step``
      normalizes, runs the optimizer update (params/opt_state donated),
      and returns ZEROED accumulators by writing into the donated grad
      buffers — the steady loop allocates nothing.
    - ``fused_step`` — the large-batch reference: the SAME chunk body
      scanned over the reshaped effective batch inside one program, then
      the same apply math. ``lax.scan`` compiles the body once and
      accumulates in the same order on the same values, so its result is
      bitwise equal to the micro-step schedule (CI-pinned) — and the same
      ordered-chunk contract is what makes P-process data parallelism
      bit-identical to ``accum_steps=P`` on one process (`parallel.
      distributed.ordered_cross_process_sum` adds the per-process chunk
      sums in rank order).

    ``micro_step``/``apply_step`` keys carry no chunk count — every
    ``accum_steps`` setting of a job family shares them; ``fused_step``
    bakes in the reshape and keys per count. Models with non-"params"
    collections (e.g. BatchNorm stats) are rejected by the train loop —
    cross-chunk mutable state has no well-defined accumulation order."""
    from ..common.jitcache import cached_jit, instance_token

    if model_key is None:
        model_key = ("inst", instance_token(model),
                     instance_token(loss_sum_of))
    if opt_key is None:
        opt_key = ("inst", instance_token(tx))

    def _chunk_grad(jax, params, batch, y, w, dkey):
        def loss(p):
            kwargs = {"rngs": {"dropout": dkey}} if dkey is not None else {}
            logits = model.apply({"params": p}, **batch,
                                 deterministic=dkey is None, **kwargs)
            with jax.named_scope("loss"):
                return loss_sum_of(logits, y, w)

        return jax.value_and_grad(loss)(params)

    def _build_micro():
        import jax
        import jax.numpy as jnp

        @partial(jax.jit, donate_argnums=(0, 1, 2))
        def micro_step(gacc, wacc, lacc, variables, batch, y, w, dkey=None):
            lsum, g = _chunk_grad(jax, variables["params"], batch, y, w,
                                  dkey)
            gacc = jax.tree.map(lambda a, b: a + b.astype(a.dtype), gacc, g)
            return (gacc, wacc + w.astype(jnp.float32).sum(), lacc + lsum)

        return micro_step

    def _apply_math(jax, jnp, optax, params, opt_state, gacc, wacc, lacc):
        with jax.named_scope("optimizer"):
            denom = jnp.maximum(wacc, 1.0)
            g = jax.tree.map(lambda a: a / denom, gacc)
            updates, opt_state2 = tx.update(g, opt_state, params)
            new_params = optax.apply_updates(params, updates)
            return new_params, opt_state2, lacc / denom

    def _build_apply():
        import jax
        import jax.numpy as jnp
        import optax

        @partial(jax.jit, donate_argnums=(0, 1, 2))
        def apply_step(variables, opt_state, gacc, wacc, lacc):
            new_params, opt_state2, loss = _apply_math(
                jax, jnp, optax, variables["params"], opt_state, gacc,
                wacc, lacc)
            zero_g = jax.tree.map(jnp.zeros_like, gacc)
            return ({"params": new_params}, opt_state2, loss, zero_g,
                    jnp.zeros_like(wacc), jnp.zeros_like(lacc))

        return apply_step

    def _build_fused():
        import jax
        import jax.numpy as jnp
        import optax

        @partial(jax.jit, donate_argnums=(0, 1))
        def fused_step(variables, opt_state, batch, y, w, dkeys=None):
            # batch/y/w arrive PRE-CHUNKED as (accum, micro, ...) stacks,
            # sharded on the micro axis (chunked_batch_sharding) — each
            # scanned chunk then has the per-device layout of a standalone
            # micro batch, which is what makes this program the bitwise
            # twin of the micro-step schedule on any mesh
            params = variables["params"]
            xs = (batch, y, w)
            if dkeys is not None:
                xs = xs + (dkeys,)

            def body(carry, x):
                gacc, wacc, lacc = carry
                bk, yk, wk = x[0], x[1], x[2]
                dk = x[3] if len(x) > 3 else None
                lsum, g = _chunk_grad(jax, params, bk, yk, wk, dk)
                gacc = jax.tree.map(lambda a, b: a + b.astype(a.dtype),
                                    gacc, g)
                return ((gacc, wacc + wk.astype(jnp.float32).sum(),
                         lacc + lsum), None)

            zero = (jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params),
                jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32))
            (gacc, wacc, lacc), _ = jax.lax.scan(body, zero, xs)
            new_params, opt_state2, loss = _apply_math(
                jax, jnp, optax, params, opt_state, gacc, wacc, lacc)
            return {"params": new_params}, opt_state2, loss

        return fused_step

    micro = cached_jit("dl.micro_step", _build_micro,
                       key_extra=("micro", model_key))
    apply_p = cached_jit("dl.apply_grads", _build_apply,
                         key_extra=("apply", model_key, opt_key))
    fused = cached_jit("dl.fused_accum_step", _build_fused,
                       key_extra=("fused", int(accum), model_key, opt_key))
    return micro, apply_p, fused


def _apply_program(model, key: Any = None):
    """Deterministic forward pass ``prog(params, batch) -> logits`` in the
    ProgramCache — eval and predict share one compiled program per model
    config."""
    from ..common.jitcache import cached_jit

    def _build_apply():
        import jax

        return jax.jit(
            lambda params, batch: model.apply(params, **batch,
                                              deterministic=True))

    return cached_jit("dl.apply_logits", _build_apply,
                      key_extra=key if key is not None else _model_key(model))


def _apply_program_int8(model, scales, key: Any = None):
    """Weight-only int8 twin of :func:`_apply_program`: the int8 parameter
    tree dequantizes in-kernel against per-channel ``scales`` (closed over
    as constants — they are tiny) before ``model.apply``. Keyed under its
    own ``dl.apply_logits.int8`` kernel id AND by the scale contents, so
    fp32 and int8 programs — and two differently-quantized fine-tunes of
    one config — coexist in the ProgramCache."""
    import jax as _jax

    from ..common.jitcache import cached_jit

    def _build_apply():
        import jax

        def run(qparams, batch):
            params = jax.tree_util.tree_map(
                lambda q, s: q if s is None else q.astype("float32") * s,
                qparams, scales)
            return model.apply(params, **batch, deterministic=True)

        return jax.jit(run)

    scale_leaves = tuple(np.asarray(s, np.float32)
                         for s in _jax.tree_util.tree_leaves(scales))
    return cached_jit(
        "dl.apply_logits.int8", _build_apply,
        key_extra=(key if key is not None else _model_key(model),
                   scale_leaves))


def _feed(build: Callable[[int], Sequence[np.ndarray]],
          place: Callable[[Sequence[np.ndarray]], Sequence[Any]],
          steps: int, *, mode: str = "async",
          depth: int = 0, phases: Optional[dict] = None
          ) -> Iterator[Tuple[int, Sequence[Any]]]:
    """Yield ``(step, device_arrays)`` for ``build(step)`` host batches.

    ``async``: batch assembly AND the sharded ``device_put`` run on the
    shared ``alink-h2d`` transfer pool via
    :func:`~alink_tpu.common.streaming.stream_map`, with up to ``depth``
    batches in flight ahead of compute — the train step consumes
    device-resident buffers and never blocks on the host. ``sync`` builds
    and ships inline (the bit-identical reference feed: both modes call the
    same ``build``/``place`` on the same step order)."""
    if mode not in ("async", "sync"):
        raise ValueError(f"unknown feed mode {mode!r}")
    if mode == "sync":
        for s in range(steps):
            yield s, place(build(s))
        return

    from ..common.streaming import stream_map

    def batches():
        for s in range(steps):
            # the "host arrays" slot carries only the step number — the
            # real assembly happens inside put() on the transfer thread
            yield s, (s,)

    def put(args):
        return place(build(int(args[0])))

    yield from stream_map(lambda *devs: list(devs), batches(), put=put,
                          depth=depth or None, phases=phases)


def _timed_feed(it):
    """Drain a feed iterator, observing ``train.feed_wait_s`` — the time
    the step loop blocked waiting for the next device batch (~0 when the
    async pipeline overlaps; ~assembly+transfer when the host is the
    bottleneck). Per-step wall (``train.step_s``) stays with the callers:
    its unit is the OPTIMIZER step, which under accumulation spans several
    feed items."""
    import time as _time

    from ..common.metrics import metrics as _metrics

    while True:
        t0 = _time.perf_counter()
        try:
            item = next(it)
        except StopIteration:
            return
        _metrics.observe("train.feed_wait_s", _time.perf_counter() - t0)
        yield item


def _pad_tail(arrs: List[np.ndarray], target: int) -> List[np.ndarray]:
    """Pad row-aligned arrays to ``target`` rows by repeating the last real
    row — numerically safe for any model (no all-padding attention rows, no
    degenerate inputs), and exact under a zero loss-weight."""
    m = arrs[0].shape[0]
    if m == target:
        return arrs
    return [np.concatenate([a, np.repeat(a[-1:], target - m, axis=0)])
            for a in arrs]


# 1 ms to 10 s in steps of 10%: a median read from these edges lies within
# 5% of the step, where the default ladder has one bucket from 50 to 100 ms
_DEVICE_STEP_BUCKETS = tuple(1e-3 * 1.1 ** i for i in range(98))


class _StepWatch:
    """Each optimizer step's finish on the device's side. The loop hands
    over every step's scalar loss with the host clock at hand-over; one
    thread waits on them in order (``block_until_ready`` releases the
    interpreter) and observes ``train.device_step_s`` = finish(i) less the
    later of finish(i-1) and hand-over(i): the device's pace while the host
    runs ahead, the host's where the device waits for it. The first step of
    a call has no finish before it and is left out. A step over the median
    of the last 32 by more than 5% and 20 ms counts ``slow.train.step``."""

    def __init__(self):
        self._handed: Any = queue.Queue()
        self._recent: Any = deque(maxlen=32)
        self._last_finish: Optional[float] = None
        # the epoch's count and slowest step, which ``drain`` takes away
        self._lock = threading.Lock()
        self._steps = 0
        self._slowest: Tuple[Optional[int], float] = (None, 0.0)
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="alink-train-watch")
        self._thread.start()

    def hand(self, step: int, loss) -> None:
        self._handed.put((step, loss, time.perf_counter()))

    def _run(self) -> None:
        import jax

        from ..common.metrics import metrics

        while True:
            item = self._handed.get()
            try:
                if item is None:
                    return
                self._watch(jax, metrics, *item)
            except Exception:
                # an instrument never ends the fit: a step it cannot read
                # (the loop meets a failed step itself) is counted, and the
                # thread lives on, so ``drain`` is always answered
                metrics.incr("train.watch_errors")
            finally:
                self._handed.task_done()

    def _watch(self, jax, metrics, step: int, loss, t_hand: float) -> None:
        jax.block_until_ready(loss)
        now = time.perf_counter()
        took = None
        if self._last_finish is not None:
            took = now - max(self._last_finish, t_hand)
            metrics.observe("train.device_step_s", took,
                            buckets=_DEVICE_STEP_BUCKETS)
            if slow_against(self._recent, took) is not None:
                metrics.incr("slow.train.step")
            self._recent.append(took)
        self._last_finish = now
        with self._lock:
            self._steps += 1
            if took is not None and took > self._slowest[1]:
                self._slowest = (step, took)

    def drain(self, span, wait: bool = True) -> None:
        """Put the epoch's count of steps and its slowest step on its span.
        ``wait``: for every step handed over so far, which costs nothing
        where the loop has just read the last step's loss itself; where it
        has not (``log_every``), the watcher adds no synchronisation of its
        own, and a step still on the device counts to the next epoch."""
        if wait:
            self._handed.join()
        with self._lock:
            steps, slowest = self._steps, self._slowest
            self._steps, self._slowest = 0, (None, 0.0)
        if span is not None:
            span.attrs["steps"] = steps
            if slowest[0] is not None:
                span.attrs["slowest_step"] = slowest[0]
                span.attrs["slowest_step_s"] = round(slowest[1], 6)

    def close(self) -> None:
        self._handed.put(None)
        self._thread.join()


def train_model(
    model,
    inputs: Dict[str, np.ndarray],
    y: np.ndarray,
    cfg: TrainConfig,
    *,
    mesh=None,
    regression: bool = False,
    seq_axis: Optional[int] = 1,
    init_params=None,
    on_epoch: Optional[Callable[[int, Any], None]] = None,
) -> Tuple[Any, Dict[str, Any]]:
    """Train a flax module. `inputs` maps arg names -> (n, ...) arrays; the
    module is called as model.apply(params, **inputs_batch, deterministic=...).
    Returns (params, history).

    Anything with a flax module's ``apply`` (and a ``repr`` that names its
    configuration) trains the same way when ``init_params`` hands its
    variables in: :class:`alink_tpu.dl.lm.CausalLMTrainer` is one, with
    ``cfg.loss = "rows"`` and ``y`` unused. Collections beside ``"params"``
    are state the step hands back updated by the model's own rule, donated
    with the rest. ``on_epoch(epoch, variables)`` is called at the end of
    every epoch, inside its span and after its one synchronisation, with the
    variables as they lie on the device: the place for one read an epoch.

    ``cfg.accum_steps`` > 1 runs the ordered-chunk gradient schedule (see
    :func:`make_accum_programs`). In a multi-process cluster
    (``jax.distributed`` joined via ``parallel.distributed.
    init_multi_host`` — the env knobs COORDINATOR_ADDRESS / NUM_PROCESSES
    / PROCESS_ID) every process calls ``train_model`` with the SAME
    arguments: each computes its own shard of every micro-chunk, gradients
    combine rank-ordered across processes before the optimizer step, and
    only the coordinator writes checkpoints — results are bit-identical on
    every process, and bit-identical to a single-process run with
    ``accum_steps = P × accum_steps`` at equal effective batch."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ..analysis import preflight_train_config
    from ..common.jitcache import bucket_rows, bucketing_enabled
    from ..parallel.distributed import (data_parallel_topology,
                                        init_multi_host)
    from ..parallel.mesh import default_mesh

    preflight_train_config(cfg)  # ALK103 recompile hazards, mode-gated
    init_multi_host()  # idempotent; no-op without the topology env knobs
    shard_idx, num_shards = data_parallel_topology()

    accum = int(cfg.accum_steps or 1)
    if accum < 1:
        raise ValueError(f"accum_steps must be >= 1, got {cfg.accum_steps}")
    if cfg.accum_mode not in ("micro", "fused"):
        raise ValueError(f"unknown accum_mode {cfg.accum_mode!r}")
    if accum > 1 and cfg.batch_size % accum:
        raise ValueError(
            f"batch_size={cfg.batch_size} is not divisible by "
            f"accum_steps={accum}: micro chunks must tile the effective "
            "batch exactly (the ordered-chunk gradient contract)")
    if num_shards > 1 and cfg.accum_mode == "fused":
        raise ValueError(
            "accum_mode='fused' needs the whole effective batch on one "
            "process; use 'micro' under multi-process data parallelism")
    scale = accum > 1 or num_shards > 1

    if num_shards > 1 and mesh is None:
        # per-process shards ride a LOCAL mesh: the global gradient is
        # combined explicitly (rank-ordered) by the accumulation loop, so
        # no program spans non-addressable devices
        from ..parallel.mesh import AXIS_DATA as _AD
        from ..parallel.mesh import make_mesh

        local = jax.local_devices()
        mesh = make_mesh({_AD: len(local)}, devices=local)
    mesh = mesh or default_mesh()
    n = y.shape[0]
    rng = np.random.default_rng(cfg.seed)

    # train/eval split
    n_eval = int(n * cfg.eval_ratio)
    perm = rng.permutation(n)
    eval_idx, train_idx = perm[:n_eval], perm[n_eval:]
    tr_inputs = {k: v[train_idx] for k, v in inputs.items()}
    tr_y = y[train_idx]
    ev_inputs = {k: v[eval_idx] for k, v in inputs.items()}
    ev_y = y[eval_idx]
    n_train = tr_y.shape[0]

    from ..parallel.mesh import AXIS_DATA

    dp = mesh.shape.get(AXIS_DATA, 1)
    # batch dim must divide evenly over the data axis — and under the
    # scale loop, each of the accum_steps micro chunks must tile over the
    # (process, data-axis) grid too
    unit = dp * accum * num_shards
    bs = max(unit, (min(cfg.batch_size, n_train) // unit) * unit)
    # device batch shape snaps onto the bucket ladder (rungs are multiples
    # of 8; pad rows carry zero loss-weight) so a batch-size sweep across
    # jobs shares compiled programs — and within a job, the ragged tail
    # batch reuses the full-batch program instead of tracing a second shape.
    # A rung that would at least double the rows is not taken: every step
    # would pay for the padding what one more compiled program costs once
    # (2 rows of 8,192 positions on a ladder that starts at 8 are four
    # steps' work)
    padded_bs = bs
    if bucketing_enabled():
        b = bucket_rows(bs)
        if b % unit == 0 and b < 2 * bs:
            padded_bs = b
    if n_train >= bs:
        steps_per_epoch = -(-n_train // bs)  # tail rows now train too
    else:
        steps_per_epoch = 1
    total_steps = steps_per_epoch * cfg.num_epochs

    # init
    key = jax.random.PRNGKey(cfg.seed)
    sample = {k: jnp.asarray(v[:1]) for k, v in tr_inputs.items()}
    if init_params is None:
        params = model.init(key, **sample, deterministic=True)
    else:
        params = init_params
    tx = _make_optimizer(cfg, total_steps)
    with trace_span("train.place_state"):
        p_shard = param_shardings(params, mesh)
        params = jax.device_put(params, p_shard)
        opt_state = tx.init(params["params"])
    loss_of = _loss_fn(cfg.loss, regression, weighted=True)

    def in_shard(arr):
        sa = seq_axis if arr.ndim > (seq_axis or 0) else None
        return batch_sharding(mesh, arr.ndim, seq_axis=sa)

    # content-keyed: N jobs with the same (model, optimizer, loss) config
    # share ONE compiled step; the key carries everything the closure bakes
    # into the program (schedule length included)
    mk = _model_key(model)
    ok = ("opt", cfg.optimizer, cfg.learning_rate, cfg.weight_decay,
          cfg.warmup_ratio, total_steps)
    job_key = (mk, ok, ("loss", cfg.loss, regression))
    train_step = micro_prog = apply_prog = fused_prog = None
    if scale:
        if any(k != "params" for k in params):
            raise ValueError(
                "accum_steps/multi-process training supports params-only "
                "models: non-'params' collections (e.g. BatchNorm "
                f"batch_stats, here {sorted(params)}) have no well-defined "
                "cross-chunk accumulation order")
        loss_sum_of = _loss_fn(cfg.loss, regression, weighted="sum")
        micro_prog, apply_prog, fused_prog = make_accum_programs(
            model, tx, loss_sum_of, accum,
            model_key=(mk, ("loss", cfg.loss, regression)), opt_key=ok)
    else:
        train_step = make_train_step(model, tx, loss_of, weighted=True,
                                     cache_key=job_key)
    eval_prog = _apply_program(model)

    from ..common.metrics import metrics as _metrics
    from ..common.tracing import set_process_identity
    import time as _time

    _metrics.set_gauge("train.state_bytes", sum(
        int(a.nbytes) for a in jax.tree.leaves((params, opt_state))))

    if num_shards > 1:
        # label this rank's spans so a 2-process drill stitches into one
        # waterfall with a lane per rank (single-process stays untagged —
        # trace output is byte-stable there)
        set_process_identity(f"rank{shard_idx}")

    ckpt = None
    start_epoch = 0
    history: Dict[str, Any] = {"loss": [], "eval_metric": []}
    best_metric, best_params, patience_left = None, None, cfg.early_stopping_patience
    step = 0
    if cfg.checkpoint_dir:
        from .checkpoint import TrainCheckpointManager

        ckpt = TrainCheckpointManager(cfg.checkpoint_dir,
                                      max_to_keep=cfg.checkpoint_keep)
        if cfg.resume:
            restored = ckpt.restore_latest(params, opt_state)
            if restored is not None:
                r_params, r_opt, extra = restored
                params = jax.device_put(r_params, p_shard)
                # re-place the optimizer state: moment trees keep the
                # shardings the fresh init derived from the sharded params;
                # scalar counters (single-device after eager init) replicate
                rep = NamedSharding(mesh, P())

                def _place(cur, new):
                    sh = getattr(cur, "sharding", None)
                    if sh is None or len(sh.device_set) < mesh.size:
                        sh = rep
                    return jax.device_put(new, sh)

                opt_state = jax.tree.map(_place, opt_state, r_opt)
                step = int(extra.get("step", 0))
                start_epoch = int(extra.get("epoch", -1)) + 1

    names = sorted(tr_inputs)
    in_shards = [in_shard(tr_inputs[k]) for k in names]
    row_shard = batch_sharding(mesh, 1)

    def place(arrs):
        # runs on the transfer pool under async feed: the sharded copies
        # complete inside the transfer thread (that is what makes the
        # overlap real), so the consuming step dispatches with zero wait
        devs = [jax.device_put(a, sh)
                for a, sh in zip(arrs, in_shards + [row_shard, row_shard])]
        jax.block_until_ready(devs)
        return devs

    place_chunked = None
    if scale and cfg.accum_mode == "fused":
        from .sharding import chunked_batch_sharding

        def _in_shard_chunked(logical_ndim):
            sa = seq_axis if logical_ndim > (seq_axis or 0) else None
            return chunked_batch_sharding(mesh, logical_ndim + 1,
                                          seq_axis=sa)

        in_shards_chunked = [_in_shard_chunked(tr_inputs[k].ndim)
                             for k in names]
        chunk_row_shard = chunked_batch_sharding(mesh, 2)

        def place_chunked(arrs):
            # the fused-accumulation feed: (accum, micro, ...) stacks
            # sharded on the micro axis, same overlap contract as place()
            devs = [jax.device_put(a, sh)
                    for a, sh in zip(arrs, in_shards_chunked
                                     + [chunk_row_shard, chunk_row_shard])]
            jax.block_until_ready(devs)
            return devs

    feed_phases: Dict[str, Any] = {}
    t_start = _time.perf_counter()
    start_step = step   # resume restores the global counter; rate uses deltas
    # multi-process: only the coordinator writes checkpoints (every process
    # computes identical state — the combine is replicated by construction)
    save_ckpt = ckpt is not None and shard_idx == 0
    micro_rows = padded_bs // accum          # chunk rows, global
    shard_rows = micro_rows // num_shards    # chunk rows, this process
    gacc = wacc = lacc = None
    if scale and cfg.accum_mode == "micro":
        gacc = jax.tree.map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params["params"])
        wacc = jnp.zeros((), jnp.float32)
        lacc = jnp.zeros((), jnp.float32)
    if num_shards > 1:
        from ..parallel.distributed import ordered_cross_process_sum

    def _after_step(s, l, epoch):
        nonlocal step
        if watch is not None:
            watch.hand(step, l)
        step += 1
        _metrics.incr("train.steps")
        _metrics.incr("train.rows", int(min(bs, n_train - s * bs))
                      if n_train >= bs else bs)
        if save_ckpt and cfg.checkpoint_every and \
                step % cfg.checkpoint_every == 0:
            # mid-epoch save: resume restarts this epoch with this state
            ckpt.save(step, jax.device_get(params),
                      jax.device_get(opt_state),
                      {"step": step, "epoch": epoch - 1})
        if cfg.log_every and step % cfg.log_every == 0:
            lv = float(l)
            history["loss"].append(lv)
            elapsed = _time.perf_counter() - t_start
            _metrics.record("dl.train", step=step, loss=lv,
                            samples_per_sec=step * bs / max(elapsed, 1e-9))

    # the device's side of every step: one thread for this call, ended with it
    watch = _StepWatch() if tracing_enabled() else None
    try:
        for epoch in range(start_epoch, cfg.num_epochs):
            # one rank-tagged span per epoch: in a multi-process drill each
            # rank exports its own train.epoch lane into the stitched trace
            with trace_span("train.epoch", unit="train", epoch=epoch,
                            rank=shard_idx, shards=num_shards) as epoch_span:
                # per-(seed, epoch) generator, NOT the sequentially-consumed rng: a
                # crash-resumed run must replay the exact shuffle of the epochs it
                # skipped past (dropout keys already align via fold_in(key, step))
                order = np.random.default_rng((cfg.seed, epoch)).permutation(n_train)
                if n_train < bs:  # tile tiny datasets up to one full batch
                    order = np.resize(order, bs)

                if not scale:
                    def build(s, _order=order):
                        idx = _order[s * bs:(s + 1) * bs]
                        arrs = [tr_inputs[k][idx] for k in names] + [tr_y[idx]]
                        w = np.ones(len(idx), np.float32)
                        if len(idx) < padded_bs:
                            arrs = _pad_tail(arrs, padded_bs)
                            w = np.concatenate(
                                [w, np.zeros(padded_bs - len(idx), np.float32)])
                        return arrs + [w]

                    t_step = _time.perf_counter()
                    for s, devs in _timed_feed(_feed(
                            build, place, steps_per_epoch, mode=cfg.feed,
                            depth=cfg.feed_depth, phases=feed_phases)):
                        batch = dict(zip(names, devs[:-2]))
                        yb, wb = devs[-2], devs[-1]
                        with step_annotation("train.step", step):
                            params, opt_state, l = train_step(
                                params, opt_state, batch, yb, wb,
                                jax.random.fold_in(key, step)
                            )
                        _metrics.observe("train.step_s",
                                         _time.perf_counter() - t_step)
                        t_step = _time.perf_counter()
                        _after_step(s, l, epoch)
                elif cfg.accum_mode == "fused":
                    def build_full(s, _order=order):
                        idx = _order[s * bs:(s + 1) * bs]
                        arrs = [tr_inputs[k][idx] for k in names] + [tr_y[idx]]
                        w = np.ones(len(idx), np.float32)
                        if len(idx) < padded_bs:
                            arrs = _pad_tail(arrs, padded_bs)
                            w = np.concatenate(
                                [w, np.zeros(padded_bs - len(idx), np.float32)])
                        # pre-chunk host-side: (accum, micro, ...) — the scan's
                        # chunk layout is decided HERE, not by an in-program
                        # reshard (see chunked_batch_sharding)
                        return [a.reshape((accum, micro_rows) + a.shape[1:])
                                for a in arrs + [w]]

                    t_step = _time.perf_counter()
                    for s, devs in _timed_feed(_feed(
                            build_full, place_chunked, steps_per_epoch,
                            mode=cfg.feed, depth=cfg.feed_depth,
                            phases=feed_phases)):
                        batch = dict(zip(names, devs[:-2]))
                        yb, wb = devs[-2], devs[-1]
                        with step_annotation("train.step", step):
                            skey = jax.random.fold_in(key, step)
                            dkeys = jnp.stack([jax.random.fold_in(skey, k)
                                               for k in range(accum)])
                            params, opt_state, l = fused_prog(
                                params, opt_state, batch, yb, wb, dkeys)
                        _metrics.observe("train.step_s",
                                         _time.perf_counter() - t_step)
                        t_step = _time.perf_counter()
                        _after_step(s, l, epoch)
                else:
                    def build_micro(m, _order=order):
                        s, k = divmod(m, accum)
                        start = s * bs
                        m_real = min(bs, len(_order) - start)
                        lo = k * micro_rows + shard_idx * shard_rows
                        pos = np.arange(lo, lo + shard_rows)
                        # positions past the real rows pad by repeating the LAST
                        # real row of the effective batch with zero loss-weight —
                        # the same exact-padding contract as the fused reference
                        idx = _order[start + np.minimum(pos, m_real - 1)]
                        arrs = [tr_inputs[k2][idx] for k2 in names] + [tr_y[idx]]
                        return arrs + [(pos < m_real).astype(np.float32)]

                    t_step = _time.perf_counter()
                    skey = None
                    # an optimizer step spans accum feed items: its annotation
                    # opens on the first chunk and closes after the apply (or
                    # when the loop raises)
                    with contextlib.ExitStack() as step_scope:
                        for m, devs in _timed_feed(_feed(
                                build_micro, place, steps_per_epoch * accum,
                                mode=cfg.feed, depth=cfg.feed_depth,
                                phases=feed_phases)):
                            s, k = divmod(m, accum)
                            if k == 0:
                                skey = jax.random.fold_in(key, step)
                                step_scope.enter_context(
                                    step_annotation("train.step", step))
                            batch = dict(zip(names, devs[:-2]))
                            yb, wb = devs[-2], devs[-1]
                            gacc, wacc, lacc = micro_prog(
                                gacc, wacc, lacc, params, batch, yb, wb,
                                jax.random.fold_in(skey, k))
                            _metrics.incr("train.micro_steps")
                            if k == accum - 1:
                                ga, wa, la = gacc, wacc, lacc
                                if num_shards > 1:
                                    # rank-ordered sum of the per-process chunk
                                    # accumulators — bit-identical on every
                                    # process
                                    ga, wa, la = ordered_cross_process_sum(
                                        (gacc, wacc, lacc))
                                t_f = _time.perf_counter()
                                params, opt_state, l, gacc, wacc, lacc = \
                                    apply_prog(params, opt_state, ga, wa, la)
                                _metrics.observe("train.accum_flush_s",
                                                 _time.perf_counter() - t_f)
                                _metrics.observe("train.step_s",
                                                 _time.perf_counter() - t_step)
                                step_scope.close()
                                t_step = _time.perf_counter()
                                _after_step(s, l, epoch)
                if not cfg.log_every:
                    lv = float(l)
                    history["loss"].append(lv)
                    elapsed = _time.perf_counter() - t_start
                    _metrics.record(
                        "dl.train", step=step, loss=lv,
                        samples_per_sec=(step - start_step) * bs / max(elapsed, 1e-9))
                if watch is not None:
                    watch.drain(epoch_span, wait=not cfg.log_every)

                if on_epoch is not None:
                    on_epoch(epoch, params)
                if save_ckpt:
                    ckpt.save(step, jax.device_get(params), jax.device_get(opt_state),
                              {"step": step, "epoch": epoch})
                if n_eval:
                    logits = _batched_apply(eval_prog, params, ev_inputs, mesh,
                                            in_shard, bs)
                    if regression:
                        metric = -float(np.mean((logits.squeeze(-1) - ev_y) ** 2))
                    else:
                        metric = float(np.mean(np.argmax(logits, -1) == ev_y))
                    history["eval_metric"].append(metric)
                    if best_metric is None or metric > best_metric:
                        # host copy: the next train_step DONATES the live buffers, so
                        # stashing the device tree directly would dangle
                        best_metric, best_params = metric, jax.device_get(params)
                        patience_left = cfg.early_stopping_patience
                    elif cfg.early_stopping_patience:
                        patience_left -= 1
                        if patience_left <= 0:
                            break
    finally:
        if watch is not None:
            watch.close()

    if best_params is not None:
        params = best_params
    history["final_loss"] = history["loss"][-1] if history["loss"] else None
    if feed_phases:
        # compute runs in THIS loop (the feed's fn is identity), so only the
        # transfer-side phases carry signal here
        history["feed"] = {
            "mode": cfg.feed,
            "transfer_s": round(feed_phases.get("transfer_s", 0.0), 4),
            "batches": feed_phases.get("batches", 0),
        }
    with trace_span("train.export_model"):
        return jax.device_get(params), history


# Rows of one slice of a predict whose producer can hand its rows out a part
# at a time (the BERT mapper tokenises them): the forward of one slice runs on
# the device while the host makes the next. A rung of the default bucket
# ladder, so a slice is a program a server has warmed. Read on the chip at
# 32, 64 and 128 (PERF.md section 6, PR 33): the smaller slice leaves a
# shorter last forward that nothing hides, the larger costs less device time
# a row; 32 gave the shortest cycle of a served batch of 256.
PREDICT_SLICE = 32
# Forwards dispatched and not yet read back: enough that the device always
# has the next one queued, few enough that a long table's inputs and results
# do not pile up on the device.
_SLICES_OUT = 4

PredictInputs = Dict[str, np.ndarray]


def _batched_apply(fn, params,
                   inputs: Union[PredictInputs, Iterable[PredictInputs]],
                   mesh, in_shard, bs: int) -> np.ndarray:
    """``fn`` over the rows of ``inputs``, at most ``bs`` rows a call, rows
    in order. ``inputs`` is one dict of row-aligned arrays or an iterable of
    such dicts. Nothing is waited for inside the loop: a chunk is padded,
    placed and dispatched, its copy back is started, and only then is the
    next chunk cut (or asked of the iterable, which may do host work for it
    under the forward just dispatched). Results are read in order at the
    end, or from the oldest on once ``_SLICES_OUT`` are out."""
    import collections

    import jax

    from ..common.jitcache import bucket_rows, bucketing_enabled
    from ..common.metrics import metrics
    from ..parallel.mesh import AXIS_DATA

    dp = mesh.shape.get(AXIS_DATA, 1)

    def chunks():
        for part in ((inputs,) if isinstance(inputs, dict) else inputs):
            names = sorted(part)
            for s in range(0, part[names[0]].shape[0], bs):
                yield names, [np.asarray(part[k][s:s + bs]) for k in names]

    out_q: collections.deque = collections.deque()
    outs: List[np.ndarray] = []

    def read_oldest():
        out, m = out_q.popleft()
        # its own span, never inside the dispatch's or the producer's: the
        # host waits here for the forward (and has nothing else to do)
        with trace_span("dl.predict.apply"):
            outs.append(np.asarray(out)[:m])

    rows = m = 0
    # the loop asks for the next chunk when this one's forward is on its way
    for names, chunk in chunks():
        if len(out_q) == _SLICES_OUT:
            read_oldest()
        with trace_span("dl.predict.apply"):
            m = chunk[0].shape[0]
            # pad up the bucket ladder (then to the data-axis multiple) and
            # trim after — the forward pass is row-wise, so repeated-last-row
            # padding is exact, and ragged eval tails reuse the full-chunk
            # program
            target = bucket_rows(m) if bucketing_enabled() else m
            target += (-target) % dp
            if target != m:
                chunk = _pad_tail(chunk, target)
            batch = {k: jax.device_put(v, in_shard(v))
                     for k, v in zip(names, chunk)}
            out = fn(params, batch)
            out.copy_to_host_async()
            out_q.append((out, m))
        rows += m
    while out_q:
        read_oldest()
    metrics.incr("predict.rows", rows)
    # every chunk but the last was dispatched before the last was asked for
    metrics.incr("predict.rows_dispatched_ahead", rows - m)
    return np.concatenate(outs, axis=0)


class PreparedParams(NamedTuple):
    """Parameters as the forward program takes them: what of
    :func:`predict_model` depends on (model, params, precision, mesh) alone."""

    apply: Any   # the forward program: dl.apply_logits or its int8 twin
    params: Any  # rounded or quantised by the policy, placed under ``mesh``
    mesh: Any


def prepare_params(model, params, *, mesh=None,
                   precision: Optional[str] = None) -> PreparedParams:
    """Apply the serving precision policy to a host parameter tree, place it
    under ``mesh`` and choose the forward program (see :func:`predict_model`
    for the policies). A caller that predicts more than once keeps the result
    and hands it to :func:`predict_model` in the tree's place.

    Given a prepared form, returns it as it is when it was placed under this
    mesh, and otherwise places its tree (the policy already applied) under
    this one: ``precision`` is read for a host tree only."""
    import jax

    from ..common import quant
    from ..parallel.mesh import default_mesh

    mesh = mesh or default_mesh()
    if isinstance(params, PreparedParams) and params.mesh == mesh:
        return params
    # dispatch time: nothing is synchronised for the span's sake
    with trace_span("dl.predict.place_params"):
        if isinstance(params, PreparedParams):
            apply, params = params.apply, params.params
        else:
            policy = quant.resolve_policy(precision)
            if policy == quant.BF16:
                params = jax.tree_util.tree_map(
                    lambda a: quant.bf16_round(a)
                    if np.issubdtype(np.asarray(a).dtype, np.floating)
                    else a, params)
            if policy == quant.INT8:
                params, scales = quant.quantize_tree(params)
                apply = _apply_program_int8(model, scales)
            else:
                apply = _apply_program(model)
        placed = jax.device_put(params, param_shardings(params, mesh))
    return PreparedParams(apply, placed, mesh)


def predict_model(
    model, params,
    inputs: Union[PredictInputs, Iterable[PredictInputs]], *, mesh=None,
    batch_size: int = 256, seq_axis: Optional[int] = 1,
    precision: Optional[str] = None,
) -> np.ndarray:
    """Batched inference returning logits (n, out_dim).

    ``inputs`` is a dict of row-aligned arrays, or an iterable of such dicts,
    one per slice of the rows (of ``PREDICT_SLICE`` rows, by convention). A
    slice is asked of the iterable only after the forward of the slice before
    it has been dispatched, so whatever the iterable does to make a slice (a
    generator that tokenises) runs on the host while the device works.

    ``precision`` applies the serving quantization policy to the encoder:
    ``int8`` quantizes every >=2-D float parameter per-channel (weight-only
    — dequantized in-kernel by the ``dl.apply_logits.int8`` program);
    ``bf16`` rounds float parameters through bfloat16. Unset leaves the
    fp32 path byte-identical.

    ``params`` is a host tree, prepared here on every call, or what
    :func:`prepare_params` made of one, which is then neither rounded nor
    placed again."""
    with trace_span("dl.predict"):
        prepared = prepare_params(model, params, mesh=mesh,
                                  precision=precision)

        def in_shard(arr):
            sa = seq_axis if arr.ndim > (seq_axis or 0) else None
            return batch_sharding(prepared.mesh, arr.ndim, seq_axis=sa)

        return _batched_apply(prepared.apply, prepared.params, inputs,
                              prepared.mesh, in_shard, batch_size)
