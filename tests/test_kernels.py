"""Custom-kernel program tests (native/kernels.py registry + the fused SGNS
and flash-attention Pallas kernels).

Numerics run in Pallas interpret mode (the root conftest asks for it) on the
8-virtual-device CPU mesh; the Mosaic side is checked as far as a CPU can
take it, by lowering each kernel for the ``tpu`` platform at the block
shapes its caller uses. Parity contracts follow the registry: pinned fp32
tolerance (atol=1e-5) where the kernel's reduction order differs from
XLA's, byte-identity for the knob-off path.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from alink_tpu.common.metrics import metrics

pytestmark = pytest.mark.kernels


# ---------------------------------------------------------------------------
# registry + shared gate
# ---------------------------------------------------------------------------


def test_registry_contents():
    from alink_tpu.native.kernels import (KERNEL_MODULES, covering,
                                          kernel_ids, kernel_spec, registry)

    assert kernel_ids() == ("dl.attn_pallas", "dl.retention_pallas",
                            "embedding.sgns_pallas", "tree.pallas_hist")
    for kid in kernel_ids():
        spec = kernel_spec(kid)
        assert spec["knob"].startswith("ALINK_")
        assert spec["module"] in KERNEL_MODULES
        assert spec["fallback"] and spec["contract"] and spec["programs"]
    assert kernel_spec("no.such.kernel") is None

    # the candidates-table join: ProgramCache kernel_id -> covering kernel
    assert covering("tree.level") == "tree.pallas_hist"
    assert covering("tree.level.depth3") == "tree.pallas_hist"
    assert covering("embedding.sgns_sharded") == "embedding.sgns_pallas"
    assert covering("dl.train_step") == "dl.attn_pallas"
    assert covering("dl.attention") == "dl.attn_pallas"
    assert covering("lm.prefill_chunk") == "dl.retention_pallas"
    assert covering("lm.decode_step") is None   # retention_step is XLA's
    assert covering("optim.lbfgs") is None
    assert covering("embedding.sgns") is None   # host engine: no kernel

    import jax

    jax.devices()                          # this process has a backend
    live = registry()
    for kid, rec in live.items():
        assert rec["enabled"] is False     # unset knob, cpu backend
        assert rec["interpret"] is True    # the conftest asked for it


def test_registry_readout_never_starts_a_backend():
    """A supervisor or the WebUI asking for the kernel table must not take
    the chip: in a process that has not started a backend the live readout
    answers from the env alone (``enabled`` is None for unset knobs) and
    leaves jax unimported."""
    code = (
        "import sys, json\n"
        "from alink_tpu.native.kernels import registry\n"
        "rows = registry()\n"
        "print(json.dumps({'jax': 'jax' in sys.modules,\n"
        "  'enabled': {k: r['enabled'] for k, r in rows.items()}}))\n")
    env = dict(os.environ, ALINK_GBDT_PALLAS="1")
    env.pop("ALINK_ATTN_PALLAS", None)
    env.pop("ALINK_SGNS_PALLAS", None)
    env.pop("ALINK_RETENTION_PALLAS", None)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run([sys.executable, "-c", code], env=env, cwd=root,
                          capture_output=True, text=True, timeout=60)
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["jax"] is False
    assert out["enabled"] == {"dl.attn_pallas": None,
                              "dl.retention_pallas": None,
                              "embedding.sgns_pallas": None,
                              "tree.pallas_hist": True}


def test_interpret_mode_is_asked_for_not_inferred(monkeypatch):
    from alink_tpu.native.kernels import interpret_mode

    assert interpret_mode() is True        # root conftest
    monkeypatch.delenv("ALINK_PALLAS_INTERPRET")
    assert interpret_mode() is False       # cpu backend, and still False


@pytest.mark.parametrize("value,expect", [
    ("0", False), ("off", False), ("false", False), ("no", False),
    ("OFF", False), (" 0 ", False),
    ("1", True), ("on", True), ("yes", True), ("anything", True),
])
def test_shared_gate_parses_all_three_knobs_identically(
        monkeypatch, value, expect):
    """One parser for every kernel knob: pallas_hist's historical
    convention (falsey spellings off, any other non-blank on) now comes
    from the registry for every ``use_*()`` gate."""
    from alink_tpu.dl.attn_pallas import use_attn_pallas
    from alink_tpu.dl.retention_pallas import use_retention_pallas
    from alink_tpu.embedding.sgns_pallas import use_sgns_pallas
    from alink_tpu.tree.pallas_hist import use_pallas_hist

    for knob, fn in (("ALINK_GBDT_PALLAS", use_pallas_hist),
                     ("ALINK_SGNS_PALLAS", use_sgns_pallas),
                     ("ALINK_ATTN_PALLAS", use_attn_pallas),
                     ("ALINK_RETENTION_PALLAS", use_retention_pallas)):
        monkeypatch.setenv(knob, value)
        assert fn() is expect, (knob, value)
        monkeypatch.delenv(knob)
        # blank = unset = backend default (off on the CPU container)
        monkeypatch.setenv(knob, "")
        assert fn() is False, (knob, "blank")


# ---------------------------------------------------------------------------
# fused SGNS block gradients
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("B,negs,D", [(13, 5, 100), (8, 1, 128), (32, 7, 64)])
def test_sgns_kernel_matches_block_grads(B, negs, D):
    # atol=1e-5 (not bit-equality): grad_v accumulates sequentially over
    # negatives inside the kernel (g_pos·u_pos + g_0·u_0 + ...) where the
    # XLA path reduces (g_neg * u_neg).sum(1) in XLA's own order — both
    # deterministic, different fp32 summation orders.
    import jax.numpy as jnp

    from alink_tpu.embedding.sgns_pallas import sgns_block_grads
    from alink_tpu.embedding.skipgram import _block_grads

    rng = np.random.default_rng(0)
    v = jnp.asarray(rng.normal(size=(B, D)), jnp.float32)
    u_pos = jnp.asarray(rng.normal(size=(B, D)), jnp.float32)
    u_neg = jnp.asarray(rng.normal(size=(B, negs, D)), jnp.float32)
    gv_ref, gu_ref = _block_grads(v, u_pos, u_neg, D)
    gv, gu = sgns_block_grads(v, u_pos, u_neg, interpret=True)
    assert gv.shape == (B, D) and gu.shape == ((negs + 1) * B, D)
    np.testing.assert_allclose(np.asarray(gv), np.asarray(gv_ref), atol=1e-5)
    np.testing.assert_allclose(np.asarray(gu), np.asarray(gu_ref), atol=1e-5)


def _sgns_fixture(seed=0):
    from alink_tpu.embedding import SkipGramConfig, build_vocab, make_pairs

    rng = np.random.default_rng(seed)
    docs = [[f"w{rng.integers(0, 25)}" for _ in range(10)]
            for _ in range(40)]
    vocab, counts = build_vocab(docs)
    cfg = SkipGramConfig(dim=6, window=2, negatives=2, epochs=2,
                         batch_size=8, seed=7)
    pairs = make_pairs(docs, vocab, counts, cfg.window, 0.0, cfg.seed)
    return pairs, vocab, counts, cfg


def test_sgns_sharded_knob_parity_and_off_identity(monkeypatch):
    """Op-level contract: knob-off ≡ unset (byte-identical — the XLA path
    is untouched), knob-on within the pinned tolerance; the two programs
    coexist in the ProgramCache (the ``fused`` static is part of the key),
    so toggling re-selects without retracing."""
    from alink_tpu.common.jitcache import programs
    from alink_tpu.embedding import train_skipgram_sharded

    pairs, vocab, counts, cfg = _sgns_fixture()

    monkeypatch.setenv("ALINK_SGNS_PALLAS", "0")
    off = train_skipgram_sharded(pairs, len(vocab), counts, cfg).to_numpy()
    monkeypatch.delenv("ALINK_SGNS_PALLAS")
    unset = train_skipgram_sharded(pairs, len(vocab), counts, cfg).to_numpy()
    np.testing.assert_array_equal(off, unset)   # CPU default = off

    monkeypatch.setenv("ALINK_SGNS_PALLAS", "1")
    on = train_skipgram_sharded(pairs, len(vocab), counts, cfg).to_numpy()
    # 2 epochs of fused steps vs XLA steps: per-step atol 1e-5 compounds
    # through the table updates, so pin a slightly looser op-level bound
    np.testing.assert_allclose(on, off, atol=5e-5)

    keys = {p.key for p in programs("embedding.sgns_sharded")}
    assert len(keys) >= 2   # fused and unfused programs coexist

    # toggling BACK must be a pure cache re-selection: no new traces
    monkeypatch.setenv("ALINK_SGNS_PALLAS", "0")
    t0 = metrics.counter("jit.trace")
    again = train_skipgram_sharded(pairs, len(vocab), counts, cfg).to_numpy()
    assert metrics.counter("jit.trace") == t0
    np.testing.assert_array_equal(again, off)


# ---------------------------------------------------------------------------
# flash attention block update
# ---------------------------------------------------------------------------


def test_flash_block_update_matches_online_softmax():
    # same pinned-tolerance rationale as SGNS: the kernel reduces row-max /
    # p.sum / matmuls per (b, h) tile, XLA over the whole 4D block
    import jax.numpy as jnp

    from alink_tpu.dl.attention import _NEG_INF
    from alink_tpu.dl.attn_pallas import (_xla_block_update,
                                          flash_block_update)

    rng = np.random.default_rng(1)
    B, H, Q, D, K = 2, 3, 5, 7, 11
    q = jnp.asarray(rng.normal(size=(B, H, Q, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, H, K, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, H, K, D)), jnp.float32)
    kvalid = jnp.asarray(rng.integers(0, 2, size=(B, K)), jnp.int32)
    kvalid = kvalid.at[0].set(0)       # one batch fully masked: the
    #                                    exp(max(m−m_new, −1e30)) guard
    ok = jnp.asarray(rng.integers(0, 2, size=(Q, K)), jnp.int32)
    o0 = jnp.asarray(rng.normal(size=(B, H, Q, D)), jnp.float32)
    m0 = jnp.full((B, H, Q), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, H, Q), jnp.float32)
    scale = float(D) ** -0.5

    # the XLA form built on _online_softmax_update — also the function whose
    # VJP is the kernel's backward pass
    o_ref, m_ref, l_ref = _xla_block_update(q, k, v, kvalid, ok, o0, m0, l0,
                                            scale)

    o, m, l = flash_block_update(q, k, v, kvalid, ok, o0, m0, l0,
                                 scale=scale, interpret=True)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref), atol=1e-5)
    np.testing.assert_allclose(np.asarray(m), np.asarray(m_ref), atol=1e-5)
    np.testing.assert_allclose(np.asarray(l), np.asarray(l_ref), atol=1e-5)
    assert not np.isnan(np.asarray(o)).any()


@pytest.mark.parametrize("causal,with_mask", [(False, False), (False, True),
                                              (True, False), (True, True)])
def test_blockwise_attention_knob_parity(monkeypatch, causal, with_mask):
    import jax.numpy as jnp

    from alink_tpu.dl.attention import blockwise_attention, full_attention

    rng = np.random.default_rng(2)
    b, s, h, d = 4, 32, 2, 8
    q = jnp.asarray(rng.normal(size=(b, s, h, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, s, h, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, s, h, d)), jnp.float32)
    mask = jnp.asarray(rng.integers(0, 2, size=(b, s)), jnp.int32) \
        if with_mask else None

    monkeypatch.setenv("ALINK_ATTN_PALLAS", "0")
    off = blockwise_attention(q, k, v, mask, block_size=8, causal=causal)
    monkeypatch.setenv("ALINK_ATTN_PALLAS", "1")
    on = blockwise_attention(q, k, v, mask, block_size=8, causal=causal)
    np.testing.assert_allclose(np.asarray(on), np.asarray(off), atol=1e-5)
    full = full_attention(q, k, v, mask, causal=causal)
    np.testing.assert_allclose(np.asarray(on), np.asarray(full), atol=2e-5)


@pytest.mark.parametrize("causal,with_mask", [(False, True), (True, False)])
def test_ring_attention_knob_parity(monkeypatch, causal, with_mask):
    import jax.numpy as jnp

    from alink_tpu.dl.attention import full_attention, ring_attention
    from alink_tpu.parallel.mesh import AXIS_DATA, AXIS_SEQ, make_mesh

    rng = np.random.default_rng(3)
    b, s, h, d = 4, 32, 2, 8
    q = jnp.asarray(rng.normal(size=(b, s, h, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, s, h, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, s, h, d)), jnp.float32)
    mask = jnp.asarray(rng.integers(0, 2, size=(b, s)), jnp.int32) \
        if with_mask else None
    mesh = make_mesh({AXIS_DATA: 2, AXIS_SEQ: 4})

    monkeypatch.setenv("ALINK_ATTN_PALLAS", "0")
    off = ring_attention(q, k, v, mask, mesh=mesh, causal=causal)
    monkeypatch.setenv("ALINK_ATTN_PALLAS", "1")
    on = ring_attention(q, k, v, mask, mesh=mesh, causal=causal)
    np.testing.assert_allclose(np.asarray(on), np.asarray(off), atol=1e-5)
    full = full_attention(q, k, v, mask, causal=causal)
    np.testing.assert_allclose(np.asarray(on), np.asarray(full), atol=2e-5)


def test_blockwise_and_ring_attention_differentiate_through_the_kernel(
        monkeypatch):
    """With the knob where a one-chip TPU process defaults it (on), a
    training step must be able to take a gradient through the flash update:
    its backward is the VJP of the XLA update it is pinned against."""
    import jax
    import jax.numpy as jnp

    from alink_tpu.dl.attention import (blockwise_attention, full_attention,
                                        ring_attention)
    from alink_tpu.parallel.mesh import AXIS_DATA, AXIS_SEQ, make_mesh

    rng = np.random.default_rng(4)
    b, s, h, d = 4, 32, 2, 8
    q, k, v = (jnp.asarray(rng.normal(size=(b, s, h, d)), jnp.float32)
               for _ in range(3))
    mask = jnp.asarray(rng.integers(0, 2, size=(b, s)), jnp.int32)
    mask = mask.at[:, 0].set(1)
    mesh = make_mesh({AXIS_DATA: 2, AXIS_SEQ: 4})

    def grads(fn):
        return jax.grad(lambda q, k, v: (fn(q, k, v) ** 2).sum(),
                        argnums=(0, 1, 2))(q, k, v)

    monkeypatch.setenv("ALINK_ATTN_PALLAS", "1")
    ref = grads(lambda q, k, v: full_attention(q, k, v, mask))
    for fn in (lambda q, k, v: blockwise_attention(q, k, v, mask,
                                                   block_size=8),
               lambda q, k, v: ring_attention(q, k, v, mask, mesh=mesh)):
        for g, g_ref in zip(grads(fn), ref):
            np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref),
                                       atol=2e-5)


def test_attention_kernel_defaults_off_where_gspmd_would_partition_it(
        monkeypatch):
    """jax cannot partition a Mosaic kernel, and the attention call sites
    are not inside a shard_map manual over every axis: with no knob set
    the kernel is on only in a one-device TPU process."""
    import jax

    from alink_tpu.dl.attn_pallas import use_attn_pallas
    from alink_tpu.tree.pallas_hist import use_pallas_hist

    monkeypatch.delenv("ALINK_ATTN_PALLAS", raising=False)
    monkeypatch.delenv("ALINK_GBDT_PALLAS", raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert jax.device_count() == 8
    assert use_attn_pallas() is False
    assert use_pallas_hist() is True      # full-manual shard_map: any count
    monkeypatch.setattr(jax, "device_count", lambda: 1)
    assert use_attn_pallas() is True
    monkeypatch.setenv("ALINK_ATTN_PALLAS", "0")
    assert use_attn_pallas() is False


def test_every_kernel_lowers_for_the_tpu_platform(monkeypatch):
    """As far as a CPU can check Mosaic: trace each kernel with
    ``interpret=False`` and lower it for the ``tpu`` platform, which runs the
    BlockSpec tiling rules and the jaxpr->Mosaic lowering (the Mosaic
    compiler proper only runs on the chip — chip_smoke.py). Shapes are the
    callers': 12 heads x 64 with K/V block 128; a retention head 128 wide;
    dim 128 with 5 negatives; a depth-6, 64-bin histogram."""
    import jax
    import jax.numpy as jnp

    from alink_tpu.dl.attention import (blockwise_attention, packed_attention,
                                        ring_attention)
    from alink_tpu.dl.retention_pallas import chunk_through_state
    from alink_tpu.embedding.sgns_pallas import sgns_block_grads
    from alink_tpu.parallel.mesh import AXIS_SEQ, make_mesh
    from alink_tpu.tree.pallas_hist import pallas_histogram

    monkeypatch.delenv("ALINK_PALLAS_INTERPRET")
    monkeypatch.setenv("ALINK_ATTN_PALLAS", "1")

    def lowers(f, *args):
        text = jax.jit(f).trace(*args).lower(
            lowering_platforms=("tpu",)).as_text()
        assert "tpu_custom_call" in text

    q = jnp.zeros((2, 256, 12, 64), jnp.bfloat16)
    mask = jnp.ones((2, 256), jnp.int32)
    block = lambda q, k, v, m: blockwise_attention(q, k, v, m,
                                                   block_size=128)
    lowers(block, q, q, q, mask)
    lowers(jax.grad(lambda *a: block(*a).astype(jnp.float32).sum()),
           q, q, q, mask)
    # under shard_map(check_vma=True) the kernel's outputs carry the vma of
    # its inputs; manual over every axis, as Mosaic requires
    mesh = make_mesh({AXIS_SEQ: 4}, devices=jax.devices()[:4])
    lowers(lambda q, k, v, m: ring_attention(q, k, v, m, mesh=mesh),
           q, q, q, mask)
    # the fused core of the default attention, from the packed projection
    # (tests/test_attn_fused.py compiles it for a described v5e besides)
    qkv = jnp.zeros((2, 256, 3, 12 * 64), jnp.bfloat16)
    packed = lambda x, m: packed_attention(x, m, num_heads=12)
    lowers(packed, qkv, mask)
    lowers(jax.grad(lambda *a: packed(*a).astype(jnp.float32).sum()),
           qkv, mask)

    # a prompt chunk through the retention state, two query heads a
    # key/value head (tests/test_attn_fused.py compiles it at the cell's size)
    head = jnp.zeros((2, 2, 16, 128), jnp.bfloat16)
    lowers(lambda *a: chunk_through_state(*a, dtype=jnp.bfloat16),
           jnp.stack([head, head], axis=2), head, head,
           jnp.zeros((2, 2, 16), jnp.float32),
           jnp.zeros((2, 2, 8256, 128), jnp.float32),
           jnp.zeros((2, 2, 8256), jnp.float32))

    v = jnp.zeros((256, 128), jnp.float32)
    lowers(sgns_block_grads, v, v, jnp.zeros((256, 5, 128), jnp.float32))
    lowers(lambda i, w: pallas_histogram(i, w, num_segments=32 * 64),
           jnp.zeros((4096, 20), jnp.int32), jnp.zeros((4096,), jnp.float32))


# ---------------------------------------------------------------------------
# candidates table + zero-retrace pin + trace artifact
# ---------------------------------------------------------------------------


def test_kernel_candidates_ranking_and_registry_join(monkeypatch):
    import jax
    import jax.numpy as jnp

    from alink_tpu.common.jitcache import cached_jit
    from alink_tpu.common.profiling import (clear_profile_registry,
                                            kernel_candidates,
                                            profile_summary)

    monkeypatch.setenv("ALINK_PROFILING", "on")
    clear_profile_registry()

    def build(kind):
        def f(x):
            return jnp.tanh(x @ x.T).sum() if kind == "mm" else (x * 2).sum()

        return jax.jit(f)

    mm = cached_jit("tree.level", build, "mm")      # covered by the registry
    add = cached_jit("demo.elementwise", build, "add")
    x = jnp.asarray(np.random.default_rng(0).normal(size=(128, 128)),
                    jnp.float32)
    for _ in range(3):
        jax.block_until_ready(mm(x))
        jax.block_until_ready(add(x))

    cands = kernel_candidates()
    by_kid = {c["kernel"]: c for c in cands}
    assert {"tree.level", "demo.elementwise"} <= set(by_kid)
    for c in cands:
        assert set(c) == {"kernel", "programs", "calls", "exec_total_s",
                          "exec_mean_s", "bound", "efficiency", "lost_s",
                          "custom_kernel", "knob", "kernel_enabled"}
    # registry cross-reference
    assert by_kid["tree.level"]["custom_kernel"] == "tree.pallas_hist"
    assert by_kid["tree.level"]["knob"] == "ALINK_GBDT_PALLAS"
    assert isinstance(by_kid["tree.level"]["kernel_enabled"], bool)
    assert by_kid["demo.elementwise"]["custom_kernel"] is None
    assert by_kid["demo.elementwise"]["knob"] is None
    # ranking: measured-efficiency rows first, by lost seconds descending;
    # unmeasured rows after, by wall
    measured = [c for c in cands if c["lost_s"] is not None]
    unmeasured = cands[len(measured):]
    assert all(c["lost_s"] is None for c in unmeasured)
    assert measured == sorted(measured, key=lambda c: -c["lost_s"])

    summ = profile_summary(top=4)
    assert summ["candidates"] == kernel_candidates(top=4)
    clear_profile_registry()


def test_knob_toggle_never_invalidates_unrelated_programs(monkeypatch):
    """The zero-retrace pin: kernel knobs select between coexisting cached
    programs — flipping one must not invalidate or retrace anything,
    related or not."""
    import jax
    import jax.numpy as jnp

    from alink_tpu.common.jitcache import cached_jit, programs

    def build():
        return jax.jit(lambda x: (x * 3).sum())

    p = cached_jit("demo.unrelated", build)
    x = jnp.arange(8, dtype=jnp.float32)
    jax.block_until_ready(p(x))   # warm: traced + compiled

    t0 = metrics.counter("jit.trace")
    h0 = metrics.counter("jit.program_hit")
    for knob in ("ALINK_SGNS_PALLAS", "ALINK_ATTN_PALLAS",
                 "ALINK_GBDT_PALLAS"):
        for value in ("1", "0"):
            monkeypatch.setenv(knob, value)
            p2 = cached_jit("demo.unrelated", build)
            jax.block_until_ready(p2(x))
    assert metrics.counter("jit.trace") == t0          # zero retraces
    assert metrics.counter("jit.program_hit") >= h0 + 6
    assert len(programs("demo.unrelated")) == 1


def test_chrome_trace_artifact(tmp_path):
    from alink_tpu.common.tracing import (chrome_trace, trace_span,
                                          write_chrome_trace)

    with trace_span("kernel_artifact_probe", phase="test") as sp:
        sp.phases["compute_s"] = 0.001

    blob = chrome_trace()
    events = blob["traceEvents"]
    assert events[0] == {"ph": "M", "pid": 1, "tid": 0,
                         "name": "process_name",
                         "args": {"name": "alink_tpu"}}
    mine = [e for e in events
            if e["ph"] == "X" and e["name"] == "kernel_artifact_probe"]
    assert mine, "span missing from the chrome trace"
    ev = mine[-1]
    assert ev["ts"] > 0 and ev["dur"] >= 0
    assert ev["args"]["outcome"] == "ok"
    assert ev["args"]["phases"]["compute_s"] == pytest.approx(0.001)
    # its thread has a thread_name metadata event with the same tid
    tids = {e["tid"]: e["args"]["name"] for e in events
            if e["ph"] == "M" and e["name"] == "thread_name"}
    assert ev["tid"] in tids

    path = tmp_path / "trace.json"
    n = write_chrome_trace(str(path))
    assert n >= 1
    loaded = json.loads(path.read_text())
    assert loaded["traceEvents"] and loaded["displayTimeUnit"] == "ms"
