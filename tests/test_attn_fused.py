"""The fused attention core (dl/attn_pallas.py ``fused_attention``) against
``full_attention``, which stays its reference: values and gradients under the
Pallas interpreter, the rule that selects it, an encoder with the path on and
off, what the traced program holds, and the kernels compiled at BERT-base's
width for a described v5e (no chip is needed or taken for that). Then the
fused causal core (``causal_attention``, the decoder's training step) the same
way: against materialised scores and against ``dl/mla``'s block loops, its
rule, its counters, and its kernels compiled at the Moonlight cell's size."""

import numpy as np
import pytest


def _reference(qkv, mask, h):
    from alink_tpu.dl.attention import full_attention

    b, s, _, hd = qkv.shape
    q, k, v = (qkv[:, :, i].reshape(b, s, h, hd // h) for i in range(3))
    return full_attention(q, k, v, mask).reshape(b, s, hd)


def _inputs(rows, seq, h, d, dtype, seed=0):
    """A packed projection, a key-padding mask whose rows end mid-block
    (one of them fully masked where there are several), a cotangent."""
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    qkv = jnp.asarray(rng.normal(size=(rows, seq, 3, h * d)), dtype)
    lens = rng.integers(1, seq, size=rows)
    lens[0] = seq - 58                      # ends inside the last 128 keys
    mask = (np.arange(seq)[None] < lens[:, None]).astype(np.int32)
    if rows > 1:
        mask[-1] = 0                        # every key of this row masked
    w = jnp.asarray(rng.normal(size=(rows, seq, h * d)), jnp.float32)
    return qkv, jnp.asarray(mask), w


# float32: the two differ by summation order only; bfloat16: by one rounding
# of P (normalised before the cast in XLA, after PV in the kernel) and by the
# scores XLA forms in bfloat16, against values of order 1
_TOL = {"float32": 2e-5, "bfloat16": 4e-2}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("rows,seq", [(1, 128), (3, 128), (1, 256), (3, 256),
                                      (1, 512), (3, 512)])
def test_fused_attention_matches_full_attention(dtype, rows, seq):
    import jax
    import jax.numpy as jnp

    from alink_tpu.dl.attn_pallas import fused_attention

    h, d = 2, 64
    qkv, mask, w = _inputs(rows, seq, h, d, jnp.dtype(dtype), seed=seq + rows)
    fused = lambda x: fused_attention(x, mask, num_heads=h, interpret=True)
    plain = lambda x: _reference(x, mask, h)
    f32 = lambda a: np.asarray(a.astype(jnp.float32))

    out, ref = fused(qkv), plain(qkv)
    assert out.shape == (rows, seq, h * d) and out.dtype == qkv.dtype
    assert not np.isnan(f32(out)).any()
    np.testing.assert_allclose(f32(out), f32(ref), atol=_TOL[dtype])

    grad = lambda f: jax.grad(
        lambda x: (f(x).astype(jnp.float32) * w).sum())(qkv)
    got, want = f32(grad(fused)), f32(grad(plain))
    assert not np.isnan(got).any()
    for i, name in enumerate("qkv"):
        np.testing.assert_allclose(got[:, :, i], want[:, :, i],
                                   atol=_TOL[dtype], err_msg="d" + name)
    if rows > 1:
        # full_attention's `where` lets nothing through to q and k of a row
        # whose keys are all masked; v still gets the even weights
        assert not got[-1, :, :2].any() and not want[-1, :, :2].any()
        assert np.abs(got[-1, :, 2]).max() > 0


def test_fused_attention_head_dimension_128_and_no_mask():
    import jax.numpy as jnp

    from alink_tpu.dl.attn_pallas import fused_attention

    qkv, _, _ = _inputs(2, 128, 3, 128, jnp.float32, seed=5)
    out = fused_attention(qkv, None, num_heads=3, interpret=True)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(_reference(qkv, None, 3)), atol=2e-5)


@pytest.mark.parametrize("case,seq,heads,d,causal,expect", [
    ("taken", 512, 12, 64, False, True),
    ("taken_at_128_wide_heads", 512, 6, 128, False, True),
    ("causal", 512, 12, 64, True, False),
    ("under_the_threshold", 64, 12, 64, False, False),
    ("not_lane_aligned", 500, 12, 64, False, False),
    ("over_one_cells_vmem", 2048, 12, 64, False, False),
    ("head_dimension_32", 512, 12, 32, False, False),
    ("odd_heads_of_64", 512, 3, 64, False, False),
    ("knob_off", 512, 12, 64, False, False),
    ("more_than_one_device", 512, 12, 64, False, False),
    ("cpu_backend", 512, 12, 64, False, False),
])
def test_fused_attention_selection(monkeypatch, case, seq, heads, d, causal,
                                   expect):
    """Decided from the call's shapes and the registry's gate: unset, the
    knob is on exactly in a one-device TPU process."""
    import jax

    from alink_tpu.dl.attn_pallas import use_fused_attention

    monkeypatch.delenv("ALINK_ATTN_PALLAS", raising=False)
    monkeypatch.setattr(jax, "default_backend",
                        lambda: "cpu" if case == "cpu_backend" else "tpu")
    monkeypatch.setattr(jax, "device_count",
                        lambda: 8 if case == "more_than_one_device" else 1)
    if case == "knob_off":
        monkeypatch.setenv("ALINK_ATTN_PALLAS", "0")
    assert use_fused_attention(seq, heads, d, causal=causal) is expect


def test_threshold_is_a_lane_aligned_length_the_benchmark_cells_pass():
    from alink_tpu.dl import attn_pallas

    assert attn_pallas._FUSED_MIN_SEQ % 128 == 0
    assert attn_pallas._FUSED_MIN_SEQ <= 512 <= attn_pallas._FUSED_MAX_SEQ


def _tiny_encoder(seq):
    import jax
    import jax.numpy as jnp

    from alink_tpu.dl.modules import BertConfig, TransformerEncoder

    cfg = BertConfig.tiny(hidden_size=128, num_heads=2, intermediate_size=256,
                          max_position=seq, dtype=jnp.float32)
    model = TransformerEncoder(cfg)
    rng = np.random.default_rng(7)
    ids = jnp.asarray(rng.integers(0, cfg.vocab_size, (3, seq)), jnp.int32)
    lens = np.array([seq, seq - 58, 17])
    mask = jnp.asarray((np.arange(seq)[None] < lens[:, None]).astype(np.int32))
    return cfg, model, ids, mask, jax.random.PRNGKey(0)


def test_encoder_agrees_with_the_path_on_and_off(monkeypatch):
    """The same parameters through ``TransformerEncoder`` with the default
    attention on the fused core and on XLA: logits and every parameter's
    gradient; and each traced layer counted down its path."""
    import jax

    from alink_tpu.common.metrics import metrics
    from alink_tpu.dl import attn_pallas

    cfg, model, ids, mask, key = _tiny_encoder(attn_pallas._FUSED_MIN_SEQ)
    monkeypatch.setenv("ALINK_ATTN_PALLAS", "0")
    params = model.init(key, ids, mask)["params"]

    def run():
        before = {n: metrics.counter("attention." + n)
                  for n in ("fused_traces", "xla_traces")}
        logits = model.apply({"params": params}, ids, mask)
        grads = jax.grad(lambda p: (model.apply(
            {"params": p}, ids, mask) ** 2).sum())(params)
        grew = {n: metrics.counter("attention." + n) - v
                for n, v in before.items()}
        return logits, grads, grew

    off_logits, off_grads, off_grew = run()
    monkeypatch.setenv("ALINK_ATTN_PALLAS", "1")
    on_logits, on_grads, on_grew = run()
    # two traces (apply, grad) of two layers each, all down one path
    assert off_grew == {"fused_traces": 0, "xla_traces": 2 * cfg.num_layers}
    assert on_grew == {"fused_traces": 2 * cfg.num_layers, "xla_traces": 0}
    np.testing.assert_allclose(np.asarray(on_logits), np.asarray(off_logits),
                               atol=1e-5)
    flat_on = jax.tree_util.tree_leaves_with_path(on_grads)
    flat_off = jax.tree_util.tree_leaves(off_grads)
    assert len(flat_on) == len(flat_off) > 10
    for (path, g), g_off in zip(flat_on, flat_off):
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(g_off), atol=1e-4,
            err_msg=jax.tree_util.keystr(path))


def _all_eqns(jaxpr, into_kernels=False):
    """Every equation of a jaxpr and of the jaxprs inside it; a kernel's own
    body is left out unless asked for."""
    from jax.extend import core as jcore

    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name == "pallas_call" and not into_kernels:
            continue
        for p in eqn.params.values():
            for sub in (p if isinstance(p, (list, tuple)) else [p]):
                if isinstance(sub, jcore.ClosedJaxpr):
                    yield from _all_eqns(sub.jaxpr, into_kernels)
                elif isinstance(sub, jcore.Jaxpr):
                    yield from _all_eqns(sub, into_kernels)


def _shapes_in(jaxpr, into_kernels=False):
    return {tuple(v.aval.shape)
            for eqn in _all_eqns(jaxpr, into_kernels)
            for v in list(eqn.invars) + list(eqn.outvars)
            if hasattr(getattr(v, "aval", None), "shape")}


def test_fused_path_traces_no_score_shaped_value(monkeypatch):
    """The encoder's forward-and-backward program holds a (B, H, S, S)
    value on the XLA path and none on the fused one, where scores exist one
    head at a time, (S, S), inside the kernels."""
    import jax

    from alink_tpu.dl import attn_pallas

    seq = attn_pallas._FUSED_MIN_SEQ
    cfg, model, ids, mask, key = _tiny_encoder(seq)
    monkeypatch.setenv("ALINK_ATTN_PALLAS", "0")
    params = model.init(key, ids, mask)["params"]
    score = (ids.shape[0], cfg.num_heads, seq, seq)
    loss = lambda p: (model.apply({"params": p}, ids, mask) ** 2).sum()

    assert score in _shapes_in(jax.make_jaxpr(jax.grad(loss))(params).jaxpr)
    monkeypatch.setenv("ALINK_ATTN_PALLAS", "1")
    fused = jax.make_jaxpr(jax.grad(loss))(params).jaxpr
    assert score not in _shapes_in(fused, into_kernels=True)
    assert (seq, seq) in _shapes_in(fused, into_kernels=True)
    calls = [e.params["name"] for e in _all_eqns(fused)
             if e.primitive.name == "pallas_call"]
    assert sorted(set(calls)) == ["attn_pallas_bwd", "attn_pallas_fwd"]
    assert len(calls) == 2 * cfg.num_layers


def test_blockwise_and_ring_settings_keep_their_paths(monkeypatch):
    """``attention_block_size`` and ``use_ring_attention`` do not pass
    through the selection: no layer of theirs is counted down either path."""
    import jax
    import jax.numpy as jnp

    from alink_tpu.common.metrics import metrics
    from alink_tpu.dl.modules import BertConfig, TransformerEncoder

    monkeypatch.setenv("ALINK_ATTN_PALLAS", "1")
    cfg = BertConfig.tiny(hidden_size=128, num_heads=2, intermediate_size=256,
                          max_position=256, attention_block_size=128,
                          dtype=jnp.float32)
    model = TransformerEncoder(cfg)
    ids = jnp.ones((2, 256), jnp.int32)
    before = metrics.counters("attention.")
    jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), ids))
    assert metrics.counters("attention.") == before


# ---------------------------------------------------------------------------
# the causal core in blocks (dl/mla.causal_core on the chip)
# ---------------------------------------------------------------------------


def _causal_inputs(cells, seq, d, dv, dtype, seed):
    """Heads-first q, k, v and a cotangent, every (row, head) cell its own
    draw."""
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    draw = lambda w, dt: jnp.asarray(rng.normal(size=(*cells, seq, w)), dt)
    return draw(d, dtype), draw(d, dtype), draw(dv, dtype), \
        draw(dv, jnp.float32)


def _materialised_causal(q, k, v, scale):
    import jax
    import jax.numpy as jnp

    s = jnp.einsum("bhtd,bhsd->bhts", q, k) * scale
    t = jnp.arange(q.shape[2])
    p = jax.nn.softmax(jnp.where(t[None, :] <= t[:, None], s, -jnp.inf), -1)
    return jnp.einsum("bhts,bhsd->bhtd", p, v)


@pytest.fixture
def toy_causal_blocks(monkeypatch):
    """The kernels' block at a toy size; the jitted core is built anew for
    it and again for what follows."""
    from alink_tpu.dl import attn_pallas

    def sized(block):
        monkeypatch.setattr(attn_pallas, "_CAUSAL_BLOCK", block)
        attn_pallas._build_causal.cache_clear()

    yield sized
    attn_pallas._build_causal.cache_clear()


_CAUSAL_CASES = [
    # cells (rows, heads), length, q/k width, v width, block
    ("several_blocks", (2, 3), 48, 48, 32, 16),
    ("one_block", (2, 3), 16, 48, 32, 16),
    ("one_cell_many_blocks", (1, 1), 128, 48, 32, 16),
    ("the_cells_widths", (1, 2), 64, 192, 128, 32),
]


@pytest.mark.parametrize("case,cells,seq,d,dv,block", _CAUSAL_CASES)
def test_causal_kernels_match_materialised_scores(toy_causal_blocks, case,
                                                  cells, seq, d, dv, block):
    """Forward and the three gradients in float32, under the interpreter,
    at unequal widths for q/k and v: the diagonal inside a block, blocks
    wholly under it, none above it."""
    import jax
    import jax.numpy as jnp

    from alink_tpu.dl.attn_pallas import causal_attention

    toy_causal_blocks(block)
    q, k, v, g = _causal_inputs(cells, seq, d, dv, jnp.float32, seed=seq + d)
    with jax.default_matmul_precision("highest"):
        out, pull = jax.vjp(lambda *a: causal_attention(
            *a, scale=0.2, interpret=True), q, k, v)
        want, pull_want = jax.vjp(
            lambda *a: _materialised_causal(*a, 0.2), q, k, v)
        assert out.shape == (*cells, seq, dv) and out.dtype == jnp.float32
        np.testing.assert_allclose(out, want, atol=2e-5)
        for name, got, ref in zip(("dq", "dk", "dv"), pull(g), pull_want(g)):
            np.testing.assert_allclose(got, ref, atol=5e-5, err_msg=name)


@pytest.mark.parametrize("case,cells,seq,d,dv,block", _CAUSAL_CASES)
def test_causal_kernels_match_the_block_loops_in_bfloat16(
        toy_causal_blocks, case, cells, seq, d, dv, block):
    """The same precisions as ``dl/mla._causal`` (bfloat16 products with
    float32 sums, float32 softmax, P and dS cast to bfloat16), so the two
    differ by summation order: the ``dl.attn_pallas`` contract's 4e-2 at
    values of order 1."""
    import jax
    import jax.numpy as jnp

    from alink_tpu.dl import mla
    from alink_tpu.dl.attn_pallas import causal_attention

    toy_causal_blocks(block)
    q, k, v, g = _causal_inputs(cells, seq, d, dv, jnp.bfloat16, seed=seq)
    scale = d ** -0.5
    out, pull = jax.vjp(lambda *a: causal_attention(
        *a, scale=scale, interpret=True), q, k, v)
    want, pull_want = jax.vjp(lambda *a: mla._causal(*a, scale, block),
                              q, k, v)
    f32 = lambda a: np.asarray(a.astype(jnp.float32))
    np.testing.assert_allclose(f32(out), f32(want), atol=_TOL["bfloat16"])
    for name, got, ref in zip(("dq", "dk", "dv"), pull(g), pull_want(g)):
        assert got.dtype == jnp.bfloat16
        np.testing.assert_allclose(f32(got), f32(ref), atol=_TOL["bfloat16"],
                                   err_msg=name)


@pytest.mark.parametrize("case,seq,d,dv,expect", [
    ("the_training_cell", 8192, 192, 128, True),
    ("one_block", 1024, 192, 128, True),
    ("equal_widths", 2048, 128, 128, True),
    ("not_a_multiple_of_the_block", 8192 + 512, 192, 128, False),
    ("shorter_than_a_block", 40, 24, 16, False),
    ("dq_of_a_row_and_head_over_vmem", 32768, 192, 128, False),
    ("values_not_lane_aligned", 8192, 192, 64, False),
    ("queries_a_third_of_a_lane_group", 8192, 160, 128, False),
    ("knob_off", 8192, 192, 128, False),
    ("two_devices", 8192, 192, 128, False),
    ("cpu_backend", 8192, 192, 128, False),
])
def test_causal_attention_selection(monkeypatch, case, seq, d, dv, expect):
    """Decided from the call's shapes and the registry's gate, as the
    encoder's rule is: a mesh and the CPU keep XLA's block loops."""
    import jax

    from alink_tpu.dl.attn_pallas import use_causal_attention

    monkeypatch.delenv("ALINK_ATTN_PALLAS", raising=False)
    monkeypatch.setattr(jax, "default_backend",
                        lambda: "cpu" if case == "cpu_backend" else "tpu")
    monkeypatch.setattr(jax, "device_count",
                        lambda: 2 if case == "two_devices" else 1)
    if case == "knob_off":
        monkeypatch.setenv("ALINK_ATTN_PALLAS", "0")
    assert use_causal_attention(seq, d, dv) is expect


@pytest.mark.parametrize("gate,seq,expect", [
    ("1", 48, {"causal_fused_traces": 2, "causal_block_traces": 0}),
    ("1", 40, {"causal_fused_traces": 0, "causal_block_traces": 2}),
    ("0", 48, {"causal_fused_traces": 0, "causal_block_traces": 2}),
])
def test_causal_core_takes_and_counts_its_program(monkeypatch,
                                                  toy_causal_blocks, gate,
                                                  seq, expect):
    """``dl/mla.causal_core`` down the kernels and down the loops: the same
    values and gradients, and each trace (forward, gradient) counted down the
    path it took, a length that is not a multiple of the kernels' block down
    the loops whatever the gate says."""
    import jax
    import jax.numpy as jnp

    from alink_tpu.common.metrics import metrics
    from alink_tpu.dl import mla

    toy_causal_blocks(16)
    monkeypatch.setattr(mla, "CAUSAL_BLOCK", 16)
    rng = np.random.default_rng(seq)
    q, k = (jnp.asarray(rng.normal(size=(2, seq, 2, 64)), jnp.float32)
            for _ in range(2))
    v, g = (jnp.asarray(rng.normal(size=(2, seq, 2, 128)), jnp.float32)
            for _ in range(2))
    core = lambda *a: mla.causal_core(*a, scale=0.125, dtype=jnp.float32)

    def run():
        before = {n: metrics.counter("attention." + n) for n in expect}
        with jax.default_matmul_precision("highest"):
            out = core(q, k, v)
            grads = jax.grad(lambda *a: (core(*a) * g).sum(),
                             argnums=(0, 1, 2))(q, k, v)
        return out, grads, {n: metrics.counter("attention." + n) - c
                            for n, c in before.items()}

    monkeypatch.setenv("ALINK_ATTN_PALLAS", "0")
    want, want_grads, _ = run()
    monkeypatch.setenv("ALINK_ATTN_PALLAS", gate)
    out, grads, grew = run()
    assert grew == expect
    assert out.shape == (2, seq, 2, 128) and out.dtype == jnp.float32
    np.testing.assert_allclose(out, want, atol=2e-5)
    for got, ref in zip(grads, want_grads):
        np.testing.assert_allclose(got, ref, atol=5e-5)


# ---------------------------------------------------------------------------
# compiled for the chip, without the chip
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def one_chip():
    """One device of a described v5e host: the TPU compiler is installed
    here and compiles for a chip that is not attached. Made in a fixture, so
    that only the worker this file goes to loads the TPU's library."""
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:       # no TPU compiler here: nothing to check
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("rows,seq,heads,d", [
    (32, 512, 12, 64),     # the fine-tune cell's step
    (1, 512, 12, 64),      # the served ladder's first rung
    (256, 512, 12, 64),    # and its last
    (8, 1024, 12, 64),     # the longest a cell's VMEM is asked to hold
    (8, 256, 6, 128),      # one head a lane group
])
def test_fused_kernels_compile_for_v5e_at_real_width(one_chip, rows, seq,
                                                     heads, d):
    """Mosaic proper: tiling, VMEM and the kernel's own lowering at the
    shapes the benchmark's cells run, forward and backward."""
    import jax
    import jax.numpy as jnp

    from alink_tpu.dl.attn_pallas import fused_attention

    qkv = jax.ShapeDtypeStruct((rows, seq, 3, heads * d), jnp.bfloat16,
                               sharding=one_chip)
    mask = jax.ShapeDtypeStruct((rows, seq), jnp.int32, sharding=one_chip)
    fwd = lambda x, m: fused_attention(x, m, num_heads=heads)
    both = jax.grad(lambda x, m: fwd(x, m).astype(jnp.float32).sum())
    for f, kernels in ((fwd, 1), (both, 2)):
        text = jax.jit(f).lower(qkv, mask).compile().as_text()
        assert text.count("custom_call_target=\"tpu_custom_call\"") == kernels
        assert "attn_pallas" in text


def test_no_copy_of_the_packed_projection_round_the_kernels(one_chip):
    """A layer's projection, core and output projection, forward and
    backward, compiled for the chip: the kernels read the projection's
    result and write its gradient in the layout the compiler gives them
    anyway, (B, 3, S, H*D), so no copy or transpose of a q/k/v-sized tensor
    stands between the products and the kernels."""
    import re

    import jax
    import jax.numpy as jnp

    from alink_tpu.dl.attn_pallas import fused_attention

    b, s, h, d = 32, 512, 12, 64
    bf16 = jnp.bfloat16

    def layer(x, w, bias, wo, mask):
        qkv = jax.lax.dot_general(x, w.astype(bf16),
                                  (((2,), (0,)), ((), ()))) + bias.astype(bf16)
        return jnp.dot(fused_attention(qkv, mask, num_heads=h),
                       wo.astype(bf16))

    shape = lambda dims, dt: jax.ShapeDtypeStruct(dims, dt, sharding=one_chip)
    args = (shape((b, s, h * d), bf16), shape((h * d, 3, h * d), jnp.float32),
            shape((3, h * d), jnp.float32),
            shape((h * d, h * d), jnp.float32), shape((b, s), jnp.int32))
    grad = jax.grad(lambda *a: layer(*a).astype(jnp.float32).sum(),
                    argnums=(0, 1, 2, 3))
    moved = re.compile(
        r"= bf16\[(32,3,512,768|32,512,3,768|32,512,2304)\]\S* "
        r"(copy|transpose)\(")
    for f in (layer, grad):
        text = jax.jit(f).lower(*args).compile().as_text()
        assert "attn_pallas" in text
        assert not moved.search(text), moved.search(text).group(0)


def test_causal_block_core_compiles_for_v5e_at_the_training_cells_size(
        one_chip, monkeypatch):
    """``dl/mla.causal_core`` (the decoder's training step, PR 34), forward
    and backward, at the Moonlight cell's shapes: 2 rows of 8,192 positions,
    16 heads, queries and keys 192 wide, values 128. It fits the chip, and no
    tensor of the compiled program has two dimensions of the sequence
    length: nothing score-shaped is written whole."""
    import re

    import jax
    import jax.numpy as jnp

    from alink_tpu.dl import mla

    # the bfloat16 products as the chip runs them, not the CPU's widened ones
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    b, t, h, dq, dv = 2, 8192, 16, 192, 128
    shape = lambda d: jax.ShapeDtypeStruct((b, t, h, d), jnp.float32,
                                           sharding=one_chip)
    core = lambda q, k, v: mla.causal_core(q, k, v, scale=dq ** -0.5,
                                           dtype=jnp.bfloat16)
    grad = jax.grad(lambda *a: core(*a).sum(), argnums=(0, 1, 2))
    for f in (core, grad):
        compiled = jax.jit(f).lower(shape(dq), shape(dq), shape(dv)).compile()
        assert not re.search(r"\[[\d,]*8192,[\d,]*8192[\d,]*\]",
                             compiled.as_text())
        assert compiled.memory_analysis().temp_size_in_bytes < 4 << 30


def test_causal_kernels_compile_for_v5e_at_the_training_cells_size(
        one_chip, monkeypatch):
    """``causal_attention``, forward and gradient, at the Moonlight cell's
    shapes (heads first: 2 rows, 16 heads, 8,192 positions, queries and keys
    192 wide, values 128) through Mosaic proper: the compiled programs hold
    the kernels by the names the trace reader knows, nothing with two
    dimensions of the length, no block of scores, and less scratch than the
    program of XLA's block loops."""
    import re

    import jax
    import jax.numpy as jnp

    from alink_tpu.dl import mla
    from alink_tpu.dl.attn_pallas import causal_attention

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")   # einsum_f32
    b, t, h, dq, dv = 2, 8192, 16, 192, 128
    # as the layer hands them over, (B, T, H*D) float32: XLA lays a 192-wide
    # entry parameter out its own way and would copy it for the kernel, the
    # layer's transposes write what the kernel reads
    shape = lambda d: jax.ShapeDtypeStruct((b, t, h * d), jnp.float32,
                                           sharding=one_chip)
    args = (shape(dq), shape(dq), shape(dv))
    scale = dq ** -0.5
    heads_first = lambda x: x.reshape(b, t, h, -1).astype(
        jnp.bfloat16).transpose(0, 2, 1, 3)

    def both(core):
        fwd = lambda *a: core(*map(heads_first, a)).transpose(
            0, 2, 1, 3).reshape(b, t, -1)
        return fwd, jax.grad(lambda *a: fwd(*a).sum(), argnums=(0, 1, 2))

    kernels = both(lambda *a: causal_attention(*a, scale=scale))
    loops = both(lambda *a: mla._causal(*a, scale, mla.CAUSAL_BLOCK))
    # a kernel writes dq, dk and dv whole in bfloat16 where XLA fuses the
    # loops' casts into the transposes back: that much more may stand
    room = (0, b * t * h * (2 * dq + dv) * 2)
    for f, loop, names, more in zip(kernels, loops, (
            ["mla_causal_fwd"], ["mla_causal_fwd", "mla_causal_bwd"]), room):
        compiled = jax.jit(f).lower(*args).compile()
        text = compiled.as_text()
        assert sorted(set(re.findall(r"mla_causal_\w+?(?=[./\"])", text))) \
            == sorted(names)
        assert text.count("custom_call_target=\"tpu_custom_call\"") \
            == len(names)
        assert not re.search(r"\[[\d,]*8192,[\d,]*8192[\d,]*\]", text)
        assert "[2,16,1024,1024]" not in text
        looped = jax.jit(loop).lower(*args).compile()
        assert "[2,16,1024,1024]" in looped.as_text()
        assert compiled.memory_analysis().temp_size_in_bytes \
            < looped.memory_analysis().temp_size_in_bytes + more


def test_retention_chunk_kernel_compiles_for_v5e_at_the_generators_size(
        one_chip, monkeypatch):
    """``retention_chunk`` down its kernel at the Brumby cell's shapes (16
    rows, a chunk of 256, 40 + 8 heads of 128, bfloat16 products, the state
    donated) through Mosaic proper: the compiled program holds the kernel by
    the name the trace reader knows, writes nothing as wide as phi, and
    updates the state in place (one copy of its 541 MB, aliased). Here, beside
    the other kernels of the main paths: one file loads the TPU's library."""
    import re

    import jax
    import jax.numpy as jnp

    from alink_tpu.dl.retention import phi_dim, retention_chunk

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")   # einsum_f32
    monkeypatch.setenv("ALINK_RETENTION_PALLAS", "1")
    monkeypatch.delenv("ALINK_PALLAS_INTERPRET")
    b, t, hq, hkv, d = 16, 256, 40, 8, 128
    p = phi_dim(d)
    shape = lambda *dims, dtype=jnp.float32: jax.ShapeDtypeStruct(
        dims, dtype, sharding=one_chip)
    chunk = lambda *a: retention_chunk(*a, eps=1e-6, dtype=jnp.bfloat16)
    compiled = jax.jit(chunk, donate_argnums=(5, 6)).lower(
        shape(b, t, hq, d), shape(b, t, hkv, d), shape(b, t, hkv, d),
        shape(b, t, hkv), shape(b, t, dtype=jnp.bool_),
        shape(b, hkv, p, d), shape(b, hkv, p)).compile()
    text = compiled.as_text()
    assert "retention_chunk_state" in text
    # phi of a chunk's queries or keys, flat or by distance
    assert not re.search(rf"\[[\d,]*\b{t},[\d,]*\b({p}|{p + d + 1}|65,{d})\b",
                         text)
    memory = compiled.memory_analysis()
    state = b * hkv * p * d * 4
    assert memory.alias_size_in_bytes >= state
    assert memory.temp_size_in_bytes < state


@pytest.mark.parametrize("rows,chunk", [(1024, 64), (16, 1)],
                         ids=["prompt_chunk", "decode_step"])
def test_gqa_kernel_compiles_for_v5e_at_the_mimo_cells_size(one_chip, rows,
                                                            chunk):
    """``gqa_attend`` at the MiMo cell's full layers (32 rows, 4 key/value
    heads of 16 queries, q/k 192 and v 128, a cache of 17,408 positions in
    blocks of 1,024) through Mosaic proper, for a prompt chunk and a decode
    step: the kernel takes the cache in its own layout, keys transposed,
    so nothing of the cache's size is copied round it."""
    import jax
    import jax.numpy as jnp

    from alink_tpu.dl.attn_pallas import gqa_attention

    b, h, d, dv, p = 32, 4, 192, 128, 17408
    shape = lambda *dims, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(
        dims, dtype, sharding=one_chip)
    compiled = jax.jit(lambda q, K, V, s, hi: gqa_attention(
        q, K, V, s, hi, chunk, scale=d ** -0.5, block=1024)).lower(
        shape(b, h, rows, d), shape(b, h, d, p), shape(b, h, p, dv),
        shape(b, dtype=jnp.int32), shape(b, dtype=jnp.int32)).compile()
    text = compiled.as_text()
    assert "gqa_attend" in text
    assert compiled.memory_analysis().temp_size_in_bytes < b * h * p * dv * 2


@pytest.mark.parametrize("rows,chunk", [(512, 64), (8, 1)],
                         ids=["prompt_chunk", "decode_step"])
def test_window_kernel_compiles_for_v5e_at_the_mimo_cells_size(one_chip, rows,
                                                               chunk):
    """``window_attend`` at the MiMo cell's window layers (32 rows, 8
    key/value heads of 8 queries, q/k 192 and v 128) through Mosaic proper:
    the ring's 128 positions and a chunk of 64 or one as operands of their
    own."""
    import jax
    import jax.numpy as jnp

    from alink_tpu.dl.attn_pallas import window_attention

    b, h, d, dv, w = 32, 8, 192, 128, 128
    shape = lambda *dims, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(
        dims, dtype, sharding=one_chip)
    compiled = jax.jit(lambda *a: window_attention(
        *a, scale=d ** -0.5, window=w)).lower(
        shape(b, h, rows, d), shape(b, h, d, w), shape(b, h, w, dv),
        shape(b, h, d, chunk), shape(b, h, chunk, dv),
        shape(b, rows, 1, dtype=jnp.int32), shape(b, 1, w, dtype=jnp.int32),
        shape(b, 1, chunk, dtype=jnp.int32),
        shape(h, rows, 1, dtype=jnp.float32)).compile()
    assert "window_attend" in compiled.as_text()


@pytest.mark.parametrize("rows,chunk", [(384, 64), (8, 1)],
                         ids=["prompt_chunk", "decode_step"])
def test_gqa_kernel_compiles_for_v5e_at_the_trinity_cells_size(one_chip, rows,
                                                               chunk):
    """``gqa_attend`` at the Trinity cell's full layer (16 rows, 8 key/value
    heads of 6 queries, q/k and v 128, a cache of 17,408 positions): a
    prompt chunk's 384 rows, and a decode step's 6 padded to a tile of 8."""
    import jax
    import jax.numpy as jnp

    from alink_tpu.dl.attn_pallas import gqa_attention

    b, h, d, p = 16, 8, 128, 17408
    shape = lambda *dims, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(
        dims, dtype, sharding=one_chip)
    compiled = jax.jit(lambda q, K, V, s, hi: gqa_attention(
        q, K, V, s, hi, chunk, scale=d ** -0.5, block=1024)).lower(
        shape(b, h, rows, d), shape(b, h, d, p), shape(b, h, p, d),
        shape(b, dtype=jnp.int32), shape(b, dtype=jnp.int32)).compile()
    assert "gqa_attend" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < b * h * p * d * 2


@pytest.mark.parametrize("rows,chunk", [(384, 64), (8, 1)],
                         ids=["prompt_chunk", "decode_step"])
def test_window_kernel_compiles_for_v5e_at_the_trinity_cells_size(one_chip, rows,
                                                                  chunk):
    """``window_attend`` at the Trinity cell's sliding layers (16 rows, 8
    key/value heads of 6 queries, q/k and v 128): the ring's 4,096 positions
    and a chunk of 64 or one as operands of their own, in the ring's layout;
    a prompt chunk's scores, 384 x 4,096 float32, held whole in VMEM; a
    decode step's 6 rows padded to 8. Its scratch is less than one ring:
    nothing of the ring is copied round the kernel."""
    import jax
    import jax.numpy as jnp

    from alink_tpu.dl.attn_pallas import window_attention

    b, h, d, w = 16, 8, 128, 4096
    shape = lambda *dims, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(
        dims, dtype, sharding=one_chip)
    compiled = jax.jit(lambda *a: window_attention(
        *a, scale=d ** -0.5, window=w)).lower(
        shape(b, h, rows, d), shape(b, h, d, w), shape(b, h, w, d),
        shape(b, h, d, chunk), shape(b, h, chunk, d),
        shape(b, rows, 1, dtype=jnp.int32), shape(b, 1, w, dtype=jnp.int32),
        shape(b, 1, chunk, dtype=jnp.int32),
        shape(h, rows, 1, dtype=jnp.float32)).compile()
    assert "window_attend" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < b * h * w * d * 2


@pytest.mark.parametrize("chunk", [64, 1], ids=["prompt_chunk", "decode_step"])
def test_window_core_writes_the_ring_in_place_for_v5e_at_the_trinity_cells_size(
        one_chip, monkeypatch, chunk):
    """The whole of a Trinity window layer's core (``dl/gqa.window_attend``:
    the kernel and the ring's write, 16 rows, 48 query heads over 8, a ring
    of 4,096) compiled with the ring donated, as the serving programs hold
    and call it: the ring is updated in
    place (aliased), no copy of its shape is in the program, and the
    scratch stays under one ring. (Written after the kernel, a decode
    step's one-slot update made XLA lay both rings out anew, in and out,
    every call.)"""
    import re

    import jax
    import jax.numpy as jnp

    from alink_tpu.dl import gqa

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setenv("ALINK_ATTN_PALLAS", "1")
    monkeypatch.delenv("ALINK_PALLAS_INTERPRET")
    b, hq, h, d, w = 16, 48, 8, 128, 4096
    shape = lambda *dims, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(
        dims, dtype, sharding=one_chip)
    core = lambda q, k, v, rk, rv, n, ok, s: gqa.window_attend(
        q, k, v, rk, rv, n, ok, s, window=w, scale=d ** -0.5,
        dtype=jnp.bfloat16)
    compiled = jax.jit(core, donate_argnums=(3, 4)).lower(
        shape(b, chunk, hq, d), shape(b, chunk, h, d), shape(b, chunk, h, d),
        shape(b, h, d, w), shape(b, h, w, d), shape(b, dtype=jnp.int32),
        shape(b, chunk, dtype=jnp.bool_), shape(hq, dtype=jnp.float32)).compile()
    text = compiled.as_text()
    ring = b * h * w * d * 2
    assert "window_attend" in text
    assert not re.search(r"= bf16\[(16,8,128,4096|16,8,4096,128)\]\{[^}]*\} copy",
                         text)
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= 2 * ring
    assert memory.temp_size_in_bytes < ring
