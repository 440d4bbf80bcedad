"""Weak-scaling invariants on the virtual mesh: per-device compiled work
must stay ~constant as dp grows with the global batch (reference analog:
the MiniCluster-with-N-TaskManagers strategy,
test_utils/.../LocalEnvFactoryImpl.java:20-41).

These catch accidental replication/gather regressions — a batch that stops
being sharded shows up as per-device FLOPs growing with dp — which the
functional multichip dryrun cannot see."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")


def _flops(compiled) -> float:
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0]
    f = ca.get("flops", 0.0)
    assert f and np.isfinite(f), ca
    return float(f)


def _dp_values():
    n = len(jax.devices())
    return [d for d in (1, 2, 4, 8) if d <= n]


def test_lbfgs_per_device_flops_constant():
    from alink_tpu.optim import optimize, softmax_obj
    from alink_tpu.parallel.mesh import AXIS_DATA, make_mesh

    dps = _dp_values()
    assert dps[-1] >= 4, "needs the 8-virtual-device CPU mesh"
    rng = np.random.RandomState(0)
    dim, k, per_dev = 16, 3, 64
    flops = {}
    for dp in dps:
        mesh = make_mesh({AXIS_DATA: dp}, devices=jax.devices()[:dp])
        n = per_dev * dp  # weak scaling: rows grow with devices
        X = rng.rand(n, dim).astype(np.float32)
        y = rng.randint(0, k, n).astype(np.float32)
        lowered = optimize(softmax_obj(dim, k), X, y, mesh=mesh,
                           max_iter=5, _lower_only=True)
        flops[dp] = _flops(lowered.compile())
    base = flops[dps[0]]
    for dp in dps[1:]:
        ratio = flops[dp] / base
        # constant per-device work (+ small collective/overhead growth);
        # full replication would show ratio ~= dp
        assert ratio < 1.6, (flops, ratio)


def test_bert_train_step_per_device_flops_constant():
    import optax

    from alink_tpu.dl.modules import BertConfig, TransformerEncoder
    from alink_tpu.dl.sharding import (batch_sharding, make_dl_mesh,
                                       param_shardings)
    from alink_tpu.dl.train import make_train_step

    dps = _dp_values()
    assert dps[-1] >= 4
    rng = np.random.RandomState(0)
    seqlen, per_dev = 32, 2
    cfg = BertConfig(
        vocab_size=256, hidden_size=32, num_layers=2, num_heads=4,
        intermediate_size=64, max_position=seqlen, num_labels=2,
        dropout=0.0)

    def ce(logits, yy):
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, yy).mean()

    flops = {}
    for dp in dps:
        mesh = make_dl_mesh(dp=dp, tp=1, sp=1, devices=jax.devices()[:dp])
        model = TransformerEncoder(cfg)
        batch = per_dev * dp
        ids = rng.randint(0, cfg.vocab_size, (batch, seqlen)).astype(
            np.int32)
        amask = np.ones((batch, seqlen), np.int32)
        y = rng.randint(0, 2, batch).astype(np.int32)
        params = model.init(jax.random.PRNGKey(0), ids, amask)
        params = jax.device_put(params, param_shardings(params, mesh))
        tx = optax.adamw(1e-3)
        opt_state = tx.init(params["params"])
        train_step = make_train_step(model, tx, ce)
        batch_args = {
            "input_ids": jax.device_put(ids, batch_sharding(mesh, 2)),
            "attention_mask": jax.device_put(amask, batch_sharding(mesh, 2)),
        }
        y_s = jax.device_put(y, batch_sharding(mesh, 1))
        lowered = train_step.lower(params, opt_state, batch_args, y_s)
        flops[dp] = _flops(lowered.compile())
    base = flops[dps[0]]
    for dp in dps[1:]:
        ratio = flops[dp] / base
        assert ratio < 1.6, (flops, ratio)


# ---------------------------------------------------------------------------
# APS owner-routed pull/push: per-device collective bytes ~constant in M
# ---------------------------------------------------------------------------


def _aps_compiled(m, mode, routed):
    """Compile pull or push on an M-device model mesh: per-device batch B
    and rows-per-shard constant (weak scaling — the vocab grows with M)."""
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from alink_tpu.parallel.aps import (ShardedEmbedding, model_mesh, pull,
                                        pull_allgather, push, push_allgather)
    from alink_tpu.parallel.mesh import AXIS_MODEL
    from alink_tpu.parallel.shardmap import shard_map

    mesh = model_mesh(m)
    rows, D, B = 16, 4, 32
    V = rows * m
    table = ShardedEmbedding(mesh, V, D)
    ids = np.random.default_rng(0).integers(0, V, size=(m, B)).astype(
        np.int32)
    grads = np.ones((m, B, D), np.float32)
    if mode == "pull":
        def body(tl, i):
            return (pull if routed else pull_allgather)(
                tl, i[0], AXIS_MODEL, rows)
        spec = (P(AXIS_MODEL),) * 2
        args = (table.array, jnp.asarray(ids))
    else:
        def body(tl, i, g):
            return (push if routed else push_allgather)(
                tl, i[0], g[0], AXIS_MODEL, rows)
        spec = (P(AXIS_MODEL),) * 3
        args = (table.array, jnp.asarray(ids), jnp.asarray(grads))
    f = jax.jit(shard_map(body, mesh=mesh, in_specs=spec,
                          out_specs=P(AXIS_MODEL), check_vma=False))
    return f.lower(*args).compile()


@pytest.mark.parametrize("mode", ["pull", "push"])
def test_aps_routed_collective_bytes_constant(mode):
    """The O(B·D) claim, pinned via compiled-HLO accounting: per-device
    steady-state collective bytes stay ~flat as the model axis grows
    1→2→4→8 (M=1 compiles to zero collective traffic, so ratios are taken
    against the smallest multi-device mesh)."""
    from alink_tpu.common.profiling import collective_bytes

    ms = _dp_values()
    assert ms[-1] >= 4, "needs the 8-virtual-device CPU mesh"
    routed = {m: collective_bytes(_aps_compiled(m, mode, True)) for m in ms}
    assert routed[ms[0]] == 0 if ms[0] == 1 else routed[ms[0]] > 0
    base = routed[ms[1]]
    assert base > 0
    for m in ms[2:]:
        ratio = routed[m] / base
        # an O(M·B·D) regression (all-gathered contributions) would show
        # ratio ~= m / ms[1]
        assert ratio < 1.6, (routed, ratio)


@pytest.mark.parametrize("mode", ["pull", "push"])
def test_aps_gather_reference_collective_bytes_grow(mode):
    """Sensitivity check for the accounting itself: the legacy all-gather
    path DOES grow ~linearly in M, so a flat routed curve is signal, not a
    blind meter."""
    from alink_tpu.common.profiling import collective_bytes

    ms = [m for m in _dp_values() if m >= 2]
    if len(ms) < 2:
        pytest.skip("needs ≥4 devices")
    gathered = {m: collective_bytes(_aps_compiled(m, mode, False))
                for m in ms}
    growth = gathered[ms[-1]] / gathered[ms[0]]
    expected = ms[-1] / ms[0]
    assert growth > 0.6 * expected, (gathered, growth)
    # and routed beats gather outright on the largest mesh
    routed_big = collective_bytes(_aps_compiled(ms[-1], mode, True))
    assert routed_big < gathered[ms[-1]] / 2, (routed_big, gathered)


# ---------------------------------------------------------------------------
# the REAL huge-embedding training loop (not the micro pull/push cycle):
# per-device collective bytes ~constant in M for the routed engine, with and
# without the hot-key cache; the host (gathered) engine grows ~linearly
# ---------------------------------------------------------------------------

def _sgns_loop_bytes(m, engine, hot=0):
    """The probe kept beside the engine (`collective_bytes_probe`)."""
    from alink_tpu.embedding.engine import collective_bytes_probe

    return collective_bytes_probe(m, engine, hot_rows=hot)


@pytest.mark.parametrize("hot", [0, 16])
def test_sgns_training_loop_collective_bytes_flat(hot):
    """ROADMAP open item 2 at the workload level: the whole sharded-SGNS
    training program (pull → grads → push per step, hot-key cache at
    hot=16) keeps per-device steady-state collective bytes ~flat as the
    model axis grows — the micro pull/push pin alone can't see a gather
    sneaking into the composed loop."""
    ms = _dp_values()
    assert ms[-1] >= 4, "needs the 8-virtual-device CPU mesh"
    got = {m: _sgns_loop_bytes(m, "sharded", hot) for m in ms if m >= 2}
    base = got[ms[1]]
    assert base > 0
    for m in list(got)[1:]:
        ratio = got[m] / base
        assert ratio < 1.6, (got, ratio)


def test_sgns_cached_loop_bytes_below_routed():
    """The hot-key cache is a net byte reduction on the full mesh under the
    Zipf frequency table (hot pulls never ride the wire; the replica
    refresh costs a flat broadcast)."""
    ms = _dp_values()
    if ms[-1] < 4:
        pytest.skip("needs a multi-device mesh")
    m = ms[-1]
    routed = _sgns_loop_bytes(m, "sharded", hot=0)
    cached = _sgns_loop_bytes(m, "sharded", hot=16)
    assert cached < routed, (cached, routed)


def test_sgns_host_reference_bytes_grow():
    """Sensitivity check: the host engine's gathered updates DO grow
    ~linearly in M, so the flat routed curve is signal, not a blind
    meter."""
    ms = [m for m in _dp_values() if m >= 2]
    if len(ms) < 2:
        pytest.skip("needs ≥4 devices")
    got = {m: _sgns_loop_bytes(m, "host") for m in ms}
    growth = got[ms[-1]] / got[ms[0]]
    expected = ms[-1] / ms[0]
    assert growth > 0.6 * expected, (got, growth)
    # and the routed engine beats the host engine outright at full scale
    routed_big = _sgns_loop_bytes(ms[-1], "sharded")
    assert routed_big < got[ms[-1]], (routed_big, got)


def test_staged_arrays_actually_sharded():
    """Each device holds n/dp rows — full replication would hold n."""
    from alink_tpu.parallel.comqueue import shard_rows
    from alink_tpu.parallel.mesh import AXIS_DATA, make_mesh

    n_dev = len(jax.devices())
    if n_dev < 2:
        pytest.skip("needs multi-device mesh")
    mesh = make_mesh({AXIS_DATA: n_dev})
    X = np.random.RandomState(0).rand(16 * n_dev, 4).astype(np.float32)
    out = shard_rows(mesh, X)
    shard_rows_count = out.addressable_shards[0].data.shape[0]
    assert shard_rows_count == 16, (shard_rows_count, n_dev)
