"""The prompt chunk's kernel (dl/retention_pallas.py ``chunk_through_state``)
against the XLA form of ``retention_chunk``, which stays its reference: under
the Pallas interpreter at the real head width (128, so phi is 8,256 wide and
its last distance half a block), the rule that selects it and the counters of
what a layer was traced down. The kernel is compiled by Mosaic for a described
v5e in ``tests/test_attn_fused.py``, beside the other kernels of the main
paths (one file loads the TPU's library)."""

import re

import numpy as np
import pytest

D, HQ, HKV = 128, 4, 2      # two query heads a key/value head


def _inputs(rows, length, *, d=D, gate_shift=0.0, seed=0):
    import jax

    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    draw = lambda key, *shape: jax.random.normal(key, shape)
    return (draw(ks[0], rows, length, HQ, d), draw(ks[1], rows, length, HKV, d),
            draw(ks[2], rows, length, HKV, d),
            jax.nn.log_sigmoid(draw(ks[3], rows, length, HKV) + gate_shift))


def _state(rows, *, d=D, seed=9):
    import jax
    import jax.numpy as jnp

    from alink_tpu.dl.retention import phi_dim

    ka, kb = jax.random.split(jax.random.PRNGKey(seed))
    return (jax.random.normal(ka, (rows, HKV, phi_dim(d), d)),
            jnp.abs(jax.random.normal(kb, (rows, HKV, phi_dim(d)))) + 1.0)


def _chunk(monkeypatch, knob, *args, **kw):
    """``retention_chunk`` with the kernel's knob set, and how many layers
    each program's counter grew by."""
    from alink_tpu.common.metrics import metrics
    from alink_tpu.dl.retention import retention_chunk

    monkeypatch.setenv("ALINK_RETENTION_PALLAS", knob)
    names = ("chunk_fused_traces", "chunk_xla_traces")
    before = [metrics.counter("retention." + n) for n in names]
    out = retention_chunk(*args, eps=1e-6, **kw)
    return out, tuple(metrics.counter("retention." + n) - b
                      for n, b in zip(names, before))


def _close(got, want, tol):
    """Within ``tol`` of the largest value, entry by entry."""
    for name, a, b in zip(("o", "S", "z"), got, want):
        a, b = np.asarray(a), np.asarray(b)
        np.testing.assert_allclose(a, b, rtol=tol, atol=tol * np.abs(b).max(),
                                   err_msg=name)


# float32: the two differ by the order of the sums only (a distance at a time
# against one product over phi); bfloat16: as tests/test_attn_fused.py, values
# of order 1 whose rounded operands' products are summed in another order
_TOL = {"float32": 2e-5, "bfloat16": 4e-2}


def _case_matches(monkeypatch, dtype):
    """A chunk on a state left by earlier chunks, rows of unequal length."""
    import jax.numpy as jnp

    q, k, v, lg = _inputs(2, 16)
    valid = jnp.arange(16)[None, :] < jnp.asarray([16, 11])[:, None]
    args = (q, k, v, lg, valid, *_state(2))
    want, grew = _chunk(monkeypatch, "0", *args, dtype=jnp.dtype(dtype))
    assert grew == (0, 1)
    got, grew = _chunk(monkeypatch, "1", *args, dtype=jnp.dtype(dtype))
    assert grew == (1, 0)
    assert got[0].dtype == got[1].dtype == got[2].dtype == jnp.float32
    _close(got, want, _TOL[dtype])


def _case_padding(monkeypatch):
    """A row of padding alone: its state comes back bit for bit, and its
    neighbour's outputs are those of the XLA form."""
    import jax.numpy as jnp

    q, k, v, lg = _inputs(2, 8, seed=1)
    S0, z0 = _state(2)
    valid = jnp.asarray([[True] * 8, [False] * 8])
    (o, S, z), _ = _chunk(monkeypatch, "1", q, k, v, lg, valid, S0, z0)
    np.testing.assert_array_equal(S[1], S0[1])
    np.testing.assert_array_equal(z[1], z0[1])
    want, _ = _chunk(monkeypatch, "0", q, k, v, lg, valid, S0, z0)
    _close((o, S, z), want, _TOL["float32"])


def _case_inherited(monkeypatch):
    """Gates near 1 (a head that remembers hundreds of positions, as the
    cell's seeded bias makes them): the outputs stand on the state the chunk
    inherits, which is what ``correct``'s ``chunk_state_dropped`` control
    guards. Dropping the state moves them far more than the tolerance."""
    import jax.numpy as jnp

    q, k, v, lg = _inputs(1, 8, gate_shift=7.0, seed=2)
    S0, z0 = _state(1)
    want, _ = _chunk(monkeypatch, "0", q, k, v, lg, None, S0, z0)
    got, _ = _chunk(monkeypatch, "1", q, k, v, lg, None, S0, z0)
    _close(got, want, _TOL["float32"])
    dropped, _ = _chunk(monkeypatch, "1", q, k, v, lg, None,
                        jnp.zeros_like(S0), jnp.zeros_like(z0))
    assert np.abs(np.asarray(dropped[0] - want[0])).max() > 0.1


def _case_prompt_then_steps(monkeypatch):
    """A prompt of three chunks, the last one padded, then one-token steps:
    the attention form over the whole sequence, as tests/test_lm.py holds the
    XLA form to it."""
    import jax.numpy as jnp

    import test_lm
    from alink_tpu.dl.retention import phi_dim, retention_step

    monkeypatch.setenv("ALINK_RETENTION_PALLAS", "1")
    n, steps = 21, 3
    q, k, v, lg = _inputs(1, n + steps, gate_shift=3.0, seed=3)
    want = test_lm.retention_attention(q, k, v, lg, eps=1e-6)
    head = lambda x: x[:, :n]
    S, z = jnp.zeros((1, HKV, phi_dim(D), D)), jnp.zeros((1, HKV, phi_dim(D)))
    got, S, z = test_lm.retention_prompt(head(q), head(k), head(v), head(lg),
                                         S, z, chunk=8, eps=1e-6)
    outs = [got]
    for t in range(n, n + steps):
        o, S, z = retention_step(q[:, t], k[:, t], v[:, t], lg[:, t], None,
                                 S, z, eps=1e-6)
        outs.append(o[:, None])
    np.testing.assert_allclose(jnp.concatenate(outs, axis=1), want,
                               rtol=2e-3, atol=2e-4)


def _case_fallback(monkeypatch, why):
    """The XLA form taken, and counted, where the kernel does not apply: a
    head half a lane group wide with the knob on; the real width with the
    knob unset off the TPU; a chunk that is not whole sublane tiles."""
    from alink_tpu.dl import retention

    d, length, knob = {"narrow_head": (64, 8, "1"),
                       "off_the_tpu": (D, 8, ""),
                       "ragged_chunk": (D, 5, "1")}[why]
    monkeypatch.setattr(retention, "chunk_through_state", None)  # not reached
    q, k, v, lg = _inputs(1, length, d=d)
    (o, S, z), grew = _chunk(monkeypatch, knob, q, k, v, lg, None,
                             *_state(1, d=d))
    assert grew == (0, 1)
    assert o.shape == q.shape and S.shape[2:] == (d * (d + 1) // 2, d)


@pytest.mark.parametrize("case,arg", [
    (_case_matches, "float32"), (_case_matches, "bfloat16"),
    (_case_padding, None), (_case_inherited, None),
    (_case_prompt_then_steps, None),
    (_case_fallback, "narrow_head"), (_case_fallback, "off_the_tpu"),
    (_case_fallback, "ragged_chunk"),
], ids=lambda x: getattr(x, "__name__", x) or "")
def test_chunk_kernel(monkeypatch, case, arg):
    case(monkeypatch, *(() if arg is None else (arg,)))


def test_the_rule_reads_the_calls_shapes_and_the_gate(monkeypatch):
    from alink_tpu.dl.retention_pallas import use_chunk_kernel

    monkeypatch.setenv("ALINK_RETENTION_PALLAS", "1")
    assert use_chunk_kernel(256, 128) and use_chunk_kernel(8, 128)
    assert not use_chunk_kernel(256, 64) and not use_chunk_kernel(256, 256)
    assert not use_chunk_kernel(12, 128)
    monkeypatch.setenv("ALINK_RETENTION_PALLAS", "0")
    assert not use_chunk_kernel(256, 128)
    monkeypatch.delenv("ALINK_RETENTION_PALLAS")
    assert not use_chunk_kernel(256, 128)       # the tests' backend is the CPU


def test_phi_is_never_written_on_the_kernels_path(monkeypatch):
    """The traced chunk holds no value as wide as phi but the state and the
    normaliser themselves."""
    import jax
    import jax.numpy as jnp

    from alink_tpu.dl.retention import phi_dim, retention_chunk

    monkeypatch.setenv("ALINK_RETENTION_PALLAS", "1")
    q, k, v, lg = _inputs(2, 8)
    S, z = _state(2)
    text = str(jax.make_jaxpr(lambda *a: retention_chunk(
        *a, eps=1e-6, dtype=jnp.bfloat16))(q, k, v, lg, None, S, z))
    p = phi_dim(D)
    wide = {m for m in re.findall(r"\[([\d,]+)\]", text)
            if str(p) in m.split(",")}
    # the cache's two tensors and a cell's block of the state in the kernel
    assert wide <= {f"2,{HKV},{p},{D}", f"2,{HKV},{p}", f"{p},{D}"}, wide
