"""The grouped form of the expert layer (``dl/moe.routed_experts`` with no
``piece``) takes the sorted assignment rows through the held experts'
products up to the smallest rung of its ladder that holds every held one.
What it gives is the whole form's, which took all ``N K`` rows through both
products, zeroed those past the held and unsorted the rest (kept below as the
reference), whatever the router held: nothing, a rung exactly, one row past
it, everything, a batch with padding positions, a skewed router.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from alink_tpu.dl import moe as E

# 256 tokens of 4 choices: 1,024 sorted rows, rungs of 128, 256, 512, 1,024
N, K, H, F, HELD, E_ALL = 256, 4, 16, 8, 4, 16


def _whole(n, local, w, sizes, gate_up, down, dtype):
    """The form before the rungs: every sorted row through both products,
    those past the held zeroed, unsorted and summed a token."""
    flat = local.reshape(-1)
    order = jnp.argsort(flat, stable=True)
    back = jnp.argsort(order)
    ours = (flat[order] < gate_up.shape[0])[:, None]
    out = E._expert_rows(n.astype(dtype)[order // K], w.reshape(-1)[order],
                         sizes, gate_up, down, dtype=dtype)
    out = jnp.where(ours, out, 0)
    return out[back].reshape(N, K, -1).astype(jnp.float32).sum(axis=1)


def _operands(seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(ks[0], (N, H)),
            jax.random.normal(ks[1], (HELD, H, 2 * F)) * 0.3,
            jax.random.normal(ks[2], (HELD, F, H)) * 0.3,
            jax.random.normal(ks[3], (E_ALL, H)) * 0.5)


def _placed(held, seed=1):
    """``held`` of the ``N K`` assignments on a held expert, drawn at random,
    the rest elsewhere, and random weights."""
    rng = np.random.default_rng(seed)
    local = np.full(N * K, HELD, np.int32)
    at = rng.choice(N * K, held, replace=False)
    local[at] = rng.integers(0, HELD, held)
    w = rng.uniform(0.1, 1.0, (N, K)).astype(np.float32)
    return jnp.asarray(local.reshape(N, K)), jnp.asarray(w)


def _routed(n, gate, skew=0.0, valid=N):
    """What the router sends the experts ``[0, 4)`` of 16, its logits
    tilted toward the low experts by ``skew`` a place (the skewed router of
    ``tests/test_lm_hybrid.py``), positions from ``valid`` on padding."""
    logits = jnp.einsum("nh,eh->ne", n, gate, precision="highest") \
        + skew * jnp.arange(E_ALL)[::-1]
    idx, w = E.route(logits, jnp.zeros(E_ALL), n_group=4, topk_group=2,
                     top_k=K, scale=2.5)
    local, _ = E.held_load(idx[None], (jnp.arange(N) < valid)[None],
                           (0, HELD))
    return local[0], w


CASES = {
    "none_held": lambda n, g: _placed(0),
    "at_a_rung": lambda n, g: _placed(256),
    "one_past_a_rung": lambda n, g: _placed(257),
    "every_one_held": lambda n, g: _placed(N * K),
    "padding_positions": lambda n, g: _routed(n, g, valid=100),
    "skewed_router": lambda n, g: _routed(n, g, skew=0.4),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_rung_form_is_the_whole_form_and_takes_the_smallest_rung(case):
    n, gate_up, down, gate = _operands()
    local, w = CASES[case](n, gate)
    sizes = (local.reshape(-1)[:, None] == jnp.arange(HELD)).sum(0).astype(
        jnp.int32)
    held = int(sizes.sum())
    with jax.default_matmul_precision("highest"):
        got = jax.jit(lambda *a: E.routed_experts(*a, dtype=jnp.float32))(
            n, local, w, sizes, gate_up, down)
        want = _whole(n, local, w, sizes, gate_up, down, jnp.float32)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    i, rows = (int(a) for a in E.rung(sizes.sum(), N * K))
    ladder = E.rungs(N * K)
    assert ladder == (128, 256, 512, 1024)
    assert rows == ladder[i] == min(r for r in ladder if r >= held)
    if case == "padding_positions":
        assert held <= 100 * K and rows < N * K
    if case == "skewed_router":
        assert N * K // 4 < held < N * K     # more than an even router's share
    if held:
        assert float(jnp.abs(got).max()) > 0
    else:
        assert float(jnp.abs(got).max()) == 0.0


@pytest.mark.parametrize("rows,ladder", [
    (16384, (1024, 2048, 4096, 8192, 16384)),  # a MiMo prompt chunk
    (65536, (4096, 8192, 16384, 32768, 65536)),  # a Ling prompt chunk
    (1024, (128, 256, 512, 1024)),               # a Ling decode step
    (256, (128, 256)),                           # a MiMo decode step
    (200, (128, 200)),
    (128, (128,)),
    (16, (16,)),
])
def test_the_ladder_is_a_rule_of_the_calls_rows(rows, ladder):
    assert E.rungs(rows) == ladder
    assert len(ladder) <= 6 and ladder[-1] == rows
    for held in sorted({0, 1, *ladder, *(r + 1 for r in ladder[:-1])}):
        i, got = (int(a) for a in E.rung(jnp.int32(held), rows))
        assert got == ladder[i] == min(r for r in ladder if r >= held)


def test_the_rung_form_has_the_whole_forms_gradient():
    """Under ``jax.grad`` (the grouped form's gradient is what the training
    step's pieces are checked against) the rows past the rung take none."""
    n, gate_up, down, _ = _operands(seed=3)
    local, w = _placed(300, seed=4)
    sizes = (local.reshape(-1)[:, None] == jnp.arange(HELD)).sum(0).astype(
        jnp.int32)
    g = jax.random.normal(jax.random.PRNGKey(5), (N, H))
    loss = lambda form: lambda n, up, dn: (form(n, local, w, sizes, up, dn,
                                                jnp.float32) * g).sum()
    rung_form = lambda *a: E.routed_experts(*a[:-1], dtype=a[-1])
    with jax.default_matmul_precision("highest"):
        got = jax.grad(loss(rung_form), argnums=(0, 1, 2))(n, gate_up, down)
        want = jax.grad(loss(_whole), argnums=(0, 1, 2))(n, gate_up, down)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)
