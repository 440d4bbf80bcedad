"""Faults planted in the program's timed path, for ``test_run.py``: each
breaks the program (never the benchmark) before ``run.main`` drives a whole
rehearsal over it, and the run has to come out with ``correct`` false.

    python3 -m benchmark.tests.faults <fault> <run.py arguments...>
"""

from __future__ import annotations

import sys


def frozen_step() -> None:
    """The compiled train step returns its state unchanged."""
    import jax
    import jax.numpy as jnp

    import alink_tpu.dl.train as dl_train

    real_make = dl_train.make_train_step

    def make(*a, **kw):
        step = real_make(*a, **kw)

        def frozen(variables, opt_state, batch, yb, wb, dkey=None):
            copy = lambda t: jax.tree.map(jnp.copy, t)      # the step donates
            loss = step(copy(variables), copy(opt_state), batch, yb, wb, dkey)[2]
            return variables, opt_state, loss

        return frozen

    dl_train.make_train_step = make


def half_batch() -> None:
    """The second half of every batch is left out; the loss is the mean over
    the rest (its rows' weights are zero)."""
    import jax.numpy as jnp

    import alink_tpu.dl.train as dl_train

    real_make = dl_train.make_train_step

    def make(*a, **kw):
        step = real_make(*a, **kw)

        def halved(variables, opt_state, batch, yb, wb, dkey=None):
            keep = jnp.arange(wb.shape[0]) < wb.shape[0] // 2
            return step(variables, opt_state, batch, yb,
                        jnp.where(keep, wb, 0.0), dkey)

        return halved

    dl_train.make_train_step = make


def altered_answer() -> None:
    """Every served row's probabilities are shifted where the mapper
    produces them."""
    import numpy as np

    import alink_tpu.operator.batch.dl as op_dl

    real = op_dl.softmax_np

    def shifted(logits):
        p = np.asarray(real(logits)).copy()
        p[:, 0] = np.clip(p[:, 0] + 0.05, 0.0, 1.0)
        p[:, 1:] *= ((1.0 - p[:, 0]) / np.maximum(p[:, 1:].sum(1), 1e-9))[:, None]
        return p

    op_dl.softmax_np = shifted


FAULTS = {"frozen_step": frozen_step, "half_batch": half_batch,
          "altered_answer": altered_answer}

if __name__ == "__main__":
    import os

    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))
    from benchmark import run

    FAULTS[sys.argv[1]]()
    sys.exit(run.main(sys.argv[2:]))
