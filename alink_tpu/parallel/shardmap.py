"""The one sanctioned import of ``jax.shard_map`` and its manual-mode helpers.

Every kernel in the tree imports :func:`shard_map` from here
(``from alink_tpu.parallel.shardmap import shard_map``) instead of touching
``jax.shard_map`` directly — alink-lint rule ALK002 bans direct use, so an
API move in jax is a one-file change. Written for the installed jax (0.9):
``jax.shard_map`` with ``check_vma`` (the varying-manual-axes type checker)
and ``axis_names`` (the mesh axes the kernel is manual over; the rest stay
in GSPMD auto mode).

jax is imported inside the functions: this package defers jax imports so
that supervisors and linters can import it without touching a runtime.
"""

from __future__ import annotations

from typing import Any, Optional


def shard_map(f, *, mesh, in_specs, out_specs,
              check_vma: bool = True,
              axis_names: Optional[Any] = None):
    """``jax.shard_map``; ``axis_names=None`` means manual over every mesh
    axis."""
    import jax

    kwargs = {} if axis_names is None else {"axis_names": set(axis_names)}
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma, **kwargs)


def pvary(x, axis_name):
    """Mark ``x`` as varying over ``axis_name`` for the vma checker."""
    import jax

    return jax.lax.pcast(x, axis_name, to="varying")


def axis_size(axis_name) -> int:
    """Static size of a bound mesh axis inside a manual kernel."""
    import jax

    return jax.lax.axis_size(axis_name)
