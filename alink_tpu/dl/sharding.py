"""Parameter/activation sharding rules over the (data, model, seq) mesh.

Replaces the reference's TF_CONFIG chief/worker/ps distribution (reference:
common/dl/DLRunner.java:95-100 role split; akdl/engine/train.py:16-40
train_and_evaluate) with sharding annotations: XLA inserts the collectives.

Rules are matched on flax param path names (see modules.py naming
conventions):
- attention qkv kernel  (D, 3, H*Dh)  -> shard last dim over `model` (head-parallel)
- attention out kernel  (H*Dh, D)     -> shard first dim over `model`
- mlp_in kernel         (D, F)        -> shard F over `model`
- mlp_out kernel        (F, D)        -> shard F over `model`
- tok_emb embedding     (V, D)        -> shard V over `model`
- the causal LM's leaves (dl/lm.py), which keep the checkpoint's (out, in)
  layout: q/k/v/gate/up_proj shard `out` (heads, FFN columns) over `model`,
  o/down_proj shard `in`, embed_tokens and lm_head shard V; g_proj (one
  output a key/value head), its bias and the norms are replicated. Of the
  kda and mla layers: f_proj, b_proj, kv_b_proj and the shared expert's
  gate/up shard `out` (heads, columns), the shared expert's down `in`, the
  short convolutions, dt_bias and A_log their channels (heads); the latent
  projection kv_a_proj_with_mqa, the router and its bias are replicated, and
  so is g_proj, whose name the three mixers share. The stacked experts
  (experts_gate_up, experts_down: (E, in, out)) shard E over `expert`, an
  axis `make_dl_mesh` does not build: a mesh that has it comes from
  `parallel.mesh.make_mesh`, and on any other the experts are replicated
- the training state of the causal LM (dl/lm.CausalLMTrainer): the `router`
  collection (`expert_bias`, `load`: (expert layers, E)) is replicated, as
  the router is; the optimizer's moments carry their parameter's leaf name at
  the end of their path, so they take their parameter's spec
- everything else replicated
Batch dims of activations shard over `data`; sequence over `seq` when ring
attention is enabled.
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import numpy as np

from ..parallel.mesh import (AXIS_DATA, AXIS_EXPERT, AXIS_MODEL, AXIS_SEQ,
                             make_mesh)


def make_dl_mesh(dp: int = 0, tp: int = 1, sp: int = 1, devices=None):
    """Mesh with (data, model, seq) axes; dp=0 means "all remaining devices"."""
    import jax as _jax

    devices = devices if devices is not None else _jax.devices()
    if dp <= 0:
        dp = max(1, len(devices) // (tp * sp))
    return make_mesh({AXIS_DATA: dp, AXIS_MODEL: tp, AXIS_SEQ: sp}, devices=devices)


def _spec_for(path: str, shape) -> "jax.sharding.PartitionSpec":
    from jax.sharding import PartitionSpec as P

    nd = len(shape)
    if path.endswith("qkv/kernel"):
        return P(*([None] * (nd - 1)), AXIS_MODEL)
    if path.endswith("mlp_in/kernel"):
        return P(None, AXIS_MODEL)
    if path.endswith("mlp_out/kernel"):
        return P(AXIS_MODEL, None)
    if path.endswith("attention/out/kernel"):
        return P(AXIS_MODEL, *([None] * (nd - 1)))
    if path.endswith("qkv/bias") or path.endswith("mlp_in/bias"):
        return P(*([None] * (nd - 1)), AXIS_MODEL) if nd >= 1 else P()
    if path.endswith("tok_emb/embedding"):
        return P(AXIS_MODEL, None)
    leaf = path.rsplit("/", 1)[-1]
    if leaf in _LM_SHARD_OUT and nd == 2:
        return P(AXIS_MODEL, None)
    if leaf in _LM_SHARD_IN and nd == 2:
        return P(None, AXIS_MODEL)
    if leaf in _LM_SHARD_CHANNELS and nd in (1, 3):
        return P(AXIS_MODEL, *([None] * (nd - 1)))
    if leaf in _LM_SHARD_EXPERT and nd == 3:
        return P(AXIS_EXPERT, None, None)
    return P()


_LM_SHARD_OUT = frozenset({"q_proj", "k_proj", "v_proj", "gate_proj", "up_proj",
                           "embed_tokens", "lm_head", "f_proj", "b_proj",
                           "kv_b_proj", "shared_gate_proj", "shared_up_proj"})
_LM_SHARD_IN = frozenset({"o_proj", "down_proj", "shared_down_proj"})
# a value a channel (a head's): the short convolutions (C, 1, K), dt_bias, A_log
_LM_SHARD_CHANNELS = frozenset({"q_conv1d", "k_conv1d", "v_conv1d", "dt_bias",
                                "A_log"})
_LM_SHARD_EXPERT = frozenset({"experts_gate_up", "experts_down"})


def sharding_for(path: str, shape, mesh):
    """The NamedSharding of one leaf: its declared spec where every named
    axis exists in this mesh and divides the dimension, else replicated."""
    from jax.sharding import NamedSharding

    spec = _spec_for(path, shape)
    for dim, ax in enumerate(spec):
        if ax is None:
            continue
        if ax not in mesh.shape or shape[dim] % mesh.shape[ax] != 0:
            return NamedSharding(mesh, jax.sharding.PartitionSpec())
    return NamedSharding(mesh, spec)


def param_shardings(params, mesh) -> Any:
    """NamedSharding pytree for a flax param tree (same structure)."""
    def to_spec(path_entries, leaf):
        path = "/".join(
            str(getattr(e, "key", getattr(e, "name", getattr(e, "idx", e))))
            for e in path_entries
        )
        return sharding_for(path, leaf.shape, mesh)

    return jax.tree_util.tree_map_with_path(to_spec, params)


def batch_sharding(mesh, ndim: int, *, seq_axis: Optional[int] = None):
    """Sharding for a batch array: dim0 over `data`, optional seq dim over `seq`."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    spec = [None] * ndim
    spec[0] = AXIS_DATA
    if seq_axis is not None and mesh.shape.get(AXIS_SEQ, 1) > 1:
        spec[seq_axis] = AXIS_SEQ
    return NamedSharding(mesh, P(*spec))


def chunked_batch_sharding(mesh, ndim: int, *,
                           seq_axis: Optional[int] = None):
    """Sharding for a ``(chunks, batch, ...)`` stacked array: dim1 over
    `data` with dim0 — the gradient-accumulation chunk axis — replicated,
    so each chunk a fused accumulation program scans over has EXACTLY the
    per-device layout of a standalone micro batch. That layout identity is
    what makes the fused large-batch reference bit-identical to the
    micro-step schedule on a multi-device mesh (a plain in-program reshape
    would re-shard the rows and change the per-device reduction shapes)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    spec = [None] * ndim
    spec[1] = AXIS_DATA
    if seq_axis is not None and mesh.shape.get(AXIS_SEQ, 1) > 1:
        spec[seq_axis + 1] = AXIS_SEQ
    return NamedSharding(mesh, P(*spec))


def shard_batch(mesh, arr: np.ndarray, *, seq_axis: Optional[int] = None):
    """Pad dim0 to the data-axis multiple and device_put with batch sharding.
    Returns (sharded, n_valid)."""
    import jax as _jax

    n = arr.shape[0]
    dp = mesh.shape[AXIS_DATA]
    pad = (-n) % dp
    if pad:
        arr = np.concatenate([arr, np.repeat(arr[-1:], pad, axis=0)], axis=0)
    return (
        _jax.device_put(arr, batch_sharding(mesh, arr.ndim, seq_axis=seq_axis)),
        n,
    )
