"""Custom-kernel registry: every hand-written Pallas kernel in one table.

The kernel program (ROADMAP item 3) grew from one ad-hoc kernel
(``tree/pallas_hist.py``) to a family; this registry is the single place
that records, per kernel id: the env knob that gates it, the module that
implements it, the XLA fallback it must stay parity-pinned against, and the
parity contract the CI suite enforces. ``profiling.kernel_candidates()``
cross-references it so "has a custom kernel" is queryable next to the
roofline worst-offenders ranking, and alink-lint ALK008 reads
:data:`KERNEL_MODULES` as the allow-list for ``jax.experimental.pallas``
imports — a Pallas call site outside a registered module fails ``--check``.

All kernels share ONE gate parser (:func:`kernel_enabled`): an env
knob set to a falsey spelling (``0/off/false/no``) disables, any other
non-blank value enables, blank/unset defers to the backend default (on for
the ``tpu`` backend, off elsewhere). Kernels are compiled by Mosaic unless
the caller asks for the Pallas interpreter (:func:`interpret_mode`,
``ALINK_PALLAS_INTERPRET=1`` — the test suite's conftest sets it): the mode
is never inferred from the backend, so a process that failed to get its
chip cannot quietly interpret its way to a result.

This module stays import-light (no jax at module scope, and the static
readouts never start a backend): the linter, the WebUI and fleet
supervisors import it without taking the chip.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import sys

from ..common.env import env_flag, env_str

# falsey spellings shared with env_flag; blank counts as UNSET (backend
# default), not as off — the convention pallas_hist established
_FALSEY = ("0", "off", "false", "no")


def interpret_mode() -> bool:
    """True when the caller asked for the Pallas interpreter
    (``ALINK_PALLAS_INTERPRET``): one switch for every kernel, set by the
    test suite so CPU meshes validate the same programs Mosaic compiles on
    the chip. Unset means compile — on a backend without Mosaic a kernel
    forced on by its knob then fails to lower instead of being emulated."""
    return env_flag("ALINK_PALLAS_INTERPRET", False)


def kernel_enabled(knob: str) -> bool:
    """The shared gate parser: explicit knob value wins (falsey spellings
    off, anything else on, blank = unset), otherwise default-on exactly on
    the ``tpu`` backend. Every registered kernel's ``use_*()`` routes through
    here so all knobs parse on/off/backend identically.

    A registry record's ``single_device_only`` narrows the default for a
    kernel whose call sites sit in GSPMD-partitioned programs: jax refuses
    to partition a Mosaic kernel ("Mosaic kernels cannot be automatically
    partitioned") unless it is inside a shard_map manual over every mesh
    axis, so such a kernel defaults on only in a one-device process."""
    flag = env_str(knob)
    if flag is not None and flag.strip():
        return flag.strip().lower() not in _FALSEY
    import jax

    if jax.default_backend() != "tpu":
        return False
    single_device_only = any(
        rec["knob"] == knob and rec.get("single_device_only")
        for rec in _REGISTRY.values())
    return not single_device_only or jax.device_count() == 1


def backend_started() -> bool:
    """Whether this process has already initialised a jax backend — the
    condition under which asking for the default backend costs nothing. A
    process that has not (a supervisor, the linter) must not be made to
    start one: on a TPU host that takes the chip from its workers."""
    if "jax" not in sys.modules:
        return False
    from jax._src import xla_bridge

    return xla_bridge.backends_are_initialized()


# kernel id -> static registration record. ``module`` paths are
# repo-relative and feed the ALK008 allow-list; ``fallback`` names the XLA
# path the knob-off route compiles; ``contract`` is the CI-pinned parity
# promise; ``programs`` lists the ProgramCache kernel_id prefixes the
# kernel rides inside — the join key :func:`covering` resolves for the
# candidates table.
_REGISTRY: Dict[str, Dict[str, Any]] = {
    "tree.pallas_hist": {
        "knob": "ALINK_GBDT_PALLAS",
        "module": "alink_tpu/tree/pallas_hist.py",
        "entry": "pallas_histogram",
        "programs": ("tree.level",),
        "fallback": "vmapped segment_sum histogram (tree/grow.py)",
        "contract": "forest trees identical vs fallback at atol=1e-5 "
                    "(tests/test_pallas_hist.py)",
    },
    "embedding.sgns_pallas": {
        "knob": "ALINK_SGNS_PALLAS",
        "module": "alink_tpu/embedding/sgns_pallas.py",
        "entry": "sgns_block_grads",
        "programs": ("embedding.sgns_sharded",),
        "fallback": "XLA gather/einsum/scatter step "
                    "(embedding/skipgram._block_grads)",
        "contract": "block gradients within atol=1e-5 of _block_grads "
                    "(fp32; summation order over negatives differs), "
                    "knob-off byte-identical (tests/test_kernels.py)",
    },
    "dl.attn_pallas": {
        "knob": "ALINK_ATTN_PALLAS",
        # called from jit programs GSPMD partitions (the encoder's default
        # attention, blockwise) and from a shard_map manual over the seq
        # axis only (ring): none can hold a Mosaic kernel on more than one
        # device
        "single_device_only": True,
        "module": "alink_tpu/dl/attn_pallas.py",
        # the whole core of the default attention, forward and backward
        # kernels under one custom_vjp; the causal core of whole sequences in
        # blocks (latent attention's expanded form, q/k and v of unequal
        # widths), likewise; the block update that blockwise/ring attention
        # call per K/V block; and a full grouped-query layer's core over its
        # key/value cache and a window layer's over its ring, a prompt chunk
        # or a decode step (dl/gqa.py)
        "entry": "fused_attention, causal_attention, flash_block_update, "
                 "gqa_attention, window_attention",
        "programs": ("dl.train_step", "dl.micro_step",
                     "dl.fused_accum_step", "dl.mlm_step", "dl.mlm_micro",
                     "dl.attention", "dl.apply_logits"),
        "fallback": "full_attention (dl/attention.py), also below the "
                    "length threshold, for a length not lane-aligned, a "
                    "head dimension other than 64 or 128, a causal call; "
                    "the XLA block loops of dl/mla._causal for the causal "
                    "core, also for a length that is not a multiple of the "
                    "kernels' block or over what the backward kernel's "
                    "VMEM holds, q/k not of whole or half lane groups, v "
                    "not of whole ones; lax.scan online-softmax "
                    "(dl/attention._online_softmax_update) for the block "
                    "update; the XLA block loop of dl/gqa._blocks for the "
                    "grouped-query core, also for a cache not in whole "
                    "blocks (query rows not in whole sublane tiles are "
                    "padded to one, dl/gqa.tile_rows); "
                    "dl/gqa._window_xla for the window core",
        "contract": "fused core: outputs and dq, dk, dv within 2e-5 "
                    "(fp32) and 4e-2 (bf16, values of order 1) of "
                    "full_attention, a fully masked row included; causal "
                    "core: output within 2e-5 and dq, dk, dv within 5e-5 "
                    "(fp32) of materialised scores, all within 4e-2 (bf16, "
                    "values of order 1) of dl/mla._causal "
                    "(tests/test_attn_fused.py); grouped-query core within "
                    "2e-5 of dl/gqa._blocks, window core within 2e-3 of "
                    "dl/gqa._window_xla, in bfloat16 operands "
                    "(tests/test_lm_window.py); blockwise/ring outputs "
                    "within atol=1e-5 of the XLA path (fp32), knob-off "
                    "byte-identical (tests/test_kernels.py)",
    },
    "dl.retention_pallas": {
        "knob": "ALINK_RETENTION_PALLAS",
        # called from the generator's prefill program, which is placed on
        # one device; on a mesh GSPMD could not partition it
        "single_device_only": True,
        "module": "alink_tpu/dl/retention_pallas.py",
        # what a prompt chunk sends through the retention state of a row
        # and key/value head (the queries' read of the state before the
        # chunk, the keys' update of it), phi built in VMEM a cyclic
        # distance at a time against that distance's block of the state
        "entry": "chunk_through_state",
        "programs": ("lm.prefill_chunk",),
        "fallback": "retention_chunk's loop over key/value heads with phi "
                    "from power_embed (dl/retention._through_state_xla), "
                    "also for a head width other than 128 and a chunk "
                    "length that is not a multiple of 8",
        "contract": "outputs and the state after the chunk within 2e-5 "
                    "(fp32) and 4e-2 (bf16, values of order 1) of the XLA "
                    "form, padded positions and an inherited state "
                    "included (tests/test_retention_pallas.py)",
    },
}

# repo-relative module suffixes allowed to import jax.experimental.pallas —
# the ALK008 allow-list (anything under alink_tpu/native/ is additionally
# allowed; see analysis/lint.py)
KERNEL_MODULES = tuple(sorted(rec["module"] for rec in _REGISTRY.values()))


def kernel_ids() -> tuple:
    return tuple(sorted(_REGISTRY))


def kernel_spec(kernel_id: str) -> Optional[Dict[str, Any]]:
    """Static registration record for one kernel id (None if the id has no
    custom kernel). The candidates table calls this per row."""
    rec = _REGISTRY.get(kernel_id)
    return dict(rec) if rec is not None else None


def covering(program_kernel_id: str) -> Optional[str]:
    """The registered custom kernel riding inside a ProgramCache program,
    by kernel_id prefix match — ``covering("tree.level") ->
    "tree.pallas_hist"``, ``covering("optim.lbfgs") -> None``. This is how
    the candidates table answers "does this worst-offender already have a
    hand-written kernel"."""
    for kid, rec in _REGISTRY.items():
        if program_kernel_id == kid:
            return kid
        for prefix in rec["programs"]:
            if program_kernel_id == prefix or \
                    program_kernel_id.startswith(prefix + "."):
                return kid
    return None


def registry(*, live: bool = True) -> Dict[str, Dict[str, Any]]:
    """JSON-able registry snapshot. With ``live`` (default) each record
    additionally reports the gate's CURRENT reading (``enabled``) and
    whether the kernel would run interpreted — the answer depends on the
    process env + backend, so readouts re-evaluate per call. A kernel whose
    knob is unset follows the backend; in a process that has not started
    one, its ``enabled`` reads ``None`` rather than starting it."""
    out: Dict[str, Dict[str, Any]] = {}
    for kid, rec in sorted(_REGISTRY.items()):
        row = dict(rec)
        if live:
            knob_set = bool((env_str(rec["knob"]) or "").strip())
            row["enabled"] = kernel_enabled(rec["knob"]) \
                if knob_set or backend_started() else None
            row["interpret"] = interpret_mode()
        out[kid] = row
    return out
