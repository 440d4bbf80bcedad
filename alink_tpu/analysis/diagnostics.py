"""Shared diagnostic/report model for the static-analysis layer.

Two engines emit these: the plan-time validator (``plancheck.validate_plan``
— walks a deferred operator DAG before execution, reference analog: the
TableSchema propagation Alink performs at graph-build time so user errors
surface before any Flink job launches) and the framework self-linter
(``lint`` — AST rules over alink_tpu's own source). Both speak one
:class:`Diagnostic` shape so ``job_report()``, the WebUI panel, and the CLI
render findings identically.

Rule ids are stable (``ALK0xx`` = source lint, ``ALK1xx`` = plan
validation); tests and suppression baselines key on them, so a rule keeps
its id for life and retired rules are never recycled.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

ERROR = "error"
WARNING = "warning"
INFO = "info"

_SEV_ORDER = {ERROR: 0, WARNING: 1, INFO: 2}

# rule id -> (title, default severity, one-line description). The table the
# docs, the WebUI panel, and ``python -m alink_tpu.analysis.lint --rules``
# render; plancheck/lint reference severities from here so a rule's level
# lives in exactly one place.
RULES: Dict[str, tuple] = {
    # -- source lint (alink-lint, AST over framework source) ---------------
    "ALK000": ("parse-error", ERROR,
               "the file does not parse — no other rule could run on it"),
    "ALK001": ("direct-jit", WARNING,
               "direct jax.jit/pjit call outside common/jitcache.ProgramCache "
               "builders — per-call rebuilt programs defeat the process-wide "
               "compile cache"),
    "ALK002": ("shard-map-drift", WARNING,
               "direct jax.shard_map usage — import it from "
               "alink_tpu/parallel/shardmap.py, the one module that follows "
               "jax's shard_map API"),
    "ALK003": ("raw-environ", WARNING,
               "direct os.environ read bypassing the common/env.py knob "
               "parsers (env_int/env_float/env_flag/env_str) — malformed "
               "values crash instead of falling back"),
    "ALK004": ("unlocked-shared-mutation", WARNING,
               "module-level shared dict mutated outside a lock in a "
               "threaded module — executor pool / transfer streams / serving "
               "batchers race on it"),
    "ALK005": ("except-swallow", WARNING,
               "bare except, or broad except whose body only passes — "
               "failures vanish without a counter or log"),
    "ALK006": ("compile-cache-drift", WARNING,
               "direct jax compilation-cache configuration "
               "(jax.config.update('jax_compilation_cache_*'/'jax_"
               "persistent_cache_*') or a raw compilation_cache import) "
               "outside common/jitcache.py — bypasses the one sanctioned "
               "owner (placement by JAX_COMPILATION_CACHE_DIR, persist counters, "
               "corruption fallback, disk LRU cap)"),
    "ALK008": ("unregistered-pallas", WARNING,
               "jax.experimental.pallas import or pl.pallas_call reference "
               "outside alink_tpu/native/ and the modules registered in "
               "native/kernels.py — an unregistered kernel has no knob, no "
               "XLA fallback, no parity contract, and is invisible to the "
               "kernel_candidates() cross-reference"),
    # -- plan validation (pre-flight over user DAGs) -----------------------
    "ALK101": ("missing-column", ERROR,
               "a column named by selectedCols/featureCols/labelCol/... is "
               "absent from the upstream schema"),
    "ALK102": ("dtype-mismatch", ERROR,
               "a column feeding a numeric kernel has a non-numeric type "
               "(e.g. STRING in featureCols)"),
    "ALK103": ("recompile-hazard", WARNING,
               "shape or cache-key hazard: micro-batch size off the "
               "bucket_rows ladder (every chunk pads + first chunk traces a "
               "fresh program), or a kernel closure capturing Unkeyable "
               "state (falls back to per-instance cache keys)"),
    "ALK104": ("missing-snapshot-hook", WARNING,
               "stateful stream op without state_snapshot/state_restore "
               "hooks — the recovery coordinator refuses it at job build"),
    "ALK105": ("fusion-breaker", INFO,
               "a non-fusable op interrupts a linear mapper chain — the run "
               "splits into multiple device programs with host round trips "
               "between them"),
    "ALK106": ("schema-underivable", INFO,
               "static output schema could not be derived for a node; "
               "downstream schema checks were skipped"),
    "ALK107": ("missing-partition-hook", WARNING,
               "stateful stream op without keyed-state hooks "
               "(state_partition/state_merge) in a job that requests "
               "elastic parallelism — its state cannot be redistributed "
               "across a rescale; ElasticStreamJob refuses it at build"),
    "ALK109": ("unpublishable-model-stream", WARNING,
               "stream-train op bound to a ModelStreamPublisher without "
               "state_snapshot/state_restore hooks — after a crash the "
               "retrain diverges from the published version history, so "
               "the republish-bit-identical contract cannot hold"),
    "ALK110": ("fleet-model-without-warmup-sidecar", WARNING,
               "model loaded into a serving fleet without a readable "
               ".ak.warmup.json sidecar — a respawned replica would fall "
               "back to trace-on-first-traffic bring-up, breaking the "
               "fleet's zero-trace steady-state contract (error severity "
               "when the fleet respawns replicas)"),
    "ALK111": ("quantized-load-unproven", WARNING,
               "quantized serving load without a real calibration sample "
               "or with the accuracy band disabled — int8/bf16 numerics "
               "would serve with nothing proving them against the fp32 "
               "baseline (error severity for respawn/recovery loads)"),
    # ALK112 is a source-lint rule despite the 1xx id: the ids are stable
    # for life, and it shipped alongside the fleet observability plane's
    # plan-era siblings — renumbering would orphan baselines.
    "ALK112": ("untraced-frame-send", WARNING,
               "frame-protocol request dict (an {'op': ...} literal in "
               "serving/) built without a 'trace' field — the request "
               "crosses the process boundary invisible to the stitched "
               "waterfall; stamp wire_context() so the replica-side spans "
               "join the caller's trace"),
}


@dataclass
class Diagnostic:
    """One finding: a stable rule id, where, what, and how to fix it."""

    rule: str
    message: str
    # plan diagnostics locate by DAG node ("KMeansTrainBatchOp#2"); lint
    # findings by file:line
    where: str = ""
    severity: str = ""
    hint: str = ""
    path: str = ""
    line: int = 0

    def __post_init__(self):
        if not self.severity:
            self.severity = RULES.get(self.rule, ("", WARNING, ""))[1]

    @property
    def title(self) -> str:
        return RULES.get(self.rule, (self.rule, "", ""))[0]

    def location(self) -> str:
        if self.path:
            return f"{self.path}:{self.line}" if self.line else self.path
        return self.where

    def to_dict(self) -> Dict[str, Any]:
        return {
            "rule": self.rule,
            "title": self.title,
            "severity": self.severity,
            "location": self.location(),
            "message": self.message,
            "hint": self.hint,
        }

    def __str__(self) -> str:
        loc = self.location()
        head = f"{self.rule} [{self.severity}]"
        body = f"{loc}: {self.message}" if loc else self.message
        return f"{head} {body}" + (f"  (fix: {self.hint})" if self.hint else "")


@dataclass
class Report:
    """An ordered batch of diagnostics from one engine run."""

    engine: str = "plan"
    target: str = ""
    diagnostics: List[Diagnostic] = field(default_factory=list)

    def add(self, rule: str, message: str, **kw) -> Diagnostic:
        d = Diagnostic(rule, message, **kw)
        self.diagnostics.append(d)
        return d

    def extend(self, diags) -> None:
        self.diagnostics.extend(diags)

    def errors(self) -> List[Diagnostic]:
        return [d for d in self.diagnostics if d.severity == ERROR]

    def warnings(self) -> List[Diagnostic]:
        return [d for d in self.diagnostics if d.severity == WARNING]

    def infos(self) -> List[Diagnostic]:
        return [d for d in self.diagnostics if d.severity == INFO]

    @property
    def ok(self) -> bool:
        return not self.diagnostics

    def by_rule(self) -> Dict[str, int]:
        out: Dict[str, int] = {}
        for d in self.diagnostics:
            out[d.rule] = out.get(d.rule, 0) + 1
        return out

    def sorted(self) -> List[Diagnostic]:
        return sorted(self.diagnostics,
                      key=lambda d: (_SEV_ORDER.get(d.severity, 9), d.rule,
                                     d.path, d.line, d.where))

    def to_dict(self) -> Dict[str, Any]:
        return {
            "engine": self.engine,
            "target": self.target,
            "counts": {
                "total": len(self.diagnostics),
                "error": len(self.errors()),
                "warning": len(self.warnings()),
                "info": len(self.infos()),
            },
            "by_rule": self.by_rule(),
            "diagnostics": [d.to_dict() for d in self.sorted()],
        }

    def render(self) -> str:
        if self.ok:
            return f"{self.engine}: clean ({self.target})" if self.target \
                else f"{self.engine}: clean"
        lines = [str(d) for d in self.sorted()]
        lines.append(f"{len(self.diagnostics)} finding(s): "
                     f"{len(self.errors())} error(s), "
                     f"{len(self.warnings())} warning(s), "
                     f"{len(self.infos())} info(s)")
        return "\n".join(lines)
