"""alink-lint — AST-based invariant checker over the framework's own source.

The codebase carries invariants that plain review keeps missing (every PR
since PR 1 notes the ``jax.shard_map`` drift; PR 2 built env-knob parsers
that new modules bypass). This linter turns them into machine-checked rules
in the spirit of compiler-level validation (TVM Relay's type checker, XLA's
pre-lowering shape inference — PAPERS.md):

- **ALK001** direct ``jax.jit``/``pjit`` calls outside
  ``common/jitcache.ProgramCache`` — allowed inside ``_build*`` builder
  functions and inside ``cached_jit(...)`` call arguments (the repo's
  builder idiom), and inside ``common/jitcache.py`` itself;
- **ALK002** any direct ``jax.shard_map`` /
  ``jax.experimental.shard_map`` reference outside
  ``parallel/shardmap.py`` — that module is the one sanctioned import
  (``from alink_tpu.parallel.shardmap import shard_map``), so a jax API
  move is a one-file change; the
  migration retired the drift, so the baseline pins this rule at zero and
  ``--shard-map-inventory`` must stay empty;
- **ALK003** raw ``os.environ`` *reads* (``.get``/subscript-load/``in``)
  outside ``common/env.py`` — writes (``setdefault``, assignment, ``del``)
  are allowed, knob *parsing* is what must be centralized;
- **ALK004** mutation of a module-level dict outside a ``with *lock*:``
  block in threaded modules (executor, metrics, serving, ...);
- **ALK005** bare ``except:``, or a broad ``except (Base)Exception:`` whose
  body only passes — swallowed failures with no counter or log;
- **ALK006** direct jax compilation-cache configuration —
  ``jax.config.update("jax_compilation_cache_*" / "jax_persistent_cache_*",
  ...)`` or any raw ``compilation_cache`` import — outside
  ``common/jitcache.py``, the one sanctioned owner of persistent compile
  artifacts (same single-owner shape as ALK002): bypasses the placement
  rule (``JAX_COMPILATION_CACHE_DIR`` or the in-checkout default), the
  ``jit.persist_*`` counters, the corruption fallback, and the on-disk LRU
  cap.

(**ALK000** parse-error, error severity, marks a file ``ast.parse`` rejects —
no other rule could run on it.)

Findings carry stable rule ids + file:line + fix hints. A committed
suppression baseline (per-rule, per-file counts — robust to line drift)
lets the gate start green and ratchet: ``--check`` fails only when a file's
count for a rule GROWS past the baseline.

CLI::

    python -m alink_tpu.analysis.lint            # report findings
    python -m alink_tpu.analysis.lint --check    # exit 1 on non-baselined
    python -m alink_tpu.analysis.lint --write-baseline
    python -m alink_tpu.analysis.lint --shard-map-inventory docs/...json
    python -m alink_tpu.analysis.lint --rules    # print the rule table
"""

from __future__ import annotations

import ast
import json
import os
import sys
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

from .diagnostics import RULES, Diagnostic, Report

# package root (…/alink_tpu) — the default scan target; relpaths in
# findings/baseline are taken against its PARENT so they read
# "alink_tpu/tree/grow.py" exactly as the repo sees them
_PKG_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEFAULT_BASELINE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "lint_baseline.json")

# modules where module-level dicts are hit from worker threads (DAG pool,
# transfer streams, serving batchers, recovery chains) — the ALK004 scope
_THREADED_MODULES = (
    "common/executor.py", "common/metrics.py", "common/jitcache.py",
    "common/staging.py", "common/streaming.py", "common/tracing.py",
    "common/recovery.py", "common/resilience.py", "common/profiling.py",
    "common/faults.py", "common/telemetry.py", "serving/router.py",
    "analysis/plancheck.py",
)

# ALK112 scope: frame-protocol request dicts are built in the serving
# tier (fleet front-end + supervisor broadcast sites)
_SERVING_DIR = "serving/"

# the knob-parser module itself — the one place raw environ reads belong
_ENV_MODULE = "common/env.py"
_JITCACHE_MODULE = "common/jitcache.py"
_SHARDMAP_SHIM = "parallel/shardmap.py"

# ALK008 allow-list: anything under native/ plus the modules the kernel
# registry declares (native/kernels.py stays import-light, so reading the
# list here costs no jax import)
_NATIVE_DIR = "alink_tpu/native/"
try:
    from ..native.kernels import KERNEL_MODULES as _KERNEL_MODULES
except Exception:  # pragma: no cover — lint must run even mid-refactor
    _KERNEL_MODULES = ()

_PALLAS_HINT = ("implement the kernel in a module registered in "
                "alink_tpu/native/kernels.py (knob + fallback + parity "
                "contract), following docs/kernels.md")

_MUTATORS = ("update", "setdefault", "pop", "popitem", "clear")

# jax config names ALK006 treats as compile-cache configuration — writing
# any of them outside common/jitcache.py bypasses the sanctioned owner
_CACHE_CONFIG_PREFIXES = ("jax_compilation_cache", "jax_persistent_cache")

# every spelling of "build me a compiled program" ALK001 polices — the call
# form, the bare-decorator form, and the functools.partial decorator form
_JIT_NAMES = ("jax.jit", "pjit", "jax.pjit", "pjit.pjit",
              "jax.experimental.pjit.pjit")


def _dotted(node: ast.AST) -> str:
    """Best-effort dotted name of an expression ('jax.jit', 'os.environ')."""
    if isinstance(node, ast.Name):
        return node.id
    if isinstance(node, ast.Attribute):
        base = _dotted(node.value)
        return f"{base}.{node.attr}" if base else node.attr
    if isinstance(node, ast.Call):
        return _dotted(node.func)
    return ""


def _is_environ(node: ast.AST) -> bool:
    d = _dotted(node)
    return d in ("os.environ", "environ")


def _lock_like(expr: ast.AST) -> bool:
    return "lock" in _dotted(expr).lower()


class _FileLinter(ast.NodeVisitor):
    def __init__(self, relpath: str, tree: ast.Module):
        self.relpath = relpath
        self.findings: List[Diagnostic] = []
        self.func_stack: List[str] = []
        self.lock_depth = 0
        self.cached_jit_depth = 0
        self._decorator_handled: set = set()
        self.is_env_module = relpath.endswith(_ENV_MODULE)
        self.is_jitcache = relpath.endswith(_JITCACHE_MODULE)
        self.is_shardmap_shim = relpath.endswith(_SHARDMAP_SHIM)
        self.is_kernel_module = _NATIVE_DIR in relpath or any(
            relpath.endswith(m) for m in _KERNEL_MODULES)
        self.is_serving = f"/{_SERVING_DIR}" in relpath \
            or relpath.startswith(_SERVING_DIR)
        self.threaded = any(relpath.endswith(m) for m in _THREADED_MODULES)
        self.shared_dicts = self._module_dicts(tree) if self.threaded else set()

    @staticmethod
    def _module_dicts(tree: ast.Module) -> set:
        """Names bound at module level to dict-like containers."""
        names: set = set()
        for stmt in tree.body:
            targets: List[ast.expr] = []
            value: Optional[ast.expr] = None
            if isinstance(stmt, ast.Assign):
                targets, value = stmt.targets, stmt.value
            elif isinstance(stmt, ast.AnnAssign) and stmt.value is not None:
                targets, value = [stmt.target], stmt.value
            if value is None:
                continue
            is_dict = isinstance(value, ast.Dict) or (
                isinstance(value, ast.Call)
                and _dotted(value.func).split(".")[-1]
                in ("dict", "OrderedDict", "defaultdict"))
            if not is_dict:
                continue
            for t in targets:
                if isinstance(t, ast.Name):
                    names.add(t.id)
        return names

    # -- finding helper ----------------------------------------------------
    def _add(self, rule: str, node: ast.AST, message: str, hint: str = ""):
        self.findings.append(Diagnostic(
            rule, message, hint=hint, path=self.relpath,
            line=getattr(node, "lineno", 0)))

    # -- context tracking --------------------------------------------------
    def visit_FunctionDef(self, node):
        # Decorators apply in the ENCLOSING scope, so every jit decorator
        # form — bare `@jax.jit`, call `@jax.jit(...)`, and
        # `@partial(jax.jit, ...)` — is judged BEFORE this function's name
        # lands on the stack: a jit-decorated `_build_x` is itself a
        # compiled program, not a builder. Handled Call decorators are
        # remembered so visit_Call (which sees them during generic_visit,
        # with the name pushed) never re-judges them in the wrong scope.
        exempt = self.is_jitcache or self._in_builder() \
            or bool(self.cached_jit_depth)
        for dec in node.decorator_list:
            if isinstance(dec, (ast.Name, ast.Attribute)) \
                    and _dotted(dec) in _JIT_NAMES:
                if not exempt:
                    self._add(
                        "ALK001", dec,
                        f"direct @{_dotted(dec)} decorator outside a "
                        "ProgramCache builder — the compiled program is "
                        "rebuilt (and jax's dispatch cache discarded) every "
                        "time this code path re-runs",
                        hint="wrap in a _build*() builder registered via "
                             "common/jitcache.cached_jit")
            elif isinstance(dec, ast.Call) and \
                    isinstance(dec.func, (ast.Name, ast.Attribute)):
                d = _dotted(dec.func)
                if d in _JIT_NAMES:
                    self._decorator_handled.add(id(dec))
                    if not exempt:
                        self._add(
                            "ALK001", dec,
                            f"direct {d}() call outside a ProgramCache "
                            "builder — the compiled program is rebuilt (and "
                            "jax's dispatch cache discarded) every time "
                            "this code path re-runs",
                            hint="wrap in a _build*() builder registered "
                                 "via common/jitcache.cached_jit")
                elif d.split(".")[-1] == "partial" and dec.args \
                        and isinstance(dec.args[0],
                                       (ast.Name, ast.Attribute)) \
                        and _dotted(dec.args[0]) in _JIT_NAMES:
                    self._decorator_handled.add(id(dec))
                    if not exempt:
                        self._add(
                            "ALK001", dec,
                            f"partial({_dotted(dec.args[0])}, ...) outside "
                            "a ProgramCache builder — the compiled program "
                            "is rebuilt (and jax's dispatch cache "
                            "discarded) every time this code path re-runs",
                            hint="wrap in a _build*() builder registered "
                                 "via common/jitcache.cached_jit")
        self.func_stack.append(node.name)
        self.generic_visit(node)
        self.func_stack.pop()

    visit_AsyncFunctionDef = visit_FunctionDef

    def visit_With(self, node: ast.With):
        locked = any(_lock_like(item.context_expr) for item in node.items)
        self.lock_depth += 1 if locked else 0
        self.generic_visit(node)
        self.lock_depth -= 1 if locked else 0

    def _in_builder(self) -> bool:
        return any(f.startswith("_build") for f in self.func_stack)

    # -- ALK001/ALK002/ALK003 calls & attributes ---------------------------
    def visit_Call(self, node: ast.Call):
        if id(node) in self._decorator_handled:
            # already judged (in the enclosing scope) by visit_FunctionDef
            self.generic_visit(node)
            return
        # only direct Name/Attribute callees: `jax.jit(f)(x)` is one direct
        # jit call, not two (the outer call invokes the returned function)
        d = _dotted(node.func) \
            if isinstance(node.func, (ast.Name, ast.Attribute)) else ""
        tail = d.split(".")[-1]
        if tail == "cached_jit":
            # jit built inside a cached_jit(...) argument (the inline
            # `lambda: jax.jit(run)` idiom) registers with the ProgramCache
            self.cached_jit_depth += 1
            self.generic_visit(node)
            self.cached_jit_depth -= 1
            return
        if d in _JIT_NAMES \
                and not self.is_jitcache and not self._in_builder() \
                and not self.cached_jit_depth:
            self._add(
                "ALK001", node,
                f"direct {d}() call outside a ProgramCache builder — the "
                "compiled program is rebuilt (and jax's dispatch cache "
                "discarded) every time this code path re-runs",
                hint="wrap in a _build*() builder registered via "
                     "common/jitcache.cached_jit")
        if tail == "partial" and node.args \
                and isinstance(node.args[0], (ast.Name, ast.Attribute)) \
                and _dotted(node.args[0]) in _JIT_NAMES \
                and not self.is_jitcache and not self._in_builder() \
                and not self.cached_jit_depth:
            # `@partial(jax.jit, donate_argnums=...)` — the decorator form
            # jit-with-options takes; same rebuild-per-run failure mode
            self._add(
                "ALK001", node,
                f"partial({_dotted(node.args[0])}, ...) outside a "
                "ProgramCache builder — the compiled program is rebuilt "
                "(and jax's dispatch cache discarded) every time this code "
                "path re-runs",
                hint="wrap in a _build*() builder registered via "
                     "common/jitcache.cached_jit")
        if tail == "update" and d.endswith("config.update") and node.args \
                and isinstance(node.args[0], ast.Constant) \
                and isinstance(node.args[0].value, str) \
                and node.args[0].value.startswith(_CACHE_CONFIG_PREFIXES) \
                and not self.is_jitcache:
            self._add(
                "ALK006", node,
                f"direct {d}({node.args[0].value!r}, ...) outside "
                "common/jitcache.py — compile-cache configuration bypasses "
                "the sanctioned owner (no persist counters, no corruption "
                "fallback, no disk LRU cap)",
                hint="route through common/jitcache.enable_persistent_cache "
                     "(placed by JAX_COMPILATION_CACHE_DIR)")
        if tail == "get" and isinstance(node.func, ast.Attribute) \
                and _is_environ(node.func.value) and not self.is_env_module:
            self._add(
                "ALK003", node,
                "raw os.environ.get() — knob parsing bypasses "
                "common/env.py (malformed values crash instead of "
                "falling back)",
                hint="use env_int/env_float/env_flag/env_str from "
                     "alink_tpu.common.env")
        if d in ("os.getenv", "getenv") and not self.is_env_module:
            self._add(
                "ALK003", node,
                "raw os.getenv() — knob parsing bypasses common/env.py "
                "(malformed values crash instead of falling back)",
                hint="use env_int/env_float/env_flag/env_str from "
                     "alink_tpu.common.env")
        self.generic_visit(node)

    def visit_Attribute(self, node: ast.Attribute):
        # flag `jax.shard_map` and `jax.experimental.shard_map` at the
        # INNERMOST matching attribute only, so the full
        # `jax.experimental.shard_map.shard_map(...)` chain reports once
        if node.attr == "shard_map" \
                and _dotted(node.value) in ("jax", "jax.experimental") \
                and not self.is_shardmap_shim:
            self._add(
                "ALK002", node,
                f"direct {_dotted(node)} reference — bypasses "
                "parallel/shardmap.py, the one module that follows jax's "
                "shard_map API",
                hint="from alink_tpu.parallel.shardmap import shard_map "
                     "(the one sanctioned import)")
        # jax.experimental.pallas attribute chains (innermost match, same
        # single-report shape as ALK002); pl.pallas_call catches call sites
        # whose import dodged the import rules (e.g. importlib)
        if not self.is_kernel_module and (
                (node.attr == "pallas"
                 and _dotted(node.value) == "jax.experimental")
                or (node.attr == "pallas_call"
                    # full chains report once, at the inner pallas attr
                    and "jax.experimental" not in _dotted(node.value))):
            self._add(
                "ALK008", node,
                f"direct {_dotted(node)} reference outside a registered "
                "kernel module — unregistered Pallas kernels have no knob, "
                "no fallback, and no parity contract",
                hint=_PALLAS_HINT)
        self.generic_visit(node)

    def visit_Import(self, node: ast.Import):
        for alias in node.names:
            if "shard_map" in alias.name and not self.is_shardmap_shim:
                self._add(
                    "ALK002", node,
                    f"import {alias.name} — shard_map drift",
                    hint="from alink_tpu.parallel.shardmap import "
                         "shard_map (the one sanctioned import)")
            if "compilation_cache" in alias.name and not self.is_jitcache:
                self._add(
                    "ALK006", node,
                    f"import {alias.name} — compile-cache drift",
                    hint="use common/jitcache (enable_persistent_cache / "
                         "persist_summary / prune_persistent_cache), the "
                         "one sanctioned owner")
            if "pallas" in alias.name and "jax" in alias.name \
                    and not self.is_kernel_module:
                self._add(
                    "ALK008", node,
                    f"import {alias.name} — Pallas outside a registered "
                    "kernel module",
                    hint=_PALLAS_HINT)
        self.generic_visit(node)

    def visit_ImportFrom(self, node: ast.ImportFrom):
        mod = node.module or ""
        drift = "shard_map" in mod or (
            mod.startswith("jax")
            and any("shard_map" in a.name for a in node.names))
        if drift and not self.is_shardmap_shim:
            names = ", ".join(a.name for a in node.names)
            self._add(
                "ALK002", node,
                f"from {mod} import {names} — shard_map drift",
                hint="from alink_tpu.parallel.shardmap import shard_map "
                     "(the one sanctioned import)")
        cache_drift = "compilation_cache" in mod or (
            mod.startswith("jax")
            and any("compilation_cache" in a.name for a in node.names))
        if cache_drift and not self.is_jitcache:
            names = ", ".join(a.name for a in node.names)
            self._add(
                "ALK006", node,
                f"from {mod} import {names} — compile-cache drift",
                hint="use common/jitcache (enable_persistent_cache / "
                     "persist_summary / prune_persistent_cache), the one "
                     "sanctioned owner")
        # jax pallas only: relative imports of the registered *_pallas
        # wrapper modules (their public entry points) are the sanctioned
        # integration idiom and carry no pl.pallas_call themselves
        pallas_drift = mod.startswith("jax") and (
            "pallas" in mod
            or any("pallas" in a.name for a in node.names))
        if pallas_drift and not self.is_kernel_module:
            names = ", ".join(a.name for a in node.names)
            self._add(
                "ALK008", node,
                f"from {mod} import {names} — Pallas outside a registered "
                "kernel module",
                hint=_PALLAS_HINT)
        self.generic_visit(node)

    def visit_Subscript(self, node: ast.Subscript):
        if _is_environ(node.value) and isinstance(node.ctx, ast.Load) \
                and not self.is_env_module:
            self._add(
                "ALK003", node,
                "raw os.environ[...] read outside common/env.py",
                hint="use env_int/env_float/env_flag/env_str from "
                     "alink_tpu.common.env")
        self._check_shared_mutation(node)
        self.generic_visit(node)

    def visit_Compare(self, node: ast.Compare):
        if any(isinstance(op, (ast.In, ast.NotIn)) for op in node.ops) \
                and any(_is_environ(c) for c in node.comparators) \
                and not self.is_env_module:
            self._add(
                "ALK003", node,
                "membership probe on os.environ outside common/env.py",
                hint="use env_str(name, None) is not None, or an env_* "
                     "helper with a default")
        self.generic_visit(node)

    # -- ALK004 shared-dict mutation ---------------------------------------
    def _check_shared_mutation(self, node: ast.Subscript):
        if not self.shared_dicts or self.lock_depth or not self.func_stack:
            return
        if isinstance(node.ctx, (ast.Store, ast.Del)) \
                and isinstance(node.value, ast.Name) \
                and node.value.id in self.shared_dicts:
            self._add(
                "ALK004", node,
                f"module-level dict {node.value.id!r} mutated outside a "
                "lock in a threaded module",
                hint="take the module's lock (with _lock:) around the "
                     "mutation, or make the structure thread-confined")

    def visit_Expr(self, node: ast.Expr):
        if self.shared_dicts and not self.lock_depth and self.func_stack \
                and isinstance(node.value, ast.Call) \
                and isinstance(node.value.func, ast.Attribute) \
                and node.value.func.attr in _MUTATORS \
                and isinstance(node.value.func.value, ast.Name) \
                and node.value.func.value.id in self.shared_dicts:
            self._add(
                "ALK004", node,
                f"module-level dict {node.value.func.value.id!r}."
                f"{node.value.func.attr}() outside a lock in a threaded "
                "module",
                hint="take the module's lock around the mutation")
        self.generic_visit(node)

    # -- ALK112 untraced frame-protocol sends ------------------------------
    def visit_Dict(self, node: ast.Dict):
        # a frame-protocol request is an {'op': ...} dict literal; in the
        # serving tier every one must carry a 'trace' field so the
        # replica-side spans stitch into the caller's waterfall. A dict
        # spread (**base, key is None) may supply it — can't prove absence
        # statically, so those are skipped rather than false-positived.
        if self.is_serving and not any(k is None for k in node.keys):
            consts = {k.value for k in node.keys
                      if isinstance(k, ast.Constant)
                      and isinstance(k.value, str)}
            if "op" in consts and "trace" not in consts:
                self._add(
                    "ALK112", node,
                    "frame-protocol request dict built without a 'trace' "
                    "field — the request crosses the process boundary "
                    "invisible to the stitched trace",
                    hint="add \"trace\": wire_context() "
                         "(common/tracing.py); replicas adopt it around "
                         "the dispatched op")
        self.generic_visit(node)

    # -- ALK005 except swallows --------------------------------------------
    def visit_ExceptHandler(self, node: ast.ExceptHandler):
        if node.type is None:
            self._add(
                "ALK005", node,
                "bare except: catches SystemExit/KeyboardInterrupt too",
                hint="catch Exception (or a narrower class) and count/log "
                     "the failure")
        else:
            broad = _dotted(node.type) in ("Exception", "BaseException")
            only_pass = all(
                isinstance(s, ast.Pass)
                or (isinstance(s, ast.Expr)
                    and isinstance(s.value, ast.Constant))
                for s in node.body)
            if broad and only_pass:
                self._add(
                    "ALK005", node,
                    f"except {_dotted(node.type)}: pass — the failure "
                    "vanishes without a counter or log",
                    hint="count it (metrics.incr) or log at debug; "
                         "narrow the exception class where possible")
        self.generic_visit(node)


# ---------------------------------------------------------------------------
# Running the linter
# ---------------------------------------------------------------------------


def iter_python_files(root: str) -> List[str]:
    out: List[str] = []
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames[:] = sorted(d for d in dirnames
                             if d not in ("__pycache__", ".git"))
        for f in sorted(filenames):
            if f.endswith(".py"):
                out.append(os.path.join(dirpath, f))
    return out


def lint_file(path: str, rel_base: Optional[str] = None) -> List[Diagnostic]:
    rel_base = rel_base or os.path.dirname(_PKG_DIR)
    rel = os.path.relpath(os.path.abspath(path), rel_base).replace(
        os.sep, "/")
    with open(path, "r", encoding="utf-8") as f:
        src = f.read()
    try:
        tree = ast.parse(src, filename=path)
    except SyntaxError as e:
        d = Diagnostic("ALK000", f"file does not parse: {e}", path=rel,
                       line=e.lineno or 0, severity="error")
        return [d]
    linter = _FileLinter(rel, tree)
    linter.visit(tree)
    return linter.findings


def run_lint(paths: Optional[Sequence[str]] = None,
             rel_base: Optional[str] = None) -> Report:
    """Lint ``paths`` (files or directories; default: the installed
    alink_tpu package) and return one Report. Counts land in the
    ``analysis.lint_*`` metrics so drift is observable at ``/metrics``."""
    from ..common.metrics import metrics

    targets: List[str] = []
    for p in (paths or [_PKG_DIR]):
        if os.path.isdir(p):
            targets.extend(iter_python_files(p))
        else:
            targets.append(p)
    report = Report(engine="lint", target=f"{len(targets)} files")
    for path in targets:
        report.extend(lint_file(path, rel_base=rel_base))
    metrics.incr("analysis.lint_runs")
    metrics.incr("analysis.lint_findings", len(report.diagnostics))
    for rule, n in report.by_rule().items():
        metrics.incr(f"analysis.rule.{rule}", n)
    return report


# ---------------------------------------------------------------------------
# Suppression baseline (per-rule, per-file counts — a ratchet)
# ---------------------------------------------------------------------------


def baseline_counts(report: Report) -> Dict[str, Dict[str, int]]:
    counts: Dict[str, Dict[str, int]] = {}
    for d in report.diagnostics:
        counts.setdefault(d.rule, {})
        counts[d.rule][d.path] = counts[d.rule].get(d.path, 0) + 1
    return {r: dict(sorted(files.items()))
            for r, files in sorted(counts.items())}


def load_baseline(path: str = DEFAULT_BASELINE) -> Dict[str, Dict[str, int]]:
    if not os.path.exists(path):
        return {}
    try:
        with open(path, "r", encoding="utf-8") as f:
            blob = json.load(f)
        return blob.get("counts", {})
    except (OSError, ValueError):
        return {}


def write_baseline(report: Report, path: str = DEFAULT_BASELINE) -> None:
    blob = {
        "comment": "alink-lint suppression baseline: per-rule per-file "
                   "finding counts. --check fails only when a count GROWS; "
                   "shrink it by fixing findings then --write-baseline.",
        "counts": baseline_counts(report),
    }
    with open(path, "w", encoding="utf-8") as f:
        json.dump(blob, f, indent=2, sort_keys=True)
        f.write("\n")


def check_against_baseline(
        report: Report,
        baseline: Dict[str, Dict[str, int]]) -> List[Tuple[str, str, int, int]]:
    """Regressions vs the baseline: (rule, file, found, allowed) for every
    (rule, file) whose finding count exceeds its baselined allowance."""
    regressions: List[Tuple[str, str, int, int]] = []
    for rule, files in baseline_counts(report).items():
        for path, n in files.items():
            allowed = int(baseline.get(rule, {}).get(path, 0))
            if n > allowed:
                regressions.append((rule, path, n, allowed))
    return regressions


# ---------------------------------------------------------------------------
# shard_map drift inventory (ROADMAP Open item 3 work-list)
# ---------------------------------------------------------------------------


def shard_map_inventory(report: Optional[Report] = None) -> Dict[str, Any]:
    """Machine-readable inventory of every ``jax.shard_map`` call site the
    ALK002 rule finds — the migration work-list for ROADMAP Open item 3."""
    report = report or run_lint()
    modules: Dict[str, Dict[str, Any]] = {}
    for d in report.diagnostics:
        if d.rule != "ALK002":
            continue
        m = modules.setdefault(d.path, {"count": 0, "lines": []})
        m["count"] += 1
        m["lines"].append(d.line)
    for m in modules.values():
        m["lines"].sort()
    total = sum(m["count"] for m in modules.values())
    return {
        "generated_by": "python -m alink_tpu.analysis.lint "
                        "--shard-map-inventory",
        "rule": "ALK002",
        "roadmap_item": 3,
        "total_call_sites": total,
        "modules": dict(sorted(modules.items())),
    }


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------


def main(argv: Optional[Sequence[str]] = None) -> int:
    import argparse

    ap = argparse.ArgumentParser(
        prog="python -m alink_tpu.analysis.lint",
        description="alink-lint: framework invariant checker")
    ap.add_argument("paths", nargs="*",
                    help="files/dirs to lint (default: the alink_tpu "
                         "package)")
    ap.add_argument("--check", action="store_true",
                    help="exit 1 on findings not covered by the baseline")
    ap.add_argument("--baseline", default=DEFAULT_BASELINE,
                    help="baseline JSON path")
    ap.add_argument("--write-baseline", action="store_true",
                    help="write the current findings as the new baseline")
    ap.add_argument("--format", choices=("text", "json"), default="text")
    ap.add_argument("--shard-map-inventory", metavar="OUT.json",
                    help="write the ALK002 drift inventory and exit")
    ap.add_argument("--rules", action="store_true",
                    help="print the rule table and exit")
    args = ap.parse_args(argv)

    if args.rules:
        for rid, (title, sev, desc) in sorted(RULES.items()):
            print(f"{rid}  {title:28s} [{sev}] {desc}")
        return 0

    report = run_lint(args.paths or None)

    if args.shard_map_inventory:
        inv = shard_map_inventory(report)
        with open(args.shard_map_inventory, "w", encoding="utf-8") as f:
            json.dump(inv, f, indent=2)
            f.write("\n")
        print(f"wrote {inv['total_call_sites']} shard_map call sites in "
              f"{len(inv['modules'])} modules to "
              f"{args.shard_map_inventory}")
        return 0

    if args.write_baseline:
        write_baseline(report, args.baseline)
        print(f"baseline written: {args.baseline} "
              f"({len(report.diagnostics)} findings suppressed)")
        return 0

    if args.format == "json":
        print(json.dumps(report.to_dict(), indent=2))
    else:
        print(report.render())

    if args.check:
        regressions = check_against_baseline(
            report, load_baseline(args.baseline))
        if regressions:
            print("\nnon-baselined findings (fix them or refresh the "
                  "baseline deliberately):")
            for rule, path, n, allowed in regressions:
                print(f"  {rule} {path}: {n} found, {allowed} baselined")
            return 1
        print("\nlint check: OK (all findings baselined)")
    return 0


if __name__ == "__main__":  # pragma: no cover — CLI entry
    sys.exit(main())
