"""Static-analysis layer: plan-time validator + alink-lint.

Container-safe: plan-validator pipelines use StandardScaler +
VectorAssembler + NaiveBayes and block-kernel mapper DAGs only (no
shard_map fit paths); lint tests run on temp files plus one self-lint of
the installed package against the committed baseline.
"""

from __future__ import annotations

import json
import os
import textwrap

import numpy as np
import pytest

from alink_tpu.analysis import (
    RULES,
    Report,
    last_plan_report,
    validate_plan,
    validation_mode,
)
from alink_tpu.analysis.lint import (
    DEFAULT_BASELINE,
    check_against_baseline,
    lint_file,
    load_baseline,
    main as lint_main,
    run_lint,
    shard_map_inventory,
)
from alink_tpu.common.exceptions import AkPlanValidationException
from alink_tpu.common.metrics import metrics
from alink_tpu.common.mtable import AlinkTypes, MTable

pytestmark = pytest.mark.analysis

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# Fixtures
# ---------------------------------------------------------------------------


def _train_table(n_per_class: int = 30) -> MTable:
    rng = np.random.RandomState(0)
    X = np.concatenate([rng.normal(c, 0.4, size=(n_per_class, 4))
                        for c in [(0, 0, 0, 0), (2, 2, 2, 2)]])
    y = np.repeat(["neg", "pos"], n_per_class)
    return MTable({f"f{i}": X[:, i] for i in range(4)}).with_column(
        "label", y)


FEATS = ["f0", "f1", "f2", "f3"]


def _nb_pipeline(**overrides):
    from alink_tpu.pipeline import (NaiveBayes, Pipeline, StandardScaler,
                                    VectorAssembler)

    kw = dict(scaler_cols=FEATS, assemble_cols=FEATS, vector_col="vec",
              label_col="label")
    kw.update(overrides)
    return Pipeline(
        StandardScaler(selectedCols=kw["scaler_cols"]),
        VectorAssembler(selectedCols=kw["assemble_cols"], outputCol="vec"),
        NaiveBayes(vectorCol=kw["vector_col"], labelCol=kw["label_col"],
                   predictionCol="pred"),
    )


def _rules(report) -> dict:
    return report.by_rule()


# ---------------------------------------------------------------------------
# Plan validator — clean plan + the five seeded defect classes
# ---------------------------------------------------------------------------


def test_clean_pipeline_no_diagnostics():
    rep = validate_plan(_nb_pipeline(), _train_table())
    assert rep.ok, rep.render()


def test_pipeline_simulation_truncation_visible_alk106():
    # a stage the simulation cannot model truncates the walk — that must
    # surface as an info diagnostic, never read as "fully validated clean"
    from alink_tpu.pipeline.base import TransformerBase

    class OpaqueTransformer(TransformerBase):
        _map_op_cls = None

    p = _nb_pipeline()
    p.stages.insert(1, OpaqueTransformer())
    rep = validate_plan(p, _train_table())
    assert any(d.rule == "ALK106" and "stopped at stage 1" in d.message
               for d in rep.diagnostics), rep.render()


def test_seeded_missing_column_alk101():
    rep = validate_plan(_nb_pipeline(assemble_cols=FEATS + ["nope"]),
                        _train_table())
    assert _rules(rep) == {"ALK101": 1}
    d = rep.diagnostics[0]
    assert d.severity == "error" and "nope" in d.message
    assert "VectorAssembler" in d.where


def test_seeded_dtype_mismatch_alk102():
    # STRING label column fed to the scaler's moment kernel
    rep = validate_plan(_nb_pipeline(scaler_cols=FEATS + ["label"]),
                        _train_table())
    assert _rules(rep) == {"ALK102": 1}
    assert rep.diagnostics[0].severity == "error"
    # numeric column where a vector is expected (train + predict op flag it)
    rep2 = validate_plan(_nb_pipeline(vector_col="f0"), _train_table())
    assert set(_rules(rep2)) == {"ALK102"}


def test_seeded_off_ladder_chunk_alk103():
    from alink_tpu.common.jitcache import bucket_rows
    from alink_tpu.operator.stream.base import TableSourceStreamOp

    assert bucket_rows(37) != 37  # the seed is genuinely off-ladder
    src = TableSourceStreamOp(_train_table(), chunkSize=37)
    rep = validate_plan(src)
    assert _rules(rep) == {"ALK103": 1}
    assert "37" in rep.diagnostics[0].message
    # on-ladder chunk size is clean
    assert validate_plan(
        TableSourceStreamOp(_train_table(), chunkSize=32)).ok


def test_seeded_missing_snapshot_hook_alk104():
    from alink_tpu.operator.stream.base import TableSourceStreamOp
    from alink_tpu.operator.stream.windows import WindowGroupByStreamOp

    t = MTable({"ts": np.arange(40, dtype=np.float64),
                "v": np.arange(40, dtype=np.float64)})
    w = WindowGroupByStreamOp(
        timeCol="ts", windowSize=5.0, selectClause="sum(v) as s"
    ).link_from(TableSourceStreamOp(t))
    rep = validate_plan(w)
    assert _rules(rep) == {"ALK104": 1}
    assert rep.diagnostics[0].severity == "warning"
    # under the recovery coordinator the same finding is an error
    rep_r = validate_plan(w, recovery=True)
    assert [d.severity for d in rep_r.diagnostics
            if d.rule == "ALK104"] == ["error"]
    # hooked window ops are clean (tumble has snapshot hooks since PR 3)
    from alink_tpu.operator.stream.windows import TumbleTimeWindowStreamOp

    hooked = TumbleTimeWindowStreamOp(
        timeCol="ts", windowSize=5.0, selectClause="sum(v) as s"
    ).link_from(TableSourceStreamOp(t))
    assert "ALK104" not in _rules(validate_plan(hooked))


class _AffineMapper:
    pass


def _affine_op_classes():
    from alink_tpu.mapper.base import BlockKernelMapper
    from alink_tpu.operator.batch.utils import MapBatchOp

    class AffMapper(BlockKernelMapper):
        def kernel(self, input_schema):
            def fn(X):
                return X * 2.0

            return ["x"], ["x2"], [AlinkTypes.DOUBLE], fn

    class AffOp(MapBatchOp):
        mapper_cls = AffMapper

    class NonFusableOp(AffOp):
        def _execute_impl(self, t):  # custom body => executor cannot fuse
            return super()._execute_impl(t)

    return AffOp, NonFusableOp


def test_seeded_fusion_breaker_alk105():
    from alink_tpu.operator.batch.base import MemSourceBatchOp

    AffOp, NonFusableOp = _affine_op_classes()
    src = MemSourceBatchOp([(1.0,), (2.0,)], "x DOUBLE")
    tail = NonFusableOp().link_from(AffOp().link_from(src))
    rep = validate_plan(tail)
    assert _rules(rep) == {"ALK105": 1}
    assert rep.diagnostics[0].severity == "info"
    # an all-fusable chain is clean
    tail2 = AffOp().link_from(AffOp().link_from(src))
    assert validate_plan(tail2).ok


def test_seeded_unkeyable_capture_alk103():
    from alink_tpu.mapper.base import BlockKernelMapper
    from alink_tpu.operator.batch.base import MemSourceBatchOp
    from alink_tpu.operator.batch.utils import MapBatchOp

    class UnkeyableMapper(BlockKernelMapper):
        def kernel(self, input_schema):
            handle = open(os.devnull)  # closure capture with no content key

            def fn(X):
                _ = handle
                return X + 1.0

            return ["x"], ["y"], [AlinkTypes.DOUBLE], fn

    class UnkeyableOp(MapBatchOp):
        mapper_cls = UnkeyableMapper

    src = MemSourceBatchOp([(1.0,), (2.0,)], "x DOUBLE")
    rep = validate_plan(UnkeyableOp().link_from(src))
    assert _rules(rep) == {"ALK103": 1}
    assert "content-hash" in rep.diagnostics[0].message


def test_schema_underivable_alk106_is_info_only():
    from alink_tpu.operator.batch.base import MemSourceBatchOp

    src = MemSourceBatchOp([(1.0,), (2.0,)], "x DOUBLE")
    bad = src.apply_func(lambda t: (_ for _ in ()).throw(ValueError("boom")),
                         name="boom")  # zero-row probe fails
    rep = validate_plan(bad)
    assert _rules(rep) == {"ALK106": 1}
    assert rep.diagnostics[0].severity == "info"


def test_custom_arity_mapper_op_columns_not_checked():
    """A mapper subclass with a custom _execute_impl / non-stock arity may
    bind columns against ANY input — the validator must not flag its column
    params against a guessed data edge (review regression)."""
    from alink_tpu.operator.batch.base import MemSourceBatchOp
    from alink_tpu.operator.batch.utils import ModelMapBatchOp

    class TwoInputJoinOp(ModelMapBatchOp):
        _min_inputs = 2
        _max_inputs = 2

        def _execute_impl(self, left, right):  # custom join-form body
            return right

    left = MemSourceBatchOp([(1, "k")], "id INT, k STRING")
    right = MemSourceBatchOp([(2.0, 3.0)], "note DOUBLE, v DOUBLE")
    op = TwoInputJoinOp(reservedCols=["note"]).link_from(left, right)
    rep = validate_plan(op)
    assert "ALK101" not in rep.by_rule(), rep.render()


# ---------------------------------------------------------------------------
# Mode wiring: off / warn / error
# ---------------------------------------------------------------------------


def test_validation_mode_default_off_and_typo_safe(monkeypatch):
    monkeypatch.delenv("ALINK_VALIDATE_PLAN", raising=False)
    assert validation_mode() == "off"
    monkeypatch.setenv("ALINK_VALIDATE_PLAN", "WARN")
    assert validation_mode() == "warn"
    monkeypatch.setenv("ALINK_VALIDATE_PLAN", "bananas")
    assert validation_mode() == "off"


def _bad_scaler_op():
    from alink_tpu.operator.batch.base import MemSourceBatchOp
    from alink_tpu.operator.batch.feature import StandardScalerTrainBatchOp

    src = MemSourceBatchOp([(1.0,), (2.0,)], "x DOUBLE")
    return StandardScalerTrainBatchOp(selectedCols=["zzz"]).link_from(src)


def test_error_mode_raises_preflight(monkeypatch):
    monkeypatch.setenv("ALINK_VALIDATE_PLAN", "error")
    with pytest.raises(AkPlanValidationException) as ei:
        _bad_scaler_op().collect()
    assert "ALK101" in str(ei.value)
    assert ei.value.report.errors()


def test_warn_mode_does_not_preempt(monkeypatch):
    # warn must never fail the job at pre-flight: the (real) runtime error
    # still surfaces, exactly as with validation off
    monkeypatch.setenv("ALINK_VALIDATE_PLAN", "warn")
    before = metrics.counter("analysis.plan_runs")
    with pytest.raises(Exception) as ei:
        _bad_scaler_op().collect()
    assert not isinstance(ei.value, AkPlanValidationException)
    assert metrics.counter("analysis.plan_runs") > before
    rep = last_plan_report()
    assert rep is not None and rep["mode"] == "warn"
    assert any(d["rule"] == "ALK101" for d in rep["diagnostics"])


def test_off_mode_skips_validation(monkeypatch):
    monkeypatch.delenv("ALINK_VALIDATE_PLAN", raising=False)
    before = metrics.counter("analysis.plan_runs")
    from alink_tpu.operator.batch.base import MemSourceBatchOp

    MemSourceBatchOp([(1.0,)], "x DOUBLE").collect()
    assert metrics.counter("analysis.plan_runs") == before


def test_pipeline_fit_validates_once_keeps_full_report(monkeypatch):
    # Pipeline.fit validates the whole simulated pipeline ONCE up front;
    # the per-stage execute() pre-flights are suppressed so a partial
    # sub-DAG walk neither triple-counts analysis.plan_runs nor overwrites
    # the full-pipeline report with a clean partial one
    monkeypatch.setenv("ALINK_VALIDATE_PLAN", "warn")
    before = metrics.counter("analysis.plan_runs")
    _nb_pipeline().fit(_train_table())
    assert metrics.counter("analysis.plan_runs") == before + 1
    rep = last_plan_report()
    assert rep is not None and rep["target"] == "Pipeline"


def test_pipeline_fit_error_mode(monkeypatch):
    monkeypatch.setenv("ALINK_VALIDATE_PLAN", "error")
    with pytest.raises(AkPlanValidationException):
        _nb_pipeline(assemble_cols=FEATS + ["nope"]).fit(_train_table())


def test_warn_mode_bit_parity(monkeypatch):
    """ALINK_VALIDATE_PLAN=warn never changes results (CI-pinned)."""

    def run():
        t = _train_table()
        model = _nb_pipeline().fit(t)
        return np.asarray(model.transform(t).collect().col("pred"))

    monkeypatch.delenv("ALINK_VALIDATE_PLAN", raising=False)
    p_off = run()
    monkeypatch.setenv("ALINK_VALIDATE_PLAN", "warn")
    p_warn = run()
    assert np.array_equal(p_off, p_warn)


def test_recovery_build_preflight_escalates_alk104(monkeypatch, tmp_path):
    """RecoverableStreamJob wires preflight(recovery=True): under error
    mode an unhooked stateful op fails with the structured report, before
    the coordinator's own bare refusal."""
    from alink_tpu.common.recovery import RecoverableStreamJob
    from alink_tpu.operator.stream.base import TableSourceStreamOp
    from alink_tpu.operator.stream.windows import WindowGroupByStreamOp

    t = MTable({"ts": np.arange(8, dtype=np.float64),
                "v": np.arange(8, dtype=np.float64)})

    def build():
        return RecoverableStreamJob(
            source=TableSourceStreamOp(t, chunkSize=8),
            chains=[([WindowGroupByStreamOp(
                timeCol="ts", windowSize=4.0,
                selectClause="sum(v) as s")], [object()])],
            checkpoint_dir=str(tmp_path))

    monkeypatch.setenv("ALINK_VALIDATE_PLAN", "error")
    with pytest.raises(AkPlanValidationException) as ei:
        build()
    assert "ALK104" in str(ei.value)
    # warn/off keep the coordinator's own hard refusal as the failure
    monkeypatch.setenv("ALINK_VALIDATE_PLAN", "warn")
    with pytest.raises(Exception) as ei2:
        build()
    assert not isinstance(ei2.value, AkPlanValidationException)


def test_counters_exported_at_metrics(monkeypatch):
    monkeypatch.setenv("ALINK_VALIDATE_PLAN", "warn")
    from alink_tpu.operator.batch.base import MemSourceBatchOp

    MemSourceBatchOp([(1.0,)], "x DOUBLE").collect()
    text = metrics.export_prometheus()
    assert "alink_analysis_plan_runs_total" in text


def test_job_report_carries_analysis(monkeypatch):
    monkeypatch.setenv("ALINK_VALIDATE_PLAN", "warn")
    from alink_tpu.common.tracing import job_report
    from alink_tpu.operator.batch.base import MemSourceBatchOp

    MemSourceBatchOp([(1.0,)], "x DOUBLE").collect()
    rep = job_report()
    assert "analysis" in rep
    assert rep["analysis"] is None or rep["analysis"]["engine"] == "plan"


# ---------------------------------------------------------------------------
# alink-lint rules (temp files)
# ---------------------------------------------------------------------------


def _lint_src(tmp_path, rel, src):
    path = tmp_path / rel
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(textwrap.dedent(src))
    return lint_file(str(path), rel_base=str(tmp_path))


def test_lint_direct_jit_alk001(tmp_path):
    diags = _lint_src(tmp_path, "mod.py", """
        import jax

        def hot(x):
            return jax.jit(lambda v: v + 1)(x)
    """)
    assert [d.rule for d in diags] == ["ALK001"]
    assert diags[0].line == 5


def test_lint_jit_decorator_forms_alk001(tmp_path):
    """Every decorator spelling is judged in the ENCLOSING scope: a
    jit-decorated function is itself a compiled program even when its NAME
    says `_build*` — only jit built INSIDE a builder is exempt."""
    diags = _lint_src(tmp_path, "mod.py", """
        from functools import partial
        import jax

        @partial(jax.jit, donate_argnums=(0,))
        def _build_a(x):
            return x

        @jax.jit
        def _build_b(x):
            return x

        @jax.jit(static_argnums=(1,))
        def _build_c(x, n):
            return x

        def _build_real():
            @partial(jax.jit, donate_argnums=(0,))
            def step(x):
                return x
            return step
    """)
    assert [d.rule for d in diags] == ["ALK001"] * 3
    assert sorted(d.line for d in diags) == [5, 9, 13]


def test_lint_jit_exemptions(tmp_path):
    # builder idiom + cached_jit inline lambda + jitcache module itself
    assert _lint_src(tmp_path, "a.py", """
        import jax

        def _build_score():
            return jax.jit(lambda v: v * 2)
    """) == []
    assert _lint_src(tmp_path, "b.py", """
        import jax
        from alink_tpu.common.jitcache import cached_jit

        def get(run):
            return cached_jit("k", lambda: jax.jit(run))
    """) == []
    assert _lint_src(tmp_path, "common/jitcache.py", """
        import jax

        def anything():
            return jax.jit(lambda v: v)
    """) == []


def test_lint_shard_map_alk002(tmp_path):
    diags = _lint_src(tmp_path, "mod.py", """
        import jax

        def f(fn, mesh):
            return jax.shard_map(fn, mesh=mesh, in_specs=None,
                                 out_specs=None)
    """)
    assert [d.rule for d in diags] == ["ALK002"]


def test_lint_alk002_catches_experimental_bypasses(tmp_path):
    """The ban covers every way of reaching shard_map without the shim —
    the full experimental attribute chain (reported ONCE), the module
    import, and the from-import — not just the `jax.shard_map` spelling."""
    for src in (
        """
        import jax

        def f(fn, mesh):
            return jax.experimental.shard_map.shard_map(fn, mesh=mesh,
                                                        in_specs=None,
                                                        out_specs=None)
        """,
        """
        import jax.experimental.shard_map
        """,
        """
        from jax.experimental import shard_map as sm
        """,
        """
        from jax.experimental.shard_map import shard_map
        """,
    ):
        diags = _lint_src(tmp_path, "mod.py", src)
        assert [d.rule for d in diags] == ["ALK002"], src


def test_lint_alk002_exempts_the_shim_itself(tmp_path):
    """parallel/shardmap.py IS the sanctioned owner of the jax.shard_map
    reference."""
    diags = _lint_src(tmp_path, "parallel/shardmap.py", """
        def f():
            import jax
            return jax.shard_map
    """)
    assert diags == []


def test_lint_raw_environ_alk003(tmp_path):
    diags = _lint_src(tmp_path, "mod.py", """
        import os

        def knobs():
            a = os.environ.get("ALINK_X")
            b = os.environ["ALINK_Y"]
            c = "ALINK_Z" in os.environ
            d = os.getenv("ALINK_W", "1")
            os.environ["SET_OK"] = "1"          # write: allowed
            os.environ.setdefault("DFLT", "2")  # write: allowed
            return a, b, c, d
    """)
    assert [d.rule for d in diags] == ["ALK003"] * 4
    # the knob-parser module itself is exempt
    assert _lint_src(tmp_path, "common/env.py", """
        import os

        def env_int(name, default):
            return int(os.environ.get(name, default))
    """) == []


def test_lint_unlocked_mutation_alk004(tmp_path):
    # only threaded modules are in scope, and lock-guarded mutation passes
    src = """
        import threading

        _CACHE = {}
        _lock = threading.Lock()

        def bad(k, v):
            _CACHE[k] = v

        def good(k, v):
            with _lock:
                _CACHE[k] = v
    """
    diags = _lint_src(tmp_path, "common/executor.py", src)
    assert [d.rule for d in diags] == ["ALK004"]
    assert _lint_src(tmp_path, "operator/whatever.py", src) == []


def test_lint_parse_error_alk000(tmp_path):
    # a file ast.parse rejects gets its own rule id (error severity) —
    # never reported under an unrelated rule like ALK005
    diags = _lint_src(tmp_path, "broken.py", """
        def f(:
    """)
    assert [(d.rule, d.severity) for d in diags] == [("ALK000", "error")]


def test_lint_except_swallow_alk005(tmp_path):
    diags = _lint_src(tmp_path, "mod.py", """
        def f():
            try:
                g()
            except:
                return 1
            try:
                g()
            except Exception:
                pass
            try:
                g()
            except ValueError:
                pass  # narrow: allowed
            try:
                g()
            except Exception as e:
                log(e)  # handled: allowed
    """)
    assert [d.rule for d in diags] == ["ALK005", "ALK005"]


def test_lint_compile_cache_drift_alk006(tmp_path):
    """Every spelling of "configure the persistent compile cache" outside
    common/jitcache.py is drift: config writes and raw compilation_cache
    imports both bypass the sanctioned owner."""
    diags = _lint_src(tmp_path, "mod.py", """
        import jax
        from jax.experimental.compilation_cache import compilation_cache
        from jax._src import compilation_cache as cc2
        import jax.experimental.compilation_cache.compilation_cache as cc3

        def setup(d):
            jax.config.update("jax_compilation_cache_dir", d)
            jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
            jax.config.update("jax_compilation_cache_max_size", 1 << 30)
            jax.config.update("jax_default_matmul_precision", "float32")  # ok
    """)
    assert [d.rule for d in diags] == ["ALK006"] * 6
    assert all("jitcache" in d.hint for d in diags)


def test_lint_alk006_exempts_the_owner_itself(tmp_path):
    diags = _lint_src(tmp_path, "common/jitcache.py", """
        import jax
        from jax._src import compilation_cache as _cc

        def _apply(d):
            jax.config.update("jax_compilation_cache_dir", d)
    """)
    assert [d.rule for d in diags] == []


def test_alk006_absent_from_baseline():
    """The suppression baseline carries no ALK006 budget — any new direct
    compile-cache configuration outside common/jitcache.py fails
    ``--check`` (the env.py implementation moved to the owner in PR 11)."""
    with open(os.path.join(
            REPO_ROOT, "alink_tpu", "analysis", "lint_baseline.json")) as f:
        baseline = json.load(f)
    assert "ALK006" not in baseline["counts"]


def test_lint_unregistered_pallas_alk008(tmp_path):
    """Every spelling of "use Pallas" outside alink_tpu/native/ and the
    registered kernel modules is drift: unregistered kernels carry no
    knob, fallback, or parity contract."""
    diags = _lint_src(tmp_path, "mod.py", """
        from jax.experimental import pallas as pl
        from jax.experimental.pallas import pallas_call
        import jax.experimental.pallas as plx

        def f(x):
            return pl.pallas_call(lambda r, o: None)(x)

        def g(x):
            import jax
            return jax.experimental.pallas.pallas_call(lambda r, o: None)(x)
    """)
    assert [d.rule for d in diags] == ["ALK008"] * 5
    assert all("kernels.py" in d.hint for d in diags)


def test_lint_alk008_exempts_registered_modules(tmp_path):
    """native/ and every module the registry declares may hold the real
    pallas_call; relative imports of a kernel module's public entry points
    (the integration idiom attention.py/skipgram.py use) are clean too."""
    src = """
        from jax.experimental import pallas as pl

        def kernel(x):
            return pl.pallas_call(lambda r, o: None)(x)
    """
    assert _lint_src(tmp_path, "alink_tpu/native/fancy.py", src) == []
    from alink_tpu.native.kernels import KERNEL_MODULES

    assert "alink_tpu/dl/attn_pallas.py" in KERNEL_MODULES
    assert "alink_tpu/embedding/sgns_pallas.py" in KERNEL_MODULES
    assert "alink_tpu/tree/pallas_hist.py" in KERNEL_MODULES
    for rel in KERNEL_MODULES:
        assert _lint_src(tmp_path, rel, src) == []
    caller = _lint_src(tmp_path, "alink_tpu/dl/attention.py", """
        from .attn_pallas import flash_block_update, use_attn_pallas
    """)
    assert [d.rule for d in caller] == []


def test_lint_untraced_frame_send_alk112(tmp_path):
    """A frame-protocol request dict (an {'op': ...} literal) built in
    serving/ without a 'trace' field crosses the process boundary
    invisible to the stitched waterfall. A ``**spread`` may supply the
    field, so spread-bearing dicts are skipped, and the rule only
    patrols the serving tier."""
    src = """
        def send(client, name, row):
            client.call({"op": "predict", "name": name, "row": row})
            return {"ok": True}
    """
    diags = _lint_src(tmp_path, "serving/fleet_frontend.py", src)
    assert [d.rule for d in diags] == ["ALK112"]
    assert diags[0].line == 3
    assert "wire_context" in diags[0].hint
    # out of scope: the same dict outside serving/ is someone else's
    # protocol, not a fleet frame
    assert _lint_src(tmp_path, "common/whatever.py", src) == []
    clean = _lint_src(tmp_path, "serving/fleet.py", """
        def send(client, name, ctx, base):
            client.call({"op": "predict", "name": name, "trace": ctx})
            client.call({**base, "name": name})
            return {"ok": True, "value": 1}
    """)
    assert clean == []


def test_alk112_absent_from_baseline():
    """Untraced frame sends are banned from day one: every serving-tier
    request dict carries its wire context, so no ALK112 budget exists and
    the first regression fails ``--check``."""
    with open(os.path.join(
            REPO_ROOT, "alink_tpu", "analysis", "lint_baseline.json")) as f:
        baseline = json.load(f)
    assert "ALK112" not in baseline["counts"]


def test_telemetry_module_in_alk004_scope(tmp_path):
    """common/telemetry.py is a threaded module (heartbeat thread writes,
    supervisor thread reads) — unlocked module-dict mutation there is
    ALK004 drift like in the other relay modules."""
    diags = _lint_src(tmp_path, "common/telemetry.py", """
        _SEEN = {}

        def bad(k, v):
            _SEEN[k] = v
    """)
    assert [d.rule for d in diags] == ["ALK004"]


def test_alk008_absent_from_baseline():
    """Pallas containment is banned from day one: no ALK008 budget exists,
    so the first unregistered pallas_call anywhere fails ``--check``."""
    with open(os.path.join(
            REPO_ROOT, "alink_tpu", "analysis", "lint_baseline.json")) as f:
        baseline = json.load(f)
    assert "ALK008" not in baseline["counts"]


# ---------------------------------------------------------------------------
# Self-lint gate + baseline ratchet + inventory
# ---------------------------------------------------------------------------


def test_repo_self_lint_is_baselined():
    """Tier-1 drift gate: new lint findings in framework source fail here
    until fixed (or deliberately baselined via --write-baseline)."""
    report = run_lint()
    regressions = check_against_baseline(report, load_baseline())
    assert regressions == [], (
        "non-baselined lint findings (run `python -m alink_tpu.analysis"
        ".lint --check` for details): " + repr(regressions))


def test_check_fails_on_injected_violation(tmp_path, capsys):
    bad = tmp_path / "alink_tpu" / "fresh_module.py"
    bad.parent.mkdir(parents=True)
    bad.write_text("import os\nX = os.environ.get('ALINK_NEW_KNOB')\n")
    rc = lint_main(["--check", str(bad)])
    assert rc == 1
    assert "ALK003" in capsys.readouterr().out
    # the same findings pass once baselined
    baseline = tmp_path / "baseline.json"
    assert lint_main([str(bad), "--write-baseline",
                      "--baseline", str(baseline)]) == 0
    assert lint_main(["--check", str(bad),
                      "--baseline", str(baseline)]) == 0


def test_baseline_is_a_ratchet():
    rep = Report(engine="lint")
    rep.add("ALK003", "x", path="alink_tpu/a.py", line=3)
    rep.add("ALK003", "y", path="alink_tpu/a.py", line=9)
    baseline = {"ALK003": {"alink_tpu/a.py": 2}}
    assert check_against_baseline(rep, baseline) == []
    rep.add("ALK003", "z", path="alink_tpu/a.py", line=12)
    assert check_against_baseline(rep, baseline) == [
        ("ALK003", "alink_tpu/a.py", 3, 2)]


def test_shard_map_inventory_committed_file_is_fresh_and_empty():
    """docs/shard_map_inventory.json (the ROADMAP Open item 3 work-list)
    must match what the ALK002 rule finds in the current source — and the
    migration to ``parallel/shardmap.py`` retired every call site, so the
    ratchet is now a ban: the inventory pins ZERO direct uses."""
    path = os.path.join(REPO_ROOT, "docs", "shard_map_inventory.json")
    with open(path) as f:
        committed = json.load(f)
    live = shard_map_inventory()
    assert committed["modules"] == live["modules"] == {}
    assert committed["total_call_sites"] == live["total_call_sites"] == 0


def test_alk002_absent_from_baseline():
    """The suppression baseline carries no ALK002 budget — any new direct
    ``jax.shard_map`` / ``experimental.shard_map`` use fails ``--check``."""
    with open(os.path.join(
            REPO_ROOT, "alink_tpu", "analysis", "lint_baseline.json")) as f:
        baseline = json.load(f)
    assert "ALK002" not in baseline["counts"]


def test_rule_table_complete():
    # every rule either engine can emit is documented in the table
    for rid in ("ALK001", "ALK002", "ALK003", "ALK004", "ALK005", "ALK006",
                "ALK008",
                "ALK101", "ALK102", "ALK103", "ALK104", "ALK105",
                "ALK106", "ALK107", "ALK109"):
        title, sev, desc = RULES[rid]
        assert title and sev in ("error", "warning", "info") and desc


# ---------------------------------------------------------------------------
# WebUI surface
# ---------------------------------------------------------------------------


def test_webui_analysis_endpoint(monkeypatch):
    import urllib.request

    from alink_tpu.webui.server import WebUIServer

    monkeypatch.setenv("ALINK_VALIDATE_PLAN", "warn")
    from alink_tpu.operator.batch.base import MemSourceBatchOp

    MemSourceBatchOp([(1.0,)], "x DOUBLE").collect()
    srv = WebUIServer(port=0).start(background=True)
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/api/analysis") as r:
            body = json.loads(r.read())
        assert body["mode"] == "warn"
        assert "ALK101" in body["rules"]
        assert body["plan"] is None or body["plan"]["engine"] == "plan"
        assert "analysis.plan_runs" in body["counters"]
    finally:
        srv.stop()


# ---------------------------------------------------------------------------
# Env-knob migration pins (satellite: behavior-identical defaults)
# ---------------------------------------------------------------------------


def test_env_str_semantics(monkeypatch):
    from alink_tpu.common.env import env_str

    monkeypatch.delenv("ALINK_T_STR", raising=False)
    assert env_str("ALINK_T_STR", "d") == "d"
    monkeypatch.setenv("ALINK_T_STR", "")
    assert env_str("ALINK_T_STR", "d") == "d"   # blank == unset
    monkeypatch.setenv("ALINK_T_STR", "value")
    assert env_str("ALINK_T_STR", "d") == "value"


def test_migrated_knob_defaults(monkeypatch):
    from alink_tpu.common import executor, profiling, streaming
    from alink_tpu.common.jitcache import _max_programs
    from alink_tpu.serving.router import ServingConfig

    for var in ("ALINK_STREAM_DEPTH", "ALINK_H2D_STREAMS",
                "ALINK_DAG_SCHEDULER", "ALINK_DAG_FUSION",
                "ALINK_PROGRAM_CACHE_SIZE", "ALINK_PROFILING",
                "ALINK_SERVING_SHED_POLICY"):
        monkeypatch.delenv(var, raising=False)
    assert streaming.stream_depth() == 2
    assert streaming._num_streams() == 4
    assert executor.scheduler_enabled() is True
    assert executor.fusion_enabled() is True
    assert _max_programs() == 256
    assert profiling.profiling_mode() == "on"
    assert ServingConfig.default().shed_policy == "reject"


def test_migrated_knob_malformed_values_fall_back(monkeypatch):
    from alink_tpu.common import profiling, streaming
    from alink_tpu.common.jitcache import _max_programs
    from alink_tpu.serving.router import ServingConfig

    monkeypatch.setenv("ALINK_STREAM_DEPTH", "not-an-int")
    assert streaming.stream_depth() == 2
    monkeypatch.setenv("ALINK_PROGRAM_CACHE_SIZE", "many")
    assert _max_programs() == 256
    monkeypatch.setenv("ALINK_PROFILING", "bananas")
    assert profiling.profiling_mode() == "on"
    monkeypatch.setenv("ALINK_SERVING_SHED_POLICY", "newest")
    assert ServingConfig.default().shed_policy == "reject"


def test_migrated_knob_overrides_still_work(monkeypatch):
    from alink_tpu.common import executor, streaming

    monkeypatch.setenv("ALINK_STREAM_DEPTH", "5")
    assert streaming.stream_depth() == 5
    monkeypatch.setenv("ALINK_DAG_FUSION", "0")
    assert executor.fusion_enabled() is False
    monkeypatch.setenv("ALINK_DAG_SCHEDULER", "off")
    assert executor.scheduler_enabled() is False


def test_pallas_flag_falsey_convention(monkeypatch):
    from alink_tpu.tree.pallas_hist import use_pallas_hist

    for v in ("0", "false", "False", "OFF", "no"):
        monkeypatch.setenv("ALINK_GBDT_PALLAS", v)
        assert use_pallas_hist() is False, v
    monkeypatch.setenv("ALINK_GBDT_PALLAS", "1")
    assert use_pallas_hist() is True


def test_train_config_off_ladder_alk103():
    """ALK103 extended to TrainConfig (ISSUE 15 satellite): off-ladder
    effective batch, off-ladder micro batch (batch_size/accum_steps), and
    accum-indivisible batch sizes are all recompile/packing hazards the
    pre-flight flags before the train loop compiles anything."""
    from alink_tpu.analysis import validate_train_config
    from alink_tpu.common.jitcache import bucket_rows
    from alink_tpu.dl.train import TrainConfig

    # clean: ladder batch, ladder micro
    assert validate_train_config(TrainConfig(batch_size=64,
                                             accum_steps=4)).ok

    rep = validate_train_config(TrainConfig(batch_size=50))
    assert _rules(rep) == {"ALK103": 1}
    assert "50" in rep.diagnostics[0].message

    # 56 is ON the ladder but 56/2=28 is not: only the micro fires
    assert bucket_rows(56) == 56 and bucket_rows(28) != 28
    rep = validate_train_config(TrainConfig(batch_size=56, accum_steps=2))
    assert _rules(rep) == {"ALK103": 1}
    assert "micro batch 28" in rep.diagnostics[0].message

    # indivisible accum flags alongside the off-ladder batch
    rep = validate_train_config(TrainConfig(batch_size=50, accum_steps=3))
    assert _rules(rep) == {"ALK103": 2}
    assert any("divisible" in d.message for d in rep.diagnostics)


def test_distributed_topology_knobs_fail_loudly(monkeypatch):
    # topology (unlike tuning) knobs must not silently degrade a multi-host
    # job: a malformed NUM_PROCESSES raises, exactly as before the env
    # migration — including exported-but-BLANK (an unexpanded ${WORLD_SIZE}
    # in a launcher manifest must not read as "unset")
    from alink_tpu.parallel.distributed import init_multi_host

    monkeypatch.setenv("NUM_PROCESSES", "abc")
    with pytest.raises(ValueError):
        init_multi_host()
    monkeypatch.setenv("NUM_PROCESSES", "")
    with pytest.raises(ValueError):
        init_multi_host()
