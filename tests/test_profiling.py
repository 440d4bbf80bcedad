"""Performance observatory (common/profiling.py + common/benchstats.py):
XLA cost/memory capture at ProgramCache compiles, roofline attribution,
registry-survives-eviction, profiling on/off bit-parity, the Prometheus
gauge surface, the /api/profile endpoint, and the benchstats regression
gate (in-process perf gate + BENCH-file compare).

Container-safe: pipelines are built from StandardScaler + VectorAssembler
+ NaiveBayes and block-kernel mapper DAGs only (no shard_map fit paths).
Cost assertions use unique kernel ids / fresh coefficients so tests stay
order-independent in the shared process."""

import json
import os
import time
import uuid

import numpy as np
import pytest

from alink_tpu.common import profiling
from alink_tpu.common.jitcache import cached_jit, clear_kernel, programs
from alink_tpu.common.metrics import metrics
from alink_tpu.common.profiling import (
    device_peaks,
    hbm_watermark,
    profile_summary,
    program_costs,
    roofline,
    sample_device_memory,
    xla_cost_analysis,
)

pytestmark = pytest.mark.profiling


def _uid() -> str:
    return uuid.uuid4().hex[:8]


def _mm_kernel(kid):
    import jax
    import jax.numpy as jnp

    return cached_jit(kid, lambda: jax.jit(lambda x, w: jnp.tanh(x @ w)))


def _affine_chain(t, a, b):
    """Two-op block-kernel mapper chain over MTable ``t`` — fuses into one
    ``mapper.kernel_chain`` program through the DAG executor."""
    from alink_tpu.common.mtable import AlinkTypes, MTable  # noqa: F401
    from alink_tpu.mapper.base import BlockKernelMapper
    from alink_tpu.operator.batch import TableSourceBatchOp
    from alink_tpu.operator.batch.utils import MapBatchOp

    def affine(col, out_col, aa, bb):
        class _M(BlockKernelMapper):
            def kernel(self, schema):
                return ([col], [out_col], [AlinkTypes.DOUBLE],
                        lambda X: X * aa + bb)

        class _Op(MapBatchOp):
            mapper_cls = _M

        return _Op()

    chain = affine("x", "x1", a, b).link_from(TableSourceBatchOp(t))
    chain = affine("x1", "x2", 0.5 * a, -b).link_from(chain)
    return chain


# ---------------------------------------------------------------------------
# Capture + roofline
# ---------------------------------------------------------------------------


def test_cost_capture_and_roofline(monkeypatch):
    monkeypatch.setenv("ALINK_PROFILING", "on")
    kid = f"prof.mm_{_uid()}"
    prog = _mm_kernel(kid)
    x = np.random.RandomState(0).rand(256, 64).astype(np.float32)
    w = np.random.RandomState(1).rand(64, 32).astype(np.float32)
    prog(x, w)            # trace: enqueues the pending cost record
    prog(x, w)            # warm: exec accounting

    recs = program_costs(kid)  # readout resolves the pending capture
    assert len(recs) == 1
    r = recs[0]
    assert r["capture"] == "cost"
    assert r["flops"] and r["flops"] > 0
    assert r["bytes_accessed"] and r["bytes_accessed"] > 0
    # estimated memory: args + outputs known without a backend compile
    assert r["argument_bytes"] == x.nbytes + w.nbytes
    assert r["output_bytes"] == 256 * 32 * 4
    assert r["peak_hbm_bytes"] == r["argument_bytes"] + r["output_bytes"]
    assert r["calls"] == 1 and r["exec_mean_s"] > 0
    assert r["achieved_flops_per_s"] > 0

    row = [k for k in profile_summary()["kernels"] if k["kernel"] == kid][0]
    rf = row["roofline"]
    assert rf["bound"] in ("compute-bound", "bandwidth-bound")
    assert rf["arithmetic_intensity"] == pytest.approx(
        r["flops"] / r["bytes_accessed"], rel=1e-3)
    assert rf["ceiling_flops_per_s"] > 0
    assert 0 < rf["efficiency"]


def test_deep_mode_exact_memory_analysis(monkeypatch):
    monkeypatch.setenv("ALINK_PROFILING", "deep")
    kid = f"prof.deep_{_uid()}"
    prog = _mm_kernel(kid)
    prog(np.ones((64, 16), np.float32), np.ones((16, 8), np.float32))
    r = program_costs(kid, resolve=False)[0]  # deep captures eagerly
    assert r["capture"] == "deep"
    assert r["memory_source"] == "memory_analysis"
    assert r["flops"] > 0
    assert r["argument_bytes"] > 0 and r["output_bytes"] > 0
    assert r["temp_bytes"] is not None
    assert r["peak_hbm_bytes"] >= r["output_bytes"]


def test_profiling_off_captures_nothing(monkeypatch):
    monkeypatch.setenv("ALINK_PROFILING", "off")
    kid = f"prof.off_{_uid()}"
    prog = _mm_kernel(kid)
    prog(np.ones((32, 8), np.float32), np.ones((8, 4), np.float32))
    prog(np.ones((32, 8), np.float32), np.ones((8, 4), np.float32))
    assert program_costs(kid) == []
    monkeypatch.setenv("ALINK_PROFILING", "on")
    # flipping on later records exec stats and back-fills the cost by
    # locating the live program in the cache
    prog(np.ones((32, 8), np.float32), np.ones((8, 4), np.float32))
    recs = program_costs(kid)
    assert len(recs) == 1
    assert recs[0]["calls"] == 1
    assert recs[0]["capture"] == "cost" and recs[0]["flops"] > 0


def test_registry_survives_program_cache_eviction(monkeypatch):
    monkeypatch.setenv("ALINK_PROFILING", "on")
    monkeypatch.setenv("ALINK_PROGRAM_CACHE_SIZE", "2")
    kid = f"prof.evict_{_uid()}"
    prog = _mm_kernel(kid)
    prog(np.ones((16, 4), np.float32), np.ones((4, 4), np.float32))
    resolved = program_costs(kid)      # pin the cost BEFORE eviction
    assert resolved[0]["flops"] > 0
    ev0 = metrics.counter("jit.program_evictions")
    for i in range(4):                 # push the 2-entry LRU past capacity
        _mm_kernel(f"prof.filler_{_uid()}")
    assert metrics.counter("jit.program_evictions") > ev0
    assert not programs(kid)           # the program is gone...
    after = program_costs(kid)         # ...the cost record is not
    assert after and after[0]["flops"] == resolved[0]["flops"]
    assert after[0]["capture"] == "cost"


def test_pending_record_of_evicted_program_is_kept(monkeypatch):
    monkeypatch.setenv("ALINK_PROFILING", "on")
    kid = f"prof.gone_{_uid()}"
    prog = _mm_kernel(kid)
    prog(np.ones((8, 4), np.float32), np.ones((4, 2), np.float32))
    clear_kernel(kid)                  # dropped before anyone read it
    recs = program_costs(kid)
    assert len(recs) == 1
    assert recs[0]["capture"] == "evicted"
    assert recs[0]["flops"] is None
    # the memory estimate and exec stats still survive
    assert recs[0]["argument_bytes"] > 0


# ---------------------------------------------------------------------------
# Bit-parity + pipeline integration (container-safe estimators)
# ---------------------------------------------------------------------------


def _nb_pipeline_predictions():
    from alink_tpu.common.mtable import MTable
    from alink_tpu.pipeline import (NaiveBayes, Pipeline, StandardScaler,
                                    VectorAssembler)

    rng = np.random.RandomState(0)
    X = np.concatenate([rng.normal(c, 0.4, size=(60, 4))
                        for c in [(0, 0, 0, 0), (2, 2, 2, 2)]])
    y = np.repeat(["neg", "pos"], 60)
    feats = ["f0", "f1", "f2", "f3"]
    t = MTable({f"f{i}": X[:, i] for i in range(4)}).with_column("label", y)
    model = Pipeline(
        StandardScaler(selectedCols=feats),
        VectorAssembler(selectedCols=feats, outputCol="vec"),
        NaiveBayes(vectorCol="vec", labelCol="label", predictionCol="pred"),
    ).fit(t)
    out = model.transform(t).collect()
    return np.asarray(out.col("pred"))


def test_pipeline_profiling_on_off_bit_identical(monkeypatch):
    monkeypatch.setenv("ALINK_PROFILING", "off")
    p_off = _nb_pipeline_predictions()
    monkeypatch.setenv("ALINK_PROFILING", "on")
    p_on = _nb_pipeline_predictions()
    assert np.array_equal(p_off, p_on)
    # the profiled run captured the NaiveBayes scoring kernel
    assert any(r["kernel"] == "naivebayes.score"
               for r in program_costs("naivebayes.score"))


def test_mapper_chain_profiling_parity_and_capture(monkeypatch):
    from alink_tpu.common.mtable import MTable

    rng = np.random.RandomState(7)
    t = MTable({"x": rng.rand(3000)})
    a = 1.0 + rng.rand()               # fresh coefficients => fresh program
    monkeypatch.setenv("ALINK_PROFILING", "off")
    o_off = np.asarray(_affine_chain(t, a, 2.0).collect().col("x2"))
    monkeypatch.setenv("ALINK_PROFILING", "on")
    o_on = np.asarray(_affine_chain(t, a, 2.0).collect().col("x2"))
    assert np.array_equal(o_off, o_on)
    assert any(r["flops"] is not None
               for r in program_costs("mapper.kernel_chain"))


def test_job_report_includes_per_kernel_profile(monkeypatch):
    """Acceptance: job_report() for a mapper-DAG job includes per-kernel
    flops, bytes_accessed, peak_hbm_bytes, achieved FLOP/s, and a roofline
    classification."""
    from alink_tpu.common.mtable import MTable
    from alink_tpu.common.tracing import job_report

    monkeypatch.setenv("ALINK_PROFILING", "on")
    monkeypatch.setenv("ALINK_TRACING", "on")
    profiling.clear_profile_registry()   # deterministic top-N in the report
    rng = np.random.RandomState(3)
    t = MTable({"x": rng.rand(5000)})
    a = 3.0 + rng.rand()
    _affine_chain(t, a, 1.0).collect()     # trace + capture
    _affine_chain(t, a, 1.0).collect()     # warm calls -> achieved FLOP/s
    report = job_report()
    assert "profile" in report
    prof = report["profile"]
    assert prof["enabled"]
    assert prof["device"]["ridge_flops_per_byte"] is not None
    chain = [k for k in prof["kernels"]
             if k["kernel"] == "mapper.kernel_chain"]
    assert chain, f"kernel table: {[k['kernel'] for k in prof['kernels']]}"
    row = chain[0]
    assert row["flops"] > 0
    assert row["bytes_accessed"] > 0
    assert row["peak_hbm_bytes"] > 0
    assert row["achieved_flops_per_s"] > 0
    assert row["roofline"]["bound"] in ("compute-bound", "bandwidth-bound")


def test_compile_summary_carries_costs(monkeypatch):
    from alink_tpu.common.jitcache import compile_summary

    monkeypatch.setenv("ALINK_PROFILING", "on")
    kid = f"prof.cs_{_uid()}"
    prog = _mm_kernel(kid)
    prog(np.ones((64, 8), np.float32), np.ones((8, 8), np.float32))
    cs = compile_summary()
    assert kid in cs["kernels"]
    cost = cs["kernels"][kid].get("cost")
    assert cost and cost["flops"] > 0 and cost["bytes_accessed"] > 0


# ---------------------------------------------------------------------------
# HBM sampling + device peaks
# ---------------------------------------------------------------------------


def test_hbm_sampling_graceful_noop_on_cpu(monkeypatch):
    monkeypatch.setenv("ALINK_PROFILING", "on")
    assert sample_device_memory() is None      # CPU: no memory_stats
    assert sample_device_memory() is None      # latched, still a no-op
    wm = hbm_watermark()
    assert wm["available"] is False
    assert wm["peak_bytes"] is None


def test_hbm_transient_error_does_not_latch(monkeypatch):
    """One stats hiccup on a live backend must not permanently disable
    watermark sampling (only a clean no-stats probe — CPU — latches)."""
    import jax

    monkeypatch.setenv("ALINK_PROFILING", "on")
    with profiling._hbm_lock:
        old = profiling._hbm["available"]
        profiling._hbm["available"] = None     # un-latch for the probe
    try:
        def boom():
            raise RuntimeError("transient runtime hiccup")

        monkeypatch.setattr(jax, "local_devices", boom)
        e0 = metrics.counter("profile.hbm_sample_errors")
        assert sample_device_memory() is None
        assert metrics.counter("profile.hbm_sample_errors") == e0 + 1
        with profiling._hbm_lock:
            assert profiling._hbm["available"] is None   # NOT latched off
    finally:
        with profiling._hbm_lock:
            profiling._hbm["available"] = old


def test_device_peaks_env_override(monkeypatch):
    monkeypatch.setenv("ALINK_PEAK_TFLOPS", "100")
    monkeypatch.setenv("ALINK_PEAK_HBM_GBS", "1000")
    p = device_peaks()
    assert p["peak_flops_per_s"] == 100e12
    assert p["hbm_bytes_per_s"] == 1000e9
    assert p["ridge_flops_per_byte"] == 100.0
    assert p["source"] == "env"
    # ridge splits the verdicts
    assert roofline(1e9, 1e6, peaks=p)["bound"] == "compute-bound"   # AI 1000
    assert roofline(1e6, 1e6, peaks=p)["bound"] == "bandwidth-bound"  # AI 1


def test_xla_cost_analysis_normalizes_shapes():
    class _ListStage:
        def cost_analysis(self):
            return [{"flops": 10.0, "bytes accessed": 5.0},
                    {"flops": 2.0, "utilization0{}": 1.0}]

    class _DictStage:
        def cost_analysis(self):
            return {"flops": 7.0, "bytes accessed": 3.0,
                    "transcendentals": 1.0}

    class _Broken:
        def cost_analysis(self):
            raise RuntimeError("nope")

    assert xla_cost_analysis(_ListStage()) == {
        "flops": 12.0, "bytes_accessed": 5.0}
    assert xla_cost_analysis(_DictStage()) == {
        "flops": 7.0, "bytes_accessed": 3.0, "transcendentals": 1.0}
    assert xla_cost_analysis(_Broken()) == {}


# ---------------------------------------------------------------------------
# Prometheus + HTTP surfaces
# ---------------------------------------------------------------------------


def test_prometheus_profile_gauges(monkeypatch):
    monkeypatch.setenv("ALINK_PROFILING", "on")
    kid = f"prof.prom_{_uid()}"
    prog = _mm_kernel(kid)
    prog(np.ones((32, 16), np.float32), np.ones((16, 8), np.float32))
    prog(np.ones((32, 16), np.float32), np.ones((16, 8), np.float32))
    text = metrics.export_prometheus()
    assert "# TYPE alink_profile_flops gauge" in text
    assert f'alink_profile_flops{{kernel="{kid}"}}' in text
    assert "# TYPE alink_profile_bytes_accessed gauge" in text
    assert f'alink_profile_achieved_flops_per_s{{kernel="{kid}"}}' in text


def test_api_profile_endpoint(monkeypatch):
    import urllib.request

    from alink_tpu.webui.server import WebUIServer

    monkeypatch.setenv("ALINK_PROFILING", "on")
    kid = f"prof.http_{_uid()}"
    prog = _mm_kernel(kid)
    prog(np.ones((16, 8), np.float32), np.ones((8, 4), np.float32))
    srv = WebUIServer(port=0).start(background=True)
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/api/profile", timeout=30) as r:
            assert r.status == 200
            body = json.loads(r.read())
    finally:
        srv.stop()
    assert body["enabled"] is True
    assert body["device"]["device_kind"]
    assert any(k["kernel"] == kid for k in body["kernels"])
    # the streaming recovery + elastic health block rides along (the WebUI
    # profile panel's rescale-event line reads it)
    assert "elastic" in body["recovery"]
    assert {"rescale_out", "rescale_in",
            "rescale_aborted"} <= set(body["recovery"]["elastic"])


# ---------------------------------------------------------------------------
# benchstats: in-process perf gate + BENCH-file regression compare
# ---------------------------------------------------------------------------


def test_trimmed_mean_and_ci():
    from alink_tpu.common.benchstats import mean_ci, trimmed_mean

    xs = [1.0, 1.0, 1.0, 1.0, 100.0]      # one interference outlier
    assert trimmed_mean(xs, trim=0.2) == 1.0
    m, half = mean_ci([1.0, 1.1, 0.9, 1.0, 1.0, 1.0, 1.0], trim=0.0)
    assert m == pytest.approx(1.0, rel=0.05)
    assert half >= 0.0
    m1, h1 = mean_ci([5.0])
    assert (m1, h1) == (5.0, 0.0)


def test_perf_gate_noise_passes_and_slowdown_flagged():
    """The CI perf-gate smoke: two same-config measurements read no-change;
    a synthetic 20% slowdown is flagged as a significant regression."""
    from alink_tpu.common.benchstats import perf_gate

    same = perf_gate(lambda: time.sleep(0.004), lambda: time.sleep(0.004),
                     repeats=9)
    assert same["verdict"] == "no-change"
    assert not same["significant"]

    slow = perf_gate(lambda: time.sleep(0.004), lambda: time.sleep(0.0048),
                     repeats=9)
    assert slow["verdict"] == "regression"
    assert slow["significant"]
    assert slow["delta_pct"] > 8.0

    faster = perf_gate(lambda: time.sleep(0.0048), lambda: time.sleep(0.004),
                       repeats=9)
    assert faster["verdict"] == "improvement"


def test_metric_direction_classification():
    from alink_tpu.common.benchstats import metric_direction

    assert metric_direction("value") == "higher"
    assert metric_direction("extras.softmax_mnist.samples_per_sec") == "higher"
    assert metric_direction("extras.bert_mfu.mfu") == "higher"
    assert metric_direction("extras.kmeans_iris.wall_clock_s") == "lower"
    assert metric_direction("extras.serving.request_p99_ms") == "lower"
    assert metric_direction("extras.gbdt_train.trees") is None
    # signed noise-centered percentages must never be flagged: a relative
    # delta between 0.9% and 2.4% overhead is meaningless
    assert metric_direction("extras.profiling.overhead_pct") is None
    assert metric_direction("extras.profiling.overhead_ci_pct") is None
    assert metric_direction(
        "extras.profiling.perf_gate.slowdown_detail.delta_pct") is None
    # roofline efficiency (kernels extra): higher is better, but it is
    # derived from a measured wall so it gets the wall-noise threshold
    from alink_tpu.common.benchstats import WALL_THRESHOLD, metric_threshold

    assert metric_direction("extras.kernels.sgns.efficiency_after") == "higher"
    assert metric_threshold(
        "extras.kernels.sgns.efficiency_after") == WALL_THRESHOLD
    assert metric_direction("extras.kernels.attention.parity_max_diff") is None
    assert metric_direction("extras.kernels.sgns.pallas_wall_s") == "lower"


def test_compare_bench_files_flags_bert_regression(tmp_path):
    """Acceptance: ``--compare`` over two archived-layout rounds flags a
    12% drop of the headline samples/s as a significant regression, while
    a same-config (self) compare reports no regressions."""
    from alink_tpu.common.benchstats import compare_bench_files

    def round_file(n, value, kmeans_cold_s):
        parsed = {
            "metric": "bert_base_finetune_throughput_per_chip",
            "value": value,
            "unit": "samples/sec/chip (seq128, bs32, bf16)",
            "extras": {
                "kmeans_iris": {"wall_clock_s": kmeans_cold_s,
                                "wall_clock_warm_s": 0.4,
                                "cluster_purity": 0.8933},
                "gbdt_train": {"samples_per_sec": 1.5e6, "trees": 20,
                               "train_accuracy": 0.9936},
            },
        }
        path = tmp_path / f"round_{n}.json"
        path.write_text(json.dumps(
            {"n": n, "cmd": "python bench.py", "rc": 0, "tail": "",
             "parsed": parsed}))
        return str(path)

    old = round_file(4, 2163.9, 2.7)
    new = round_file(5, 1897.7, 2.8)
    rep = compare_bench_files(old, new)
    assert rep["verdict"] == "regression"
    flagged = {e["metric"] for e in rep["regressions"]}
    assert "value" in flagged          # the bert samples/s/chip drop
    bert = next(e for e in rep["regressions"] if e["metric"] == "value")
    assert bert["delta_pct"] < -10.0
    assert bert["direction"] == "higher"

    same = compare_bench_files(old, old)
    assert same["verdict"] == "ok"
    assert same["regressions"] == []


def test_compare_bench_files_handles_raw_and_wrapped(tmp_path):
    from alink_tpu.common.benchstats import compare_bench_files

    raw = {"metric": "m", "value": 100.0,
           "extras": {"w": {"samples_per_sec": 50.0, "wall_clock_s": 2.0,
                            "note": "text", "flag": True,
                            "trace": [1, 2, 3]}}}
    wrapped = {"n": 2, "parsed": {
        "metric": "m", "value": 80.0,
        "extras": {"w": {"samples_per_sec": 50.5, "wall_clock_s": 2.1}}}}
    p1, p2 = tmp_path / "a.json", tmp_path / "b.json"
    p1.write_text(json.dumps(raw))
    p2.write_text(json.dumps(wrapped))
    rep = compare_bench_files(str(p1), str(p2))
    by_metric = {e["metric"]: e for e in rep["regressions"]}
    assert "value" in by_metric                       # -20% throughput
    names = {e["metric"] for e in rep["regressions"]
             + rep["improvements"]}
    assert "extras.w.samples_per_sec" not in names    # +1% is noise
    assert rep["metrics_compared"] == 3               # text/bool/list skipped
    assert rep["platform_change"] is None             # no device evidence


def test_compare_bench_files_platform_change_demotes_hw_metrics(tmp_path):
    """A round pair from DIFFERENT accelerators (TPU round vs CPU
    container) must not false-flag the hardware swap as a code regression:
    hardware-bound perf metrics demote to the loud ``platform-change``
    verdict, while hardware-independent quality metrics keep gating —
    the r05 (TPU) → r06 (CPU) handover case."""
    from alink_tpu.common.benchstats import (compare_bench_files,
                                             round_device_kind)

    def doc(kind, sps, acc):
        return {"metric": "m", "value": sps, "extras": {
            "bert_mfu": {"device_kind": kind},
            "w": {"samples_per_sec": sps, "accuracy_holdout": acc}}}

    tpu = tmp_path / "tpu.json"
    cpu = tmp_path / "cpu.json"
    tpu.write_text(json.dumps(doc("TPU v5 lite", 1900.0, 0.96)))
    # 400x slower chip, same model quality
    cpu.write_text(json.dumps(doc("cpu", 4.4, 0.958)))
    assert round_device_kind(json.loads(tpu.read_text())) == "TPU v5 lite"
    rep = compare_bench_files(str(tpu), str(cpu))
    assert rep["platform_change"] == {"old": "TPU v5 lite", "new": "cpu"}
    assert rep["regressions"] == []                   # hw swap ≠ regression
    assert rep["platform_demoted"] >= 2               # value + samples/sec
    assert rep["verdict"] == "ok"
    # ... but a QUALITY drop still gates across the platform change
    cpu.write_text(json.dumps(doc("cpu", 4.4, 0.55)))
    rep = compare_bench_files(str(tpu), str(cpu))
    assert any(e["metric"] == "extras.w.accuracy_holdout"
               for e in rep["regressions"])
    assert rep["verdict"] == "regression"
    # same-platform rounds: full gating, exactly as before
    fast = tmp_path / "fast.json"
    slow = tmp_path / "slow.json"
    fast.write_text(json.dumps(doc("cpu", 100.0, 0.9)))
    slow.write_text(json.dumps(doc("cpu", 50.0, 0.9)))
    rep = compare_bench_files(str(fast), str(slow))
    assert rep["platform_change"] is None
    assert any(e["metric"] == "value" for e in rep["regressions"])
