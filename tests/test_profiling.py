"""Performance observatory (common/profiling.py): XLA cost/memory capture
at ProgramCache compiles, roofline attribution, registry-survives-eviction,
profiling on/off bit-parity, the Prometheus gauge surface and the
/api/profile endpoint.

Container-safe: pipelines are built from StandardScaler + VectorAssembler
+ NaiveBayes and block-kernel mapper DAGs only (no shard_map fit paths).
Cost assertions use unique kernel ids / fresh coefficients so tests stay
order-independent in the shared process."""

import json
import os
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
