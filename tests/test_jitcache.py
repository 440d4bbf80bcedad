"""Shape-stable execution layer (common/jitcache.py): program-cache reuse,
shape bucketing bit-parity gates, recompile-regression counters, AOT warmup,
and the staging-cache HBM sizing satellite.

Everything here measures COUNTER DELTAS (jit.compile / jit.trace are
monotonic process counters), so tests are order-independent."""

import os

import numpy as np
import pytest

from alink_tpu.common import jitcache
from alink_tpu.common.jitcache import (
    bucket_rows,
    cached_jit,
    call_row_bucketed,
    compile_summary,
    fn_content_key,
    floor_bucket_rows,
    load_shape_profile,
    pad_rows,
    programs,
    warmup,
)
from alink_tpu.common.metrics import metrics
from alink_tpu.common.model import model_to_table
from alink_tpu.common.mtable import AlinkTypes, MTable

pytestmark = pytest.mark.compile


def _compiles() -> int:
    return metrics.counter("jit.compile")


def _traces() -> int:
    return metrics.counter("jit.trace")


# ---------------------------------------------------------------------------
# bucket ladder
# ---------------------------------------------------------------------------

def test_bucket_ladder_default():
    # linear head: multiples of 8 up to 64; then powers of two
    assert [bucket_rows(n) for n in (1, 7, 8, 9, 33, 64)] == \
        [8, 8, 8, 16, 40, 64]
    assert [bucket_rows(n) for n in (65, 100, 1000, 1024, 1025)] == \
        [128, 128, 1024, 1024, 2048]
    # a bucketed size is a fixed point — repeated bucketing cannot drift
    for n in (8, 40, 64, 128, 4096):
        assert bucket_rows(bucket_rows(n)) == bucket_rows(n)


def test_bucket_ladder_env(monkeypatch):
    monkeypatch.setenv("ALINK_SHAPE_BUCKETS", "off")
    assert bucket_rows(33) == 33
    assert not jitcache.bucketing_enabled()
    monkeypatch.setenv("ALINK_SHAPE_BUCKETS", "16,128")
    assert bucket_rows(5) == 16
    assert bucket_rows(100) == 128
    assert bucket_rows(200) == 256   # beyond the last rung: multiples of it
    monkeypatch.setenv("ALINK_SHAPE_BUCKETS", "garbage,,")
    assert bucket_rows(33) == 40     # malformed knob falls back to default


def test_floor_bucket_rows():
    assert floor_bucket_rows(100) == 64
    assert floor_bucket_rows(1000) == 512
    assert floor_bucket_rows(64) == 64
    assert floor_bucket_rows(33) == 32
    assert floor_bucket_rows(3) == 3   # below the smallest rung: unchanged
    # floor lands ON the ladder, so steady chunks ship with zero padding
    assert bucket_rows(floor_bucket_rows(1000)) == floor_bucket_rows(1000)


def test_pad_rows_and_trim():
    a = np.arange(6, dtype=np.float32).reshape(3, 2)
    p = pad_rows(a, 8)
    assert p.shape == (8, 2)
    assert np.array_equal(p[:3], a)
    assert not p[3:].any()
    assert pad_rows(a, 3) is a      # no-op keeps the original block


# ---------------------------------------------------------------------------
# program cache identity + content keys
# ---------------------------------------------------------------------------

def _build_scale(factor):
    import jax

    return jax.jit(lambda x: x * factor)


def test_cached_jit_identity_and_counters():
    p1 = cached_jit("test.scale", _build_scale, 2.0)
    p2 = cached_jit("test.scale", _build_scale, 2.0)
    p3 = cached_jit("test.scale", _build_scale, 3.0)
    assert p1 is p2
    assert p1 is not p3
    c0 = _compiles()
    x = np.ones(10, np.float32)
    assert np.array_equal(np.asarray(p1(x)), x * 2.0)
    assert _compiles() == c0 + 1     # first sig: one trace+compile
    p1(x)
    assert _compiles() == c0 + 1     # steady state: zero new compiles
    p1(np.ones(20, np.float32))      # new shape: one more
    assert _compiles() == c0 + 2


def test_fn_content_key_distinguishes_captured_config():
    def make(a):
        def f(x):
            return x * a
        return f

    assert fn_content_key(make(2.0)) == fn_content_key(make(2.0))
    assert fn_content_key(make(2.0)) != fn_content_key(make(3.0))
    with pytest.raises(jitcache.Unkeyable):
        fn_content_key(make(object()))


def test_mesh_fingerprint_registry():
    import jax
    from jax.sharding import Mesh

    mesh_a = Mesh(np.asarray(jax.devices()), ("data",))
    mesh_b = Mesh(np.asarray(jax.devices()), ("data",))
    fp = jitcache.mesh_fingerprint(mesh_a)
    assert jitcache.mesh_fingerprint(mesh_b) == fp
    # one representative mesh per fingerprint
    assert jitcache.mesh_for(fp) is not None


# ---------------------------------------------------------------------------
# kmeans assign: shared across model loads + bucketing bit-parity
# ---------------------------------------------------------------------------

def _kmeans_model(k=3, d=4, seed=0, metric="EUCLIDEAN"):
    rng = np.random.default_rng(seed)
    C = rng.normal(size=(k, d)).astype(np.float32)
    cols = [f"f{i}" for i in range(d)]
    return model_to_table(
        {"modelName": "KMeansModel", "k": k, "distanceType": metric,
         "vectorCol": None, "featureCols": cols, "dim": d},
        {"centroids": C})


def _feature_table(n, d=4, seed=1):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, d))
    return MTable({f"f{i}": X[:, i] for i in range(d)})


def test_kmeans_model_load_shares_one_program():
    from alink_tpu.operator.batch.clustering import KMeansModelMapper

    model = _kmeans_model()
    t = _feature_table(25)
    m1 = KMeansModelMapper(model.schema, t.schema).load_model(model)
    n_programs = len(programs("kmeans.assign"))
    hits0 = metrics.counter("jit.program_hit")
    # loading N more copies of the same model registers ZERO new programs
    mappers = [KMeansModelMapper(model.schema, t.schema).load_model(model)
               for _ in range(3)]
    assert len(programs("kmeans.assign")) == n_programs
    assert metrics.counter("jit.program_hit") >= hits0 + 3
    out1 = m1.map_table(t)
    c0 = _compiles()
    for m in mappers:
        out = m.map_table(t)
        assert np.array_equal(np.asarray(out.col("pred")),
                              np.asarray(out1.col("pred")))
    assert _compiles() == c0   # sibling loads predict with zero new compiles


def test_kmeans_bucketed_bit_parity(monkeypatch):
    from alink_tpu.operator.batch.clustering import KMeansModelMapper

    model = _kmeans_model()
    for n in (5, 33, 100):
        t = _feature_table(n, seed=n)
        m = KMeansModelMapper(model.schema, t.schema,
                              predictionDetailCol="detail").load_model(model)
        got = m.map_table(t)
        monkeypatch.setenv("ALINK_SHAPE_BUCKETS", "off")
        want = KMeansModelMapper(model.schema, t.schema,
                                 predictionDetailCol="detail") \
            .load_model(model).map_table(t)
        monkeypatch.delenv("ALINK_SHAPE_BUCKETS")
        assert np.array_equal(np.asarray(got.col("pred")),
                              np.asarray(want.col("pred")))
        # the per-row distance details must be bit-identical too
        assert list(got.col("detail")) == list(want.col("detail"))


def test_kmeans_batch_size_sweep_zero_recompiles():
    from alink_tpu.operator.batch.clustering import KMeansModelMapper

    model = _kmeans_model(seed=7)
    m = KMeansModelMapper(model.schema, _feature_table(1).schema) \
        .load_model(model)
    # warm the buckets this sweep will land in (40 and 128)
    for n in (40, 100):
        m.map_table(_feature_table(n, seed=n))
    c0, t0 = _compiles(), _traces()
    for n in (33, 34, 39, 40, 65, 90, 128, 127):
        m.map_table(_feature_table(n, seed=n))
    assert _compiles() == c0, "steady-state sweep must not compile"
    assert _traces() == t0, "steady-state sweep must not trace"


# ---------------------------------------------------------------------------
# linear predict: bit-parity + sweep
# ---------------------------------------------------------------------------

def _linear_model(d=3):
    return model_to_table(
        {"modelName": "LinearModel", "linearModelType": "LinearReg",
         "vectorCol": None, "featureCols": [f"f{i}" for i in range(d)],
         "labelCol": "y", "labelType": AlinkTypes.DOUBLE, "labels": None,
         "hasIntercept": True, "dim": d},
        {"weights": np.asarray([1.5, -2.0, 0.25], np.float32),
         "intercept": np.asarray([0.125], np.float32)})


def test_linear_predict_bucketed_bit_parity(monkeypatch):
    from alink_tpu.operator.batch.linear import LinearModelMapper

    model = _linear_model()
    for n in (1, 37, 200):
        t = _feature_table(n, d=3, seed=n)
        got = np.asarray(
            LinearModelMapper(model.schema, t.schema, predictionCol="p")
            .load_model(model).map_table(t).col("p"))
        monkeypatch.setenv("ALINK_SHAPE_BUCKETS", "off")
        want = np.asarray(
            LinearModelMapper(model.schema, t.schema, predictionCol="p")
            .load_model(model).map_table(t).col("p"))
        monkeypatch.delenv("ALINK_SHAPE_BUCKETS")
        assert np.array_equal(got, want)


def test_linear_sweep_zero_recompiles_across_model_loads():
    from alink_tpu.operator.batch.linear import LinearModelMapper

    model = _linear_model()
    t0 = _feature_table(64, d=3)
    LinearModelMapper(model.schema, t0.schema, predictionCol="p") \
        .load_model(model).map_table(t0)
    c0 = _compiles()
    # fresh mapper instances (a new predict op per job) + varying sizes in
    # the warmed bucket: zero new compiles
    for n in (57, 63, 64):
        t = _feature_table(n, d=3, seed=n)
        LinearModelMapper(model.schema, t.schema, predictionCol="p") \
            .load_model(model).map_table(t)
    assert _compiles() == c0


# ---------------------------------------------------------------------------
# fused mapper chains
# ---------------------------------------------------------------------------

def _affine_mapper(col, out, a, b):
    from alink_tpu.mapper.base import BlockKernelMapper

    class _M(BlockKernelMapper):
        def kernel(self, schema):
            def fn(X):
                return X * a + b

            return ([col], [out], [AlinkTypes.DOUBLE], fn)

    return _M()


def _chain(a=2.0):
    from alink_tpu.mapper.base import FusedMapperChain

    return FusedMapperChain([_affine_mapper("x", "x1", a, 1.0),
                             _affine_mapper("x1", "x2", 0.5, -3.0)])


def test_fused_chain_bit_parity(monkeypatch):
    rng = np.random.default_rng(2)
    t = MTable({"x": rng.normal(size=75)})
    got = np.asarray(_chain().map_table(t).col("x2"))
    monkeypatch.setenv("ALINK_SHAPE_BUCKETS", "off")
    want = np.asarray(_chain().map_table(t).col("x2"))
    monkeypatch.delenv("ALINK_SHAPE_BUCKETS")
    assert np.array_equal(got, want)


def test_fused_chain_steady_state_and_content_keys():
    rng = np.random.default_rng(3)
    _chain().map_table(MTable({"x": rng.normal(size=100)}))
    c0 = _compiles()
    # rebuilt chains (fresh mapper instances, same captured constants) over
    # a batch-size sweep inside the warmed bucket: zero new traces
    for n in (100, 97, 70, 128):
        _chain().map_table(MTable({"x": rng.normal(size=n)}))
    assert _compiles() == c0
    # a different captured constant is a DIFFERENT program (no false hit)
    out9 = _chain(a=9.0).map_table(MTable({"x": np.ones(10)}))
    assert np.asarray(out9.col("x2"))[0] == pytest.approx((9.0 + 1.0) * 0.5 - 3.0)


def test_chain_with_np_capture_is_content_keyed():
    # numpy captures are content-DIGESTED into the key (not token-keyed), so
    # two instances with equal arrays share a program and an in-place array
    # swap cannot serve a stale program
    from alink_tpu.mapper.base import BlockKernelMapper, FusedMapperChain

    class _Closed(BlockKernelMapper):
        def __init__(self, w, *a, **kw):
            super().__init__(*a, **kw)
            self.w = np.asarray([w], np.float32)

        def kernel(self, schema):
            w = self.w

            def fn(X):
                return X * w[0]

            return (["x"], ["z"], [AlinkTypes.DOUBLE], fn)

    t = MTable({"x": np.arange(80, dtype=np.float64)})
    out1 = np.asarray(FusedMapperChain([_Closed(2.0)]).map_table(t).col("z"))
    c0 = _compiles()
    out2 = np.asarray(FusedMapperChain([_Closed(2.0)]).map_table(t).col("z"))
    assert _compiles() == c0          # equal content: shared program
    assert np.array_equal(out1, out2)
    out3 = np.asarray(FusedMapperChain([_Closed(5.0)]).map_table(t).col("z"))
    assert out3[2] == pytest.approx(10.0)   # new content: new program


def test_chain_with_unkeyable_capture_falls_back_to_instance_token():
    from alink_tpu.mapper.base import BlockKernelMapper, FusedMapperChain

    class _Closed(BlockKernelMapper):
        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            self.w = np.asarray([2.0], np.float32)

        def kernel(self, schema):
            def fn(X):
                return X * self.w[0]   # captures `self` → Unkeyable

            return (["x"], ["z"], [AlinkTypes.DOUBLE], fn)

    m = _Closed()
    with pytest.raises(jitcache.Unkeyable):
        fn_content_key(m.kernel(None)[3])
    chain = FusedMapperChain([m])
    t = MTable({"x": np.arange(80, dtype=np.float64)})
    out1 = np.asarray(chain.map_table(t).col("z"))
    c0 = _compiles()
    out2 = np.asarray(chain.map_table(t).col("z"))  # same instance: cached
    assert _compiles() == c0
    assert np.array_equal(out1, out2)
    # a DIFFERENT instance gets a fresh token (no false sharing)
    out3 = np.asarray(FusedMapperChain([_Closed()]).map_table(t).col("z"))
    assert np.array_equal(out1, out3)


# ---------------------------------------------------------------------------
# ragged stream chunks (FTRL)
# ---------------------------------------------------------------------------

def _run_ftrl(n, chunk=64, seed=11):
    from alink_tpu.operator.stream.base import TableSourceStreamOp
    from alink_tpu.operator.stream.onlinelearning import FtrlTrainStreamOp

    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, 3))
    y = (X[:, 0] > 0).astype(np.int64)
    t = MTable({"f0": X[:, 0], "f1": X[:, 1], "f2": X[:, 2], "label": y})
    op = FtrlTrainStreamOp(labelCol="label",
                           featureCols=["f0", "f1", "f2"]).link_from(
        TableSourceStreamOp(t, chunkSize=chunk))
    last = None
    for snap in op._stream():
        last = snap
    return last


def test_ftrl_ragged_final_chunk_bit_parity(monkeypatch):
    from alink_tpu.common.model import table_to_model

    got = _run_ftrl(161)           # chunks 64, 64, 33 → ragged tail
    monkeypatch.setenv("ALINK_SHAPE_BUCKETS", "off")
    want = _run_ftrl(161)
    monkeypatch.delenv("ALINK_SHAPE_BUCKETS")
    _, a = table_to_model(got)
    _, b = table_to_model(want)
    # zero-row padding is a bit-exact FTRL no-op: identical accumulators,
    # identical emitted model
    assert np.array_equal(a["weights"], b["weights"])
    assert np.array_equal(a["intercept"], b["intercept"])


def test_ftrl_second_stream_zero_recompiles():
    _run_ftrl(161)                 # warm: buckets 64 and 40
    c0 = _compiles()
    _run_ftrl(167, seed=12)        # chunks 64, 64, 39 → same buckets
    assert _compiles() == c0


def test_ftrl_steady_off_ladder_chunks_run_unpadded():
    # steady chunk size 65 is OFF the bucket ladder and must never pad (the
    # FTRL step is a sequential per-row scan — padding every steady chunk
    # would be pure wasted work). A single-label FIRST chunk triggers the
    # warm-up merge; the steady size must still be the raw 65, not the
    # merged size.
    from alink_tpu.operator.stream.base import TableSourceStreamOp
    from alink_tpu.operator.stream.onlinelearning import FtrlTrainStreamOp

    rng = np.random.default_rng(21)
    n = 65 * 4
    X = rng.normal(size=(n, 3))
    y = (X[:, 0] > 0).astype(np.int64)
    y[:65] = 0                      # first chunk single-label → warm-up buffer
    t = MTable({"f0": X[:, 0], "f1": X[:, 1], "f2": X[:, 2], "label": y})
    op = FtrlTrainStreamOp(labelCol="label",
                           featureCols=["f0", "f1", "f2"]).link_from(
        TableSourceStreamOp(t, chunkSize=65))
    for _ in op._stream():
        pass
    shapes = sorted({leaf[1][0] for p in programs("ftrl.step")
                     for sig in p._sigs
                     for leaf in sig if leaf[0] == "a" and len(leaf[1]) == 2})
    assert 65 in shapes, f"steady 65-row chunks must run unpadded: {shapes}"


# ---------------------------------------------------------------------------
# warmup + shape profile
# ---------------------------------------------------------------------------

def test_warmup_blocks_then_first_call_is_free():
    prog = cached_jit("test.warm", _build_scale, 5.0)
    sig = [((64,), "float32")]
    res = warmup([("test.warm", sig)], block=True)
    assert res["compiled"] >= 1 and res["errors"] == 0
    c0 = _compiles()
    out = prog(np.ones(64, np.float32))
    assert np.asarray(out)[0] == 5.0
    assert _compiles() == c0, "warmed shape must not compile on first use"
    # re-warming the same sig is a no-op
    assert warmup([("test.warm", sig)], block=True)["compiled"] == 0


def test_warmup_background_thread():
    cached_jit("test.warmbg", _build_scale, 6.0)
    th = warmup([("test.warmbg", [((8,), "float32")])])
    th.join(timeout=30)
    assert not th.is_alive()
    assert th.result["errors"] == 0


def test_shape_profile_records_and_drives_warmup(tmp_path, monkeypatch):
    path = str(tmp_path / "profile.jsonl")
    monkeypatch.setenv("ALINK_SHAPE_PROFILE", path)
    prog = cached_jit("test.profiled", _build_scale, 7.0)
    prog(np.ones(40, np.float32))
    specs = load_shape_profile(path)
    assert ("test.profiled", [((40,), "<f4")]) in specs
    # a second call with the same sig adds no duplicate record
    prog(np.ones(40, np.float32))
    assert len(load_shape_profile(path)) == len(specs)
    # profile-driven warmup round-trips without error
    assert warmup(specs, block=True)["errors"] == 0


# ---------------------------------------------------------------------------
# whole-fit reuse
# ---------------------------------------------------------------------------

def test_second_identical_pipeline_fit_zero_traces():
    from alink_tpu.operator.batch.base import TableSourceBatchOp
    from alink_tpu.pipeline import KMeans, Pipeline

    rng = np.random.default_rng(5)
    t = MTable({"a": rng.normal(size=60), "b": rng.normal(size=60)})
    src = TableSourceBatchOp(t)

    def fit_once():
        pipe = Pipeline(KMeans(k=3, maxIter=20, featureCols=["a", "b"],
                               predictionCol="pred"))
        return pipe.fit(src).transform(src).collect()

    out1 = fit_once()
    c0, t0 = _compiles(), _traces()
    out2 = fit_once()
    assert _traces() == t0 and _compiles() == c0, \
        "a second identical Pipeline.fit must perform zero new traces"
    assert np.array_equal(np.asarray(out1.col("pred")),
                          np.asarray(out2.col("pred")))


# ---------------------------------------------------------------------------
# observability
# ---------------------------------------------------------------------------

def test_compile_events_land_on_executor_node_phases():
    from alink_tpu.common.metrics import node_phase_context

    prog = cached_jit("test.phases", _build_scale, 11.0)
    phases = {}
    with node_phase_context(phases):
        prog(np.ones(16, np.float32))   # first sig → compile inside the node
    assert phases.get("compile_s", 0.0) > 0.0
    phases2 = {}
    with node_phase_context(phases2):
        prog(np.ones(16, np.float32))   # steady state → no compile phase
    assert "compile_s" not in phases2


def test_executor_phase_summary_includes_compile():
    from alink_tpu.common.metrics import executor_phase_summary

    from alink_tpu.common.tracing import trace_span

    # a unit's record is its span under a scheduled dag.run (PR 36)
    with trace_span("dag.run", nodes=2, units=1):
        with trace_span("CompileProbeOp") as sp:
            sp.phases["compile_s"] = 0.25
    summary = executor_phase_summary()
    assert summary["CompileProbeOp"]["compile_s"] == pytest.approx(0.25)


def test_compile_summary_shape():
    cached_jit("test.summary", _build_scale, 13.0)(np.ones(8, np.float32))
    s = compile_summary()
    assert s["programs"] >= 1
    assert "jit.compile" in s["counters"]
    assert s["hit_rate"] is None or 0.0 <= s["hit_rate"] <= 1.0
    assert s["kernels"]["test.summary"]["signatures"] >= 1
    assert s["kernels"]["test.summary"]["compile"]["count"] >= 1


def test_clear_kernel_drops_only_that_kernel():
    cached_jit("test.drop", _build_scale, 17.0)
    keep = cached_jit("test.keep", _build_scale, 17.0)
    assert jitcache.clear_kernel("test.drop") >= 1
    assert programs("test.drop") == []
    assert cached_jit("test.keep", _build_scale, 17.0) is keep


# ---------------------------------------------------------------------------
# staging-cache HBM sizing (satellite)
# ---------------------------------------------------------------------------

def test_staging_cap_scales_with_device_hbm(monkeypatch):
    import jax

    from alink_tpu.common import staging

    class _Dev:
        def __init__(self, limit):
            self._limit = limit

        def memory_stats(self):
            return {"bytes_limit": self._limit}

    # 16 GiB part: 12% ≈ 1.92 GiB beats the flat 2 GiB default
    monkeypatch.setattr(staging, "_hbm_cap", None)
    monkeypatch.setattr(jax, "local_devices", lambda: [_Dev(16 * 1024 ** 3)])
    assert staging._device_default_cap() == int(16 * 1024 ** 3 * 0.12)
    # huge part: flat 2 GiB cap wins
    monkeypatch.setattr(staging, "_hbm_cap", None)
    monkeypatch.setattr(jax, "local_devices", lambda: [_Dev(64 * 1024 ** 3)])
    assert staging._device_default_cap() == staging._DEFAULT_MAX_BYTES
    # no stats (CPU/old plugin): flat default
    monkeypatch.setattr(staging, "_hbm_cap", None)
    monkeypatch.setattr(jax, "local_devices",
                        lambda: (_ for _ in ()).throw(RuntimeError("no dev")))
    assert staging._device_default_cap() == staging._DEFAULT_MAX_BYTES
    monkeypatch.setattr(staging, "_hbm_cap", None)  # re-probe for real later


def test_staging_cap_env_override_wins(monkeypatch):
    from alink_tpu.common.staging import StagingCache

    monkeypatch.setenv("ALINK_STAGING_CACHE_BYTES", "12345")
    assert StagingCache().max_bytes == 12345
    # an explicit negative value disables the cache (max_bytes <= 0 is the
    # put() no-op path) — it must NOT fall back to the device default
    monkeypatch.setenv("ALINK_STAGING_CACHE_BYTES", "-1")
    assert StagingCache().max_bytes == -1
    monkeypatch.setenv("ALINK_STAGING_CACHE_BYTES", "bogus")
    assert StagingCache(max_bytes=777).max_bytes == 777
    monkeypatch.delenv("ALINK_STAGING_CACHE_BYTES")
    assert StagingCache(max_bytes=777).max_bytes == 777


# NOTE: keep last in the file — shrinking the cap evicts programs other
# tests registered (they re-register on demand; only counters are shared).
def test_program_cache_lru_bound(monkeypatch):
    monkeypatch.setenv("ALINK_PROGRAM_CACHE_SIZE", "2")
    ev0 = metrics.counter("jit.program_evictions")
    p1 = cached_jit("test.lru", _build_scale, 101.0)
    cached_jit("test.lru", _build_scale, 102.0)
    assert cached_jit("test.lru", _build_scale, 101.0) is p1  # hit → MRU
    cached_jit("test.lru", _build_scale, 103.0)   # cap 2: evicts 102 (LRU)
    assert metrics.counter("jit.program_evictions") > ev0
    assert cached_jit("test.lru", _build_scale, 101.0) is p1  # survived
    assert len(programs()) <= 2
