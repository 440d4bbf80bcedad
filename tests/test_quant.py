"""Quantized serving tests — int8/bf16 inference as a first-class
precision policy (``ModelServer.load(..., precision=...)``).

Pins the never-silent contract end to end:

- weight-quantization primitives round-trip within their scales;
- calibration capture is process-wide (the predict fans out across the
  DAG executor pool) and max-merges per site;
- knob-off is byte-identical — an fp32 load serves exactly the
  pre-feature numerics, and fp32/int8 versions of one model coexist in
  the ProgramCache without cross-contamination;
- every refusal path (synthetic sample, degenerate ranges, failed
  accuracy band) is loud: a counted reason and a byte-clean fp32
  fallback;
- the proven policy rides the ``.ak.warmup.json`` sidecar: respawns
  adopt it, reuse its calibration, and reach readiness with zero
  post-warmup traces — single-server, fleet, and modelstream publish.
"""

import threading
import time

import numpy as np
import pytest

from alink_tpu.common import MTable
from alink_tpu.common.exceptions import (
    AkIllegalArgumentException,
    AkIllegalStateException,
    AkPlanValidationException,
)
from alink_tpu.common.metrics import metrics
from alink_tpu.common import quant
from alink_tpu.pipeline import (
    LinearRegression,
    LocalPredictor,
    NaiveBayes,
    Pipeline,
    StandardScaler,
    VectorAssembler,
)
from alink_tpu.serving import ModelServer, ServingConfig

pytestmark = pytest.mark.quant

SCHEMA = "f0 double, f1 double, f2 double, f3 double"
FEATS = ["f0", "f1", "f2", "f3"]


def _counter(name):
    return metrics.counter(name)


def _make_data(n_per=60, seed=0):
    rng = np.random.default_rng(seed)
    X = np.concatenate([rng.normal(c, 0.4, size=(n_per, 4))
                        for c in [(0, 0, 0, 0), (2, 2, 2, 2)]])
    y = np.repeat(["neg", "pos"], n_per)
    t = MTable({f"f{i}": X[:, i] for i in range(4)}).with_column("label", y)
    return X, t


@pytest.fixture(scope="module")
def fitted():
    X, t = _make_data()
    model = Pipeline(
        StandardScaler(selectedCols=FEATS),
        VectorAssembler(selectedCols=FEATS, outputCol="vec"),
        NaiveBayes(vectorCol="vec", labelCol="label", predictionCol="pred"),
    ).fit(t)
    return X, t, model


@pytest.fixture(scope="module")
def serial_rows(fitted):
    """fp32 ground truth: serial, uncached-plan, single-row predicts."""
    X, _, model = fitted
    lp = LocalPredictor(model, SCHEMA, cache_plan=False)
    return [lp.predict_row(tuple(r)) for r in X]


@pytest.fixture(scope="module")
def fitted_lr():
    """A regressor whose output column is NUMERIC — the accuracy band's
    max_rel_diff leg only has teeth on numeric outputs (the NB label
    column gates on agreement instead)."""
    X, t = _make_data(seed=3)
    y = X @ np.array([0.5, -1.0, 2.0, 0.25]) + 1.0
    t = t.drop(["label"]).with_column("y", y)
    model = Pipeline(
        LinearRegression(featureCols=FEATS, labelCol="y",
                         predictionCol="pred"),
    ).fit(t)
    return X, model


# ---------------------------------------------------------------------------
# unit: weight quantization primitives
# ---------------------------------------------------------------------------


def test_quantize_per_channel_round_trip():
    rng = np.random.default_rng(1)
    w = rng.normal(0, 3, size=(16, 5)).astype(np.float32)
    wq, scale = quant.quantize_per_channel(w, axis=-1)
    assert wq.dtype == np.int8 and scale.shape == (5,)
    back = quant.dequantize(wq, scale, axis=-1)
    # symmetric rounding: error bounded by half an lsb per channel
    assert np.all(np.abs(back - w) <= scale[None, :] * 0.5 + 1e-7)


def test_quantize_per_channel_zero_channel_exact():
    w = np.zeros((4, 3), np.float32)
    w[:, 1] = [1.0, -2.0, 0.5, 0.25]
    wq, scale = quant.quantize_per_channel(w)
    assert scale[0] == 1.0 and scale[2] == 1.0  # all-zero channels
    assert np.array_equal(quant.dequantize(wq, scale)[:, 0], w[:, 0])


def test_quantize_per_channel_1d():
    w = np.array([1.0, -127.0, 63.5], np.float32)
    wq, scale = quant.quantize_per_channel(w)
    assert wq.dtype == np.int8 and scale.ndim == 0
    assert np.allclose(wq * float(scale), w, atol=float(scale) / 2 + 1e-7)


def test_quantize_last_axis_shapes_and_zero_rows():
    rng = np.random.default_rng(2)
    leaves = rng.normal(0, 1, size=(3, 2, 8)).astype(np.float32)
    leaves[1, 0] = 0.0
    lq, ls = quant.quantize_last_axis(leaves)
    assert lq.shape == leaves.shape and ls.shape == (3, 2)
    assert ls[1, 0] == 1.0
    back = lq.astype(np.float32) * ls[..., None]
    assert np.all(np.abs(back - leaves) <= ls[..., None] * 0.5 + 1e-7)


def test_quantize_tree_weight_only():
    params = {"w1": np.ones((4, 3), np.float32) * 0.5,
              "b1": np.arange(3, dtype=np.float32),
              "steps": np.array([1, 2], np.int64)}
    q, s = quant.quantize_tree(params)
    assert q["w1"].dtype == np.int8 and s["w1"].shape == (3,)
    # 1-D floats and integer leaves pass through untouched, scale None
    assert np.array_equal(q["b1"], params["b1"]) and s["b1"] is None
    assert np.array_equal(q["steps"], params["steps"]) and s["steps"] is None
    assert np.allclose(quant.dequantize(q["w1"], s["w1"]), params["w1"])


def test_resolve_policy():
    assert quant.resolve_policy(None) is None
    assert quant.resolve_policy("") is None
    assert quant.resolve_policy("fp32") is None
    assert quant.resolve_policy("INT8") == quant.INT8
    assert quant.resolve_policy("bf16") == quant.BF16
    with pytest.raises(AkIllegalArgumentException):
        quant.resolve_policy("fp8")


def test_calib_scale_refuses_uncovered_site():
    with pytest.raises(AkIllegalStateException):
        quant.calib_scale(None, "m:op0.x")


# ---------------------------------------------------------------------------
# unit: calibration capture (process-wide, cross-thread)
# ---------------------------------------------------------------------------


def test_observe_is_noop_outside_calibration():
    rec_before = dict()
    quant.observe("m:op0.x", np.ones((2, 2)))
    assert not quant.capturing() and rec_before == {}


def test_calibration_max_merges_across_batches():
    rec = {}
    with quant.calibration(rec):
        assert quant.capturing()
        quant.observe("s", np.array([1.0, -3.0]))
        quant.observe("s", np.array([2.0]))
        quant.observe("t", np.zeros(0))         # empty block -> 0.0
        quant.observe("u", np.array([np.inf]))  # non-finite -> inf
    assert not quant.capturing()
    assert rec == {"s": 3.0, "t": 0.0, "u": float("inf")}


def test_calibration_sees_observes_from_other_threads():
    """The serving predict fans out across the DAG executor pool, so the
    mapper calling observe() is rarely the thread that opened the
    context — capture must be process-wide, not thread-local."""
    rec = {}
    with quant.calibration(rec):
        th = threading.Thread(
            target=lambda: quant.observe("x", np.array([4.5])))
        th.start()
        th.join()
    assert rec == {"x": 4.5}


def test_degenerate_sites():
    assert quant.degenerate_sites({"a": 1.0, "b": 0.0,
                                   "c": float("inf")}) == \
        {"b": 0.0, "c": float("inf")}
    assert quant.degenerate_sites({}) == {}
    assert quant.degenerate_sites(None) == {}


def test_accuracy_band_report_legs():
    from alink_tpu.common.mtable import AlinkTypes

    base = [(1.0, "pos", '{"p": 0.9}'), (2.0, "neg", '{"p": 0.1}')]
    good = [(1.004, "pos", '{"p": 0.91}'), (2.0, "neg", '{"p": 0.1}')]
    types = [AlinkTypes.DOUBLE, AlinkTypes.STRING, AlinkTypes.STRING]
    rep = quant.accuracy_band_report(base, good, types, band=0.0, tol=0.01)
    # JSON-detail strings are skipped; numeric drift inside tol; labels agree
    assert rep["ok"] and rep["agreement"] == 1.0
    assert rep["max_rel_diff"] == pytest.approx(0.004, abs=1e-6)

    flipped = [(1.0, "neg", "{}"), (2.0, "neg", "{}")]
    rep = quant.accuracy_band_report(base, flipped, types, band=0.0,
                                     tol=0.01)
    assert not rep["ok"] and rep["agreement"] == 0.5

    drifted = [(1.5, "pos", "{}"), (2.0, "neg", "{}")]
    rep = quant.accuracy_band_report(base, drifted, types, band=0.0,
                                     tol=0.01)
    assert not rep["ok"] and rep["max_rel_diff"] == pytest.approx(0.5)


# ---------------------------------------------------------------------------
# serving: knob-off identity, int8 lifecycle, coexistence
# ---------------------------------------------------------------------------


def test_knob_off_is_byte_identical(fitted, serial_rows):
    """No precision arg, no precision config: the served numerics are
    exactly the pre-feature fp32 results."""
    X, _, model = fitted
    srv = ModelServer(ServingConfig(max_batch_rows=16))
    try:
        info = srv.load("plain", model, SCHEMA, warmup_rows=[tuple(X[0])])
        assert info["precision"] == {"policy": "fp32"}
        got = [srv.predict("plain", tuple(r)) for r in X]
        assert got == serial_rows
        st = srv.stats()["models"][0]
        assert st["precision"] == "fp32"
    finally:
        srv.close()


def test_int8_load_calibrates_gates_and_serves_zero_trace(fitted,
                                                          serial_rows):
    X, _, model = fitted
    loads0 = _counter("serving.precision_loads")
    srv = ModelServer(ServingConfig(max_batch_rows=16))
    try:
        info = srv.load("m8", model, SCHEMA,
                        warmup_rows=[tuple(r) for r in X[::3]],
                        precision="int8")
        prec = info["precision"]
        assert prec["policy"] == "int8" and "fallback" not in prec
        assert prec["calib_source"] == "live"
        # deterministic model-name-prefixed sites, healthy ranges
        assert prec["calib"] and all(k.startswith("m8:op")
                                     for k in prec["calib"])
        assert not quant.degenerate_sites(prec["calib"])
        assert prec["band_report"]["ok"]
        assert _counter("serving.precision_loads") == loads0 + 1
        assert srv.stats()["models"][0]["precision"] == "int8"

        # post-warmup traffic: labels match fp32 over BOTH clusters, zero
        # new traces at any batch size on the ladder
        t0 = _counter("jit.trace")
        got = [srv.predict("m8", tuple(r)) for r in X]
        batch = srv.predict_many("m8", [tuple(r) for r in X[:13]])
        assert _counter("jit.trace") == t0, \
            "quantized traffic after warmup must not trace"
        assert [r[-1] for r in got] == [r[-1] for r in serial_rows]
        assert [r[-1] for r in batch] == [r[-1] for r in serial_rows[:13]]
    finally:
        srv.close()


def test_fp32_and_int8_coexist_without_cross_contamination(fitted,
                                                           serial_rows):
    """The same model under two precisions at once: the fp32 replica's
    results stay byte-identical to serial while the int8 replica serves —
    the quantized programs live under their own ProgramCache keys."""
    X, _, model = fitted
    srv = ModelServer(ServingConfig(max_batch_rows=16))
    try:
        srv.load("f32", model, SCHEMA, warmup_rows=[tuple(X[0])])
        srv.load("i8", model, SCHEMA,
                 warmup_rows=[tuple(r) for r in X[::3]], precision="int8")
        t0 = _counter("jit.trace")
        inter = []
        for r in X[:30]:
            inter.append(srv.predict("f32", tuple(r)))
            srv.predict("i8", tuple(r))
        assert inter == serial_rows[:30]          # byte-identical fp32
        assert _counter("jit.trace") == t0        # both warmed, both reuse
        by_name = {m["model"]: m for m in srv.stats()["models"]}
        assert by_name["f32"]["precision"] == "fp32"
        assert by_name["i8"]["precision"] == "int8"
    finally:
        srv.close()


def test_hot_swap_precision_and_back(fitted, serial_rows):
    """fp32 -> int8 -> fp32 hot-swaps under one name; the final fp32
    incarnation is byte-identical to serial (stamped precision params are
    stripped clean on the way out)."""
    X, _, model = fitted
    srv = ModelServer(ServingConfig(max_batch_rows=16))
    try:
        srv.load("swap", model, SCHEMA, warmup_rows=[tuple(X[0])])
        info = srv.load("swap", model, SCHEMA,
                        warmup_rows=[tuple(r) for r in X[::3]],
                        precision="int8")
        assert info["precision"]["policy"] == "int8"
        assert srv.stats()["models"][0]["precision"] == "int8"
        info = srv.load("swap", model, SCHEMA, warmup_rows=[tuple(X[0])])
        assert info["precision"] == {"policy": "fp32"}
        got = [srv.predict("swap", tuple(r)) for r in X]
        assert got == serial_rows
    finally:
        srv.close()


def test_bf16_policy_gates_and_reuses_f32_programs(fitted):
    """bf16 changes values, never shapes/dtypes on the wire — traffic
    after warmup reuses the already-compiled programs."""
    X, _, model = fitted
    srv = ModelServer(ServingConfig(max_batch_rows=16))
    try:
        info = srv.load("b16", model, SCHEMA,
                        warmup_rows=[tuple(r) for r in X[::3]],
                        precision="bf16")
        prec = info["precision"]
        assert prec["policy"] == "bf16" and "fallback" not in prec
        assert prec["band_report"]["ok"]
        t0 = _counter("jit.trace")
        srv.predict_many("b16", [tuple(r) for r in X[:16]])
        assert _counter("jit.trace") == t0
    finally:
        srv.close()


# ---------------------------------------------------------------------------
# refusal paths: loud, counted, byte-clean fp32 fallback
# ---------------------------------------------------------------------------


def test_synthetic_rows_refuse_int8(fitted, serial_rows, tmp_path):
    """A load with only schema-synthesized zero rows must never seed
    activation ranges: int8 is refused, fp32 serves byte-identically."""
    X, _, model = fitted
    ak = str(tmp_path / "m.ak")
    model.save(ak)
    skipped0 = _counter("serving.calib_skipped_synthetic")
    fb0 = _counter("serving.precision_fallback")
    srv = ModelServer(ServingConfig(max_batch_rows=16))
    try:
        info = srv.load("syn", ak, SCHEMA, precision="int8")
        assert info["warmup_source"] == "synthesized"
        prec = info["precision"]
        assert prec["policy"] == "fp32" and "synthetic" in prec["fallback"]
        assert _counter("serving.calib_skipped_synthetic") == skipped0 + 1
        assert _counter("serving.precision_fallback") == fb0 + 1
        assert srv.stats()["models"][0]["precision"] == "fp32"
        got = [srv.predict("syn", tuple(r)) for r in X[:20]]
        assert got == serial_rows[:20]
    finally:
        srv.close()


def test_synthetic_sidecar_rows_never_count_as_real(fitted, serial_rows,
                                                    tmp_path):
    """Sidecar rows a previous replica SYNTHESIZED carry the
    ``synthetic_rows`` marker — a later int8 load must refuse them just
    like a live synthesized sample."""
    from alink_tpu.serving import load_warmup_spec

    X, _, model = fitted
    ak = str(tmp_path / "m.ak")
    model.save(ak)
    srv = ModelServer(ServingConfig(max_batch_rows=16))
    try:
        srv.load("seed", ak, SCHEMA)  # synthesized rows -> marked sidecar
        assert load_warmup_spec(ak).get("synthetic_rows") is True
        skipped0 = _counter("serving.calib_skipped_synthetic")
        info = srv.load("adopt", ak, precision="int8")
        assert info["warmup_source"] == "sidecar"
        assert info["precision"]["policy"] == "fp32"
        assert _counter("serving.calib_skipped_synthetic") == skipped0 + 1
        got = [srv.predict("adopt", tuple(r)) for r in X[:10]]
        assert got == serial_rows[:10]
    finally:
        srv.close()


def test_band_gate_failure_falls_back_byte_equal(fitted_lr):
    """band=0/tol=0 on a numeric-output model: real int8 rounding error
    must fail the gate, and the fallback serves EXACTLY fp32."""
    X, model = fitted_lr
    ref = LocalPredictor(model, SCHEMA, cache_plan=False)
    expect = [ref.predict_row(tuple(r)) for r in X[:20]]
    gate0 = _counter("serving.band_gate_failed")
    fb0 = _counter("serving.precision_fallback")
    srv = ModelServer(ServingConfig(max_batch_rows=16, quant_band=0.0,
                                    quant_tol=0.0))
    try:
        info = srv.load("lr0", model, SCHEMA,
                        warmup_rows=[tuple(r) for r in X[::3]],
                        precision="int8")
        prec = info["precision"]
        assert prec["policy"] == "fp32" and "accuracy band" in \
            prec["fallback"]
        assert prec["band_report"]["max_rel_diff"] > 0.0
        assert _counter("serving.band_gate_failed") == gate0 + 1
        assert _counter("serving.precision_fallback") == fb0 + 1
        got = [srv.predict("lr0", tuple(r)) for r in X[:20]]
        assert got == expect
    finally:
        srv.close()


def test_default_band_admits_int8_regressor(fitted_lr):
    """The same model/rows pass under the default band — and the served
    int8 numerics stay inside quant_tol on rows OUTSIDE the warmup
    sample (the two-cluster sample covers the input range)."""
    X, model = fitted_lr
    ref = LocalPredictor(model, SCHEMA, cache_plan=False)
    srv = ModelServer(ServingConfig(max_batch_rows=16))
    try:
        info = srv.load("lr", model, SCHEMA,
                        warmup_rows=[tuple(r) for r in X[::3]],
                        precision="int8")
        assert info["precision"]["policy"] == "int8"
        tol = 0.05  # the ServingConfig default quant_tol
        for r in X[1::7]:
            b = float(ref.predict_row(tuple(r))[-1])
            c = float(srv.predict("lr", tuple(r))[-1])
            assert abs(b - c) / max(1.0, abs(b)) <= tol
    finally:
        srv.close()


def test_uncached_plan_refuses_precision(fitted):
    """Precision policies ride stamped plan params — a predictor that
    rebuilds its plan per call cannot hold them."""
    X, _, model = fitted
    lp = LocalPredictor(model, SCHEMA, cache_plan=False)
    un0 = _counter("serving.precision_plan_uncached")
    srv = ModelServer(ServingConfig(max_batch_rows=8))
    try:
        info = srv.load("raw", lp, warmup_rows=[tuple(r) for r in X[::3]],
                        precision="int8")
        assert info["precision"]["policy"] == "fp32"
        assert _counter("serving.precision_plan_uncached") == un0 + 1
    finally:
        srv.close()


# ---------------------------------------------------------------------------
# sidecar: the proven policy survives respawns with zero traces
# ---------------------------------------------------------------------------


def test_sidecar_precision_block_respawn_adopts_and_reuses(fitted,
                                                           tmp_path):
    """First int8 load proves calibration + band and persists them; a
    path-only respawn adopts the policy, reuses the calibration (no
    re-gate), and serves identical predictions with zero new traces."""
    from alink_tpu.serving import load_warmup_spec

    X, _, model = fitted
    ak = str(tmp_path / "m.ak")
    model.save(ak)
    srv1 = ModelServer(ServingConfig(max_batch_rows=16))
    try:
        info1 = srv1.load("q", ak, SCHEMA,
                          warmup_rows=[tuple(r) for r in X[::3]],
                          precision="int8")
        assert info1["precision"]["policy"] == "int8"
        first = [srv1.predict("q", tuple(r)) for r in X[:30]]
    finally:
        srv1.close()
    spec = load_warmup_spec(ak)
    assert spec["precision"]["policy"] == "int8"
    assert spec["precision"]["calib"] == info1["precision"]["calib"]
    assert spec["precision"]["band"] == {"band": 0.005, "tol": 0.05}

    adopted0 = _counter("serving.precision_sidecar_adopted")
    reused0 = _counter("serving.calib_reused_sidecar")
    srv2 = ModelServer(ServingConfig(max_batch_rows=16))
    try:
        info2 = srv2.load("q", ak)      # nothing but the path
        prec = info2["precision"]
        assert prec["policy"] == "int8"
        assert prec["adopted_from_sidecar"] and \
            prec["calib_source"] == "sidecar"
        assert "band_report" not in prec  # the first replica's gate holds
        assert _counter("serving.precision_sidecar_adopted") == adopted0 + 1
        assert _counter("serving.calib_reused_sidecar") == reused0 + 1
        t0 = _counter("jit.trace")
        got = [srv2.predict("q", tuple(r)) for r in X[:30]]
        assert _counter("jit.trace") == t0, \
            "a sidecar-adopted quantized respawn must not trace"
        assert got == first
    finally:
        srv2.close()


def test_sidecar_adoption_under_a_different_name(fitted, tmp_path):
    """Calibration sites are model-name-prefixed; a SECOND serving name
    over the same .ak must adopt the proven ranges REKEYED onto its own
    name (regression: the verbatim reuse stamped ranges no site could
    find and crashed the load mid-warmup)."""
    X, _, model = fitted
    ak = str(tmp_path / "m.ak")
    model.save(ak)
    srv = ModelServer(ServingConfig(max_batch_rows=16))
    try:
        srv.load("orig", ak, SCHEMA,
                 warmup_rows=[tuple(r) for r in X[::3]], precision="int8")
        first = [srv.predict("orig", tuple(r)) for r in X[:20]]
        info = srv.load("twin", ak)     # path-only, different name
        prec = info["precision"]
        assert prec["policy"] == "int8" and \
            prec["calib_source"] == "sidecar"
        assert prec["calib"] and all(k.startswith("twin:op")
                                     for k in prec["calib"])
        assert [srv.predict("twin", tuple(r)) for r in X[:20]] == first
    finally:
        srv.close()


def test_explicit_fp32_blocks_sidecar_adoption(fitted, serial_rows,
                                               tmp_path):
    X, _, model = fitted
    ak = str(tmp_path / "m.ak")
    model.save(ak)
    srv = ModelServer(ServingConfig(max_batch_rows=16))
    try:
        srv.load("q", ak, SCHEMA, warmup_rows=[tuple(r) for r in X[::3]],
                 precision="int8")
        info = srv.load("pin32", ak, precision="fp32")
        assert info["precision"] == {"policy": "fp32"}
        got = [srv.predict("pin32", tuple(r)) for r in X[:15]]
        assert got == serial_rows[:15]
    finally:
        srv.close()


def test_explicit_fp32_rolls_back_the_sidecar_policy(fitted, serial_rows,
                                                     tmp_path):
    """An explicit fp32 load is the ROLLBACK lever: after its warmup the
    rewritten sidecar carries no precision block (last-writer-wins, the
    sidecar's usual semantic), so later path-only respawns serve fp32."""
    from alink_tpu.serving import load_warmup_spec

    X, _, model = fitted
    ak = str(tmp_path / "m.ak")
    model.save(ak)
    srv = ModelServer(ServingConfig(max_batch_rows=16))
    try:
        srv.load("m", ak, SCHEMA, warmup_rows=[tuple(r) for r in X[::3]],
                 precision="int8")
        assert load_warmup_spec(ak)["precision"]["policy"] == "int8"
        srv.load("m", ak, SCHEMA, warmup_rows=[tuple(r) for r in X[::3]],
                 precision="fp32")
        assert load_warmup_spec(ak).get("precision") is None
    finally:
        srv.close()
    srv2 = ModelServer(ServingConfig(max_batch_rows=16))
    try:
        info = srv2.load("m", ak)
        assert info["precision"] == {"policy": "fp32"}
        assert [srv2.predict("m", tuple(r)) for r in X[:10]] == \
            serial_rows[:10]
    finally:
        srv2.close()


# ---------------------------------------------------------------------------
# ALK111 plan rule
# ---------------------------------------------------------------------------


def test_alk111_off_mode_skips(monkeypatch):
    from alink_tpu.analysis import preflight_quantized_load

    monkeypatch.delenv("ALINK_VALIDATE_PLAN", raising=False)
    assert preflight_quantized_load("m", policy="int8", real_sample=False,
                                    band_enabled=True) is None


def test_alk111_warns_on_unproven_load(monkeypatch):
    from alink_tpu.analysis import WARNING, preflight_quantized_load

    monkeypatch.setenv("ALINK_VALIDATE_PLAN", "warn")
    report = preflight_quantized_load("m", policy="int8",
                                      real_sample=False,
                                      band_enabled=False)
    assert report.by_rule() == {"ALK111": 1}
    assert report.diagnostics[0].severity == WARNING
    msg = report.diagnostics[0].message
    assert "no real calibration sample" in msg and "band" in msg


def test_alk111_error_severity_in_recovery(monkeypatch):
    from alink_tpu.analysis import preflight_quantized_load

    monkeypatch.setenv("ALINK_VALIDATE_PLAN", "warn")
    report = preflight_quantized_load("m", policy="int8",
                                      real_sample=False, band_enabled=True,
                                      recovery=True)
    assert len(report.errors()) == 1
    monkeypatch.setenv("ALINK_VALIDATE_PLAN", "error")
    with pytest.raises(AkPlanValidationException):
        preflight_quantized_load("m", policy="int8", real_sample=False,
                                 band_enabled=True, recovery=True)


def test_alk111_clean_with_real_sample(monkeypatch):
    from alink_tpu.analysis import preflight_quantized_load

    monkeypatch.setenv("ALINK_VALIDATE_PLAN", "error")
    report = preflight_quantized_load("m", policy="int8", real_sample=True,
                                      band_enabled=True, recovery=True)
    assert report.ok


def test_alk111_fires_through_server_load(fitted, tmp_path, monkeypatch):
    """The rule is wired into the real load path: a synthetic-sample int8
    load under warn mode records ALK111 (and still refuses + serves
    fp32)."""
    X, _, model = fitted
    ak = str(tmp_path / "m.ak")
    model.save(ak)
    monkeypatch.setenv("ALINK_VALIDATE_PLAN", "warn")
    r0 = _counter("analysis.rule.ALK111")
    srv = ModelServer(ServingConfig(max_batch_rows=8))
    try:
        info = srv.load("syn", ak, SCHEMA, precision="int8")
        assert info["precision"]["policy"] == "fp32"
        assert _counter("analysis.rule.ALK111") == r0 + 1
    finally:
        srv.close()


# ---------------------------------------------------------------------------
# satellites: onnx wrap program sharing
# ---------------------------------------------------------------------------


def test_onnx_wrap_positional_shares_programs():
    """wrap_positional rides cached_jit: re-wrapping the SAME content fn
    reuses the compiled program (zero new traces on the second wrap)."""
    import jax.numpy as jnp

    from alink_tpu.onnx.precision import wrap_positional

    def fn(a, b):
        return jnp.dot(a, b)

    x = np.ones((3, 4), np.float64)
    w = np.full((4, 2), 2.0)
    f1 = wrap_positional(fn, "float32")
    out = np.asarray(f1(x, w))
    assert out.dtype == np.float32 and np.all(out == 8.0)
    t0 = _counter("jit.trace")

    def fn2(a, b):
        return jnp.dot(a, b)

    out2 = np.asarray(wrap_positional(fn2, "float32")(x, w))
    assert _counter("jit.trace") == t0
    assert np.array_equal(out, out2)


def test_onnx_wrap_named_kwargs_path():
    """wrap_named serves the kwargs call sites (modelpredict) through the
    positional program adapter — kwarg ORDER must not matter."""
    import jax.numpy as jnp

    from alink_tpu.onnx.precision import wrap_named

    def fn(**kw):
        return {"y": kw["a"] + 2 * kw["b"]}

    f = wrap_named(fn, "float32")
    a = np.ones((2, 2), np.float64)
    b = np.full((2, 2), 3.0)
    out1 = np.asarray(f(a=a, b=b)["y"])
    out2 = np.asarray(f(b=b, a=a)["y"])
    assert out1.dtype == np.float32
    assert np.array_equal(out1, out2) and np.all(out1 == 7.0)


# ---------------------------------------------------------------------------
# modelstream: publish -> quantized swap, zero traces across versions
# ---------------------------------------------------------------------------


class _Servable:
    def __init__(self, table):
        self._t = table

    def servable_model(self):
        return self._t


def _lr_model_table(slope):
    from alink_tpu.operator.batch import (LinearRegTrainBatchOp,
                                          MemSourceBatchOp)

    rows = [(float(x), float(slope * x + 1.0)) for x in range(-10, 10)]
    src = MemSourceBatchOp(rows, "x double, y double")
    return LinearRegTrainBatchOp(featureCols=["x"], labelCol="y") \
        .link_from(src).collect()


def test_modelstream_publish_quantized_swaps_zero_trace(tmp_path):
    """A publisher targeting an int8 serving config: every published
    version calibrates from the REAL sidecar rows, passes the band, and
    hot-swaps with zero traces after the first load."""
    from alink_tpu.modelstream import ModelStreamPublisher

    delta0 = _counter("modelstream.swap_trace_delta")
    srv = ModelServer()
    cfg = ServingConfig(max_batch_rows=8, precision="int8")
    pub = ModelStreamPublisher(
        str(tmp_path / "store"), "mq", server=srv, input_schema="x double",
        warmup_rows=[(-8.0,), (-2.5,), (0.5,), (3.0,), (9.0,)],
        serving_config=cfg)
    try:
        for epoch, slope in enumerate([2.0, -1.5, 4.0]):
            assert pub.publish_epoch(_Servable(_lr_model_table(slope)),
                                     epoch)
            assert pub.swap_epoch(epoch)
            st = srv.stats()["models"][0]
            assert st["model"] == "mq" and st["precision"] == "int8"
            got = float(srv.predict("mq", (4.0,))[-1])
            want = slope * 4.0 + 1.0
            assert abs(got - want) / max(1.0, abs(want)) <= cfg.quant_tol
        # swaps after the first reuse the compiled quantized ladder
        assert _counter("modelstream.swap_trace_delta") == delta0
    finally:
        srv.close()


# ---------------------------------------------------------------------------
# fleet: quantized replicas, sidecar-warmed respawn
# ---------------------------------------------------------------------------


def _wait(pred, timeout=30.0, interval=0.1):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(interval)
    return False


def test_fleet_quantized_load_and_respawn_zero_trace(fitted, tmp_path):
    """Fleet e2e: every replica serves int8 (each adopting the sidecar's
    proven calibration), and a killed replica's respawn comes back int8,
    sidecar-warmed, with a zero jit-trace delta."""
    from alink_tpu.serving import FleetConfig, ServingFleet

    X, _, model = fitted
    ak = str(tmp_path / "m.ak")
    model.save(ak)
    # prove the policy once — the sidecar precision block every replica
    # (and every respawn) then reproduces without recalibrating
    seed = ModelServer(ServingConfig(max_batch_rows=16))
    try:
        info = seed.load("m", ak, SCHEMA,
                         warmup_rows=[tuple(r) for r in X[::3]],
                         precision="int8")
        assert info["precision"]["policy"] == "int8"
        expect = [seed.predict("m", tuple(r)) for r in X[:12]]
    finally:
        seed.close()

    with ServingFleet(FleetConfig(replicas=2, heartbeat_s=0.2,
                                  heartbeat_timeout_s=1.0)) as fleet:
        out = fleet.load("m", ak, SCHEMA, precision="int8")
        assert out["replicas"] and all(
            o["ok"] and o["precision"] == "int8"
            for o in out["replicas"].values())
        assert [fleet.predict("m", tuple(r)) for r in X[:12]] == expect

        gen0 = max(r["gen"] for r in fleet.fleet_summary()["replicas"]
                   if r["replica"] == "r1")
        fleet._replicas["r1"].proc.kill()
        # the death must be DETECTED before waiting on the respawn
        assert _wait(lambda: any(
            r["replica"] == "r1" and r["gen"] > gen0
            for r in fleet.fleet_summary()["replicas"]), timeout=30.0)
        assert _wait(lambda: fleet.fleet_summary()["states"].get(
            "ready") == 2, timeout=30.0)
        assert _wait(lambda: all(
            r["trace_delta"] == 0 and r["synced"].get("m")
            for r in fleet.fleet_summary()["replicas"]), timeout=10.0)
        respawned = [r for r in fleet.fleet_summary()["replicas"]
                     if r["replica"] == "r1"][0]
        assert respawned["gen"] > gen0
        assert [(ld["warmup_source"], ld["precision"])
                for ld in respawned["loads"]] == [("sidecar", "int8")]
        assert [fleet.predict("m", tuple(r)) for r in X[:12]] == expect
