"""Foreign-model ingest tests: ONNX codec + converter, torch.export -> JAX,
StableHLO export/serve (reference model: dl_predictors predictor-onnx /
predictor-torch / predictor-tf mapper tests, e.g.
predictor-onnx/src/test/java/.../OnnxModelPredictMapperTest.java)."""

import numpy as np
import pytest

from alink_tpu.common.mtable import AlinkTypes, MTable
from alink_tpu.operator.batch import (
    MemSourceBatchOp,
    OnnxModelPredictBatchOp,
    StableHloModelPredictBatchOp,
    TableSourceBatchOp,
    TorchModelPredictBatchOp,
    export_stablehlo,
)
from alink_tpu.operator.stream import (
    OnnxModelPredictStreamOp,
    TableSourceStreamOp,
    TorchModelPredictStreamOp,
)


def _mlp_onnx(path, rng):
    from alink_tpu.onnx import NodeProto, OnnxGraph, OnnxModel, ValueInfo

    W1 = rng.randn(4, 8).astype(np.float32)
    b1 = rng.randn(8).astype(np.float32)
    W2 = rng.randn(8, 3).astype(np.float32)
    b2 = rng.randn(3).astype(np.float32)
    g = OnnxGraph(
        nodes=[
            NodeProto("Gemm", ["x", "W1", "b1"], ["h"]),
            NodeProto("Relu", ["h"], ["hr"]),
            NodeProto("Gemm", ["hr", "W2", "b2"], ["logits"]),
            NodeProto("Softmax", ["logits"], ["probs"]),
        ],
        initializers={"W1": W1, "b1": b1, "W2": W2, "b2": b2},
        inputs=[ValueInfo("x", 1, (None, 4))],
        outputs=[ValueInfo("probs", 1, (None, 3))],
    )
    OnnxModel(g).save(path)

    def ref(x):
        h = np.maximum(x @ W1 + b1, 0) @ W2 + b2
        e = np.exp(h - h.max(1, keepdims=True))
        return e / e.sum(1, keepdims=True)

    return ref


def test_onnx_roundtrip_and_convert(tmp_path):
    rng = np.random.RandomState(0)
    path = str(tmp_path / "mlp.onnx")
    ref = _mlp_onnx(path, rng)

    from alink_tpu.onnx import OnnxModel, OnnxToJax

    m = OnnxModel.load(path)
    assert [n.op_type for n in m.graph.nodes] == [
        "Gemm", "Relu", "Gemm", "Softmax"
    ]
    fn = OnnxToJax(m).jitted()
    x = rng.randn(7, 4).astype(np.float32)
    out = np.asarray(fn(x=x)["probs"])
    np.testing.assert_allclose(out, ref(x), atol=1e-5)


def test_onnx_conv_graph(tmp_path):
    """Conv + BatchNorm + MaxPool + GlobalAveragePool + Flatten pipeline."""
    from alink_tpu.onnx import (
        NodeProto, OnnxGraph, OnnxModel, OnnxToJax, ValueInfo,
    )
    from alink_tpu.onnx.proto import AttributeProto

    rng = np.random.RandomState(1)
    W = rng.randn(6, 3, 3, 3).astype(np.float32) * 0.2
    scale = np.abs(rng.randn(6).astype(np.float32)) + 0.5
    bias = rng.randn(6).astype(np.float32)
    mean = rng.randn(6).astype(np.float32) * 0.1
    var = np.abs(rng.randn(6).astype(np.float32)) + 0.5

    conv_attrs = {
        "pads": AttributeProto("pads", ints=(1, 1, 1, 1)),
        "strides": AttributeProto("strides", ints=(1, 1)),
    }
    pool_attrs = {
        "kernel_shape": AttributeProto("kernel_shape", ints=(2, 2)),
        "strides": AttributeProto("strides", ints=(2, 2)),
    }
    g = OnnxGraph(
        nodes=[
            NodeProto("Conv", ["x", "W"], ["c"], attrs=conv_attrs),
            NodeProto("BatchNormalization",
                      ["c", "scale", "bias", "mean", "var"], ["bn"]),
            NodeProto("Relu", ["bn"], ["r"]),
            NodeProto("MaxPool", ["r"], ["p"], attrs=pool_attrs),
            NodeProto("GlobalAveragePool", ["p"], ["gap"]),
            NodeProto("Flatten", ["gap"], ["y"]),
        ],
        initializers={"W": W, "scale": scale, "bias": bias,
                      "mean": mean, "var": var},
        inputs=[ValueInfo("x", 1, (None, 3, 8, 8))],
        outputs=[ValueInfo("y", 1, (None, 6))],
    )
    path = str(tmp_path / "cnn.onnx")
    OnnxModel(g).save(path)
    fn = OnnxToJax(OnnxModel.load(path)).jitted()

    x = rng.randn(2, 3, 8, 8).astype(np.float32)
    out = np.asarray(fn(x=x)["y"])

    # torch reference of the same math
    import torch
    import torch.nn as nn

    tconv = nn.Conv2d(3, 6, 3, padding=1, bias=False)
    tconv.weight.data = torch.from_numpy(W)
    tbn = nn.BatchNorm2d(6).eval()
    tbn.weight.data = torch.from_numpy(scale)
    tbn.bias.data = torch.from_numpy(bias)
    tbn.running_mean.data = torch.from_numpy(mean)
    tbn.running_var.data = torch.from_numpy(var)
    with torch.no_grad():
        r = torch.relu(tbn(tconv(torch.from_numpy(x))))
        p = nn.functional.max_pool2d(r, 2, 2)
        ref = p.mean(dim=(2, 3)).numpy()
    np.testing.assert_allclose(out, ref, atol=1e-4)


def test_onnx_predict_op(tmp_path):
    rng = np.random.RandomState(2)
    path = str(tmp_path / "mlp.onnx")
    ref = _mlp_onnx(path, rng)
    X = rng.randn(9, 4)
    t = MTable({f"f{i}": X[:, i] for i in range(4)})
    src = TableSourceBatchOp(t)
    op = OnnxModelPredictBatchOp(
        modelPath=path, selectedCols=[f"f{i}" for i in range(4)],
        outputCols=["probs"], predictBatchSize=4,
    ).link_from(src)
    # static schema: no execution needed
    assert op.schema.names == [f"f{i}" for i in range(4)] + ["probs"]
    assert op.schema.type_of("probs") == AlinkTypes.TENSOR
    out = op.collect()
    got = np.stack(list(out.col("probs")))
    np.testing.assert_allclose(got, ref(X.astype(np.float32)), atol=1e-5)


def test_onnx_predict_stream(tmp_path):
    rng = np.random.RandomState(3)
    path = str(tmp_path / "mlp.onnx")
    ref = _mlp_onnx(path, rng)
    X = rng.randn(12, 4)
    t = MTable({f"f{i}": X[:, i] for i in range(4)})
    out = OnnxModelPredictStreamOp(
        modelPath=path, selectedCols=[f"f{i}" for i in range(4)],
        outputCols=["probs"],
    ).link_from(TableSourceStreamOp(t, numChunks=3)).collect()
    got = np.stack(list(out.col("probs")))
    np.testing.assert_allclose(got, ref(X.astype(np.float32)), atol=1e-5)


@pytest.mark.parametrize("kind", ["batch", "stream"])
def test_torch_export_predict_op(tmp_path, kind):
    import torch
    import torch.nn as nn

    torch.manual_seed(0)
    model = nn.Sequential(
        nn.Linear(3, 16), nn.ReLU(), nn.LayerNorm(16), nn.Linear(16, 1),
    ).eval()
    x = torch.randn(4, 3)
    ep = torch.export.export(model, (x,))
    path = str(tmp_path / "mlp.pt2")
    torch.export.save(ep, path)

    X = np.random.RandomState(4).randn(10, 3)
    t = MTable({"a": X[:, 0], "b": X[:, 1], "c": X[:, 2]})
    params = dict(modelPath=path, selectedCols=["a", "b", "c"],
                  outputCols=["score"])
    if kind == "batch":
        op = TorchModelPredictBatchOp(**params).link_from(
            TableSourceBatchOp(t))
        assert op.schema.type_of("score") == AlinkTypes.DOUBLE
    else:  # three micro-batches, the last one ragged
        op = TorchModelPredictStreamOp(predictBatchSize=4, **params).link_from(
            TableSourceStreamOp(t, chunkSize=4))
    out = op.collect()
    assert out.num_rows == len(X)
    assert out.schema.type_of("score") == AlinkTypes.DOUBLE
    with torch.no_grad():
        ref = model(torch.from_numpy(X.astype(np.float32))).numpy()[:, 0]
    np.testing.assert_allclose(
        np.asarray(out.col("score")), ref, atol=1e-5
    )


def test_torch_cnn_convert():
    import torch
    import torch.nn as nn

    from alink_tpu.onnx import load_torch_fn

    torch.manual_seed(1)
    cnn = nn.Sequential(
        nn.Conv2d(3, 8, 3, stride=2, padding=1), nn.BatchNorm2d(8), nn.ReLU(),
        nn.MaxPool2d(2), nn.Conv2d(8, 16, 3, padding=1, groups=2), nn.ReLU(),
        nn.AdaptiveAvgPool2d(1), nn.Flatten(), nn.Linear(16, 5),
        nn.Softmax(dim=-1),
    ).eval()
    x = torch.randn(2, 3, 16, 16)
    fn, _ = load_torch_fn(cnn, (x,))
    out = np.asarray(fn(x.numpy())[0])
    with torch.no_grad():
        ref = cnn(x).numpy()
    np.testing.assert_allclose(out, ref, atol=1e-4)


def test_stablehlo_export_serve(tmp_path):
    """The SavedModel-analog path: flax model -> StableHLO artifact -> serve
    through StableHloModelPredictBatchOp (BASELINE config #3 mechanism)."""
    import jax

    from alink_tpu.dl.resnet import resnet18_like

    model = resnet18_like(num_classes=4, dtype=np.float32)
    rng = np.random.RandomState(5)
    x0 = rng.rand(4, 8, 8, 3).astype(np.float32)
    variables = model.init(jax.random.PRNGKey(0), x0)

    def forward(x):
        return model.apply(variables, x)

    path = str(tmp_path / "resnet.hlo")
    export_stablehlo(forward, (x0,), path)

    imgs = [rng.rand(8, 8, 3).astype(np.float32) for _ in range(4)]
    t = MTable({"img": np.array(imgs, dtype=object)})
    op = StableHloModelPredictBatchOp(
        modelPath=path, selectedCols=["img"], outputCols=["logits"],
        predictBatchSize=4,
    ).link_from(TableSourceBatchOp(t))
    out = op.collect()
    got = np.stack(list(out.col("logits")))
    ref = np.asarray(forward(np.stack(imgs)))
    np.testing.assert_allclose(got, ref, atol=1e-4)


def test_tf_savedmodel_bad_path_raises():
    # the real ingest path (tests/test_tfsaved.py) surfaces load errors for
    # broken artifacts instead of the old API-parity shim's blanket raise
    from alink_tpu.operator.batch import TFSavedModelPredictBatchOp

    t = MTable({"x": np.zeros(3)})
    op = TFSavedModelPredictBatchOp(
        modelPath="/nonexistent", selectedCols=["x"]
    ).link_from(TableSourceBatchOp(t))
    with pytest.raises(Exception):
        op.collect()


def test_torch_pooling_semantics():
    """count_include_pad (avg) and ceil_mode/dilation (max) match torch."""
    import torch
    import torch.nn as nn

    from alink_tpu.onnx import load_torch_fn

    torch.manual_seed(2)
    x = torch.randn(1, 2, 6, 6)
    for mod in [
        nn.AvgPool2d(2, stride=2, padding=1),
        nn.AvgPool2d(3, stride=2, padding=1, count_include_pad=False),
        nn.MaxPool2d(3, stride=2, ceil_mode=True),
        nn.MaxPool2d(3, stride=1, dilation=2),
    ]:
        fn, _ = load_torch_fn(mod.eval(), (x,))
        out = np.asarray(fn(x.numpy())[0])
        with torch.no_grad():
            ref = mod(x).numpy()
        assert out.shape == ref.shape, (mod, out.shape, ref.shape)
        np.testing.assert_allclose(out, ref, atol=1e-5, err_msg=str(mod))


def test_stablehlo_short_table(tmp_path):
    """Tables smaller than predictBatchSize pad up to the fixed batch."""
    import jax

    def forward(x):
        return x @ np.ones((3, 2), np.float32)

    path = str(tmp_path / "f.hlo")
    export_stablehlo(forward, (np.zeros((4, 3), np.float32),), path)
    X = np.random.RandomState(0).rand(2, 3)  # 2 rows < batch 4
    t = MTable({"a": X[:, 0], "b": X[:, 1], "c": X[:, 2]})
    out = StableHloModelPredictBatchOp(
        modelPath=path, selectedCols=["a", "b", "c"], outputCols=["y"],
        predictBatchSize=4,
    ).link_from(TableSourceBatchOp(t)).collect()
    got = np.stack(list(out.col("y")))
    np.testing.assert_allclose(got, X.astype(np.float32).sum(1)[:, None]
                               @ np.ones((1, 2)), atol=1e-5)


def test_torch_predict_bfloat16_precision(tmp_path):
    """precision="bfloat16" serves the ingested model in the TPU-native
    policy with fp32-close outputs."""
    import os

    import torch
    import torch.nn as nn

    from alink_tpu.common.mtable import MTable
    from alink_tpu.operator.batch import TorchModelPredictBatchOp
    from alink_tpu.operator.batch.base import TableSourceBatchOp

    torch.manual_seed(0)
    model = nn.Sequential(nn.Linear(8, 32), nn.ReLU(), nn.Linear(32, 1))
    ep = torch.export.export(model.eval(), (torch.randn(4, 8),))
    path = os.path.join(tmp_path, "m.pt2")
    torch.export.save(ep, path)

    rng = np.random.RandomState(0)
    X = rng.randn(64, 8).astype(np.float64)
    t = MTable({f"f{i}": X[:, i] for i in range(8)})

    def run(prec):
        out = TorchModelPredictBatchOp(
            modelPath=path, selectedCols=[f"f{i}" for i in range(8)],
            outputCols=["s"], precision=prec,
        ).link_from(TableSourceBatchOp(t)).collect()
        return np.asarray(out.col("s"))

    s32, s16 = run("float32"), run("bfloat16")
    assert s16.dtype == np.float64  # outputs come back as fp32/double
    np.testing.assert_allclose(s16, s32, atol=0.05, rtol=0.05)
    # the policy must actually engage: bf16 rounding makes outputs differ
    assert not np.array_equal(s16, s32)

    # the ONNX ingest honors the same policy (artifact built with the
    # in-repo ONNX writer, same as the other ONNX tests)
    onnx_path = os.path.join(tmp_path, "m.onnx")
    _mlp_onnx(onnx_path, np.random.RandomState(7))
    Xo = np.random.RandomState(2).randn(32, 4)
    to = MTable({f"g{i}": Xo[:, i] for i in range(4)})

    def run_onnx(prec):
        out = OnnxModelPredictBatchOp(
            modelPath=onnx_path, selectedCols=[f"g{i}" for i in range(4)],
            outputCols=["probs"], precision=prec, predictBatchSize=8,
        ).link_from(TableSourceBatchOp(to)).collect()
        return np.stack([np.asarray(v) for v in out.col("probs")])

    o32, o16 = run_onnx("float32"), run_onnx("bfloat16")
    np.testing.assert_allclose(o16, o32, atol=0.05, rtol=0.05)
    assert not np.array_equal(o16, o32)
    # and other formats must refuse rather than silently serving fp32
    import pytest as _pytest

    from alink_tpu.common.exceptions import AkUnsupportedOperationException
    from alink_tpu.operator.batch import StableHloModelPredictBatchOp

    with _pytest.raises(AkUnsupportedOperationException, match="bfloat16"):
        StableHloModelPredictBatchOp(
            modelPath=path, selectedCols=["f0"], precision="bfloat16",
        ).link_from(TableSourceBatchOp(t)).collect()
