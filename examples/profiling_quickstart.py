"""Performance observatory quick start: per-kernel XLA cost accounting and
roofline attribution (alink_tpu/common/profiling.py — see README
"Profiling").

Runs a fitted pipeline and a fused mapper-chain DAG with profiling on and
prints the per-kernel cost/roofline table every readout surface shares
(job_report()["profile"], GET /api/profile, alink_profile_* gauges at
/metrics)."""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")    # drop on a TPU host
os.environ.setdefault("ALINK_PROFILING", "on")   # the default; explicit here

import numpy as np  # noqa: E402

from alink_tpu import job_report, profile_summary  # noqa: E402
from alink_tpu.common.mtable import AlinkTypes, MTable  # noqa: E402
from alink_tpu.mapper.base import BlockKernelMapper  # noqa: E402
from alink_tpu.operator.batch import TableSourceBatchOp  # noqa: E402
from alink_tpu.operator.batch.utils import MapBatchOp  # noqa: E402
from alink_tpu.pipeline import (NaiveBayes, Pipeline, StandardScaler,  # noqa: E402
                                VectorAssembler)

# -- 1. a pipeline workload: fit + transform twice (the warm run joins
#       measured exec time into achieved FLOP/s) -----------------------------
rng = np.random.default_rng(0)
X = np.concatenate([rng.normal(c, 0.4, size=(200, 4))
                    for c in [(0, 0, 0, 0), (2, 2, 2, 2)]])
labels = np.repeat(["neg", "pos"], 200)
feats = ["f0", "f1", "f2", "f3"]
train = MTable({f"f{i}": X[:, i] for i in range(4)}).with_column(
    "label", labels)
model = Pipeline(
    StandardScaler(selectedCols=feats),
    VectorAssembler(selectedCols=feats, outputCol="vec"),
    NaiveBayes(vectorCol="vec", labelCol="label", predictionCol="pred"),
).fit(train)
model.transform(train).collect()
model.transform(train).collect()


# -- 2. a fused block-kernel mapper chain through the DAG executor -----------
def affine(col, out_col, a, b):
    class _M(BlockKernelMapper):
        def kernel(self, schema):
            return ([col], [out_col], [AlinkTypes.DOUBLE],
                    lambda V: V * a + b)

    class _Op(MapBatchOp):
        mapper_cls = _M

    return _Op()


t = MTable({"x": np.random.default_rng(1).random(200_000)})
for _ in range(2):                               # trace once, then warm
    chain = affine("x", "x1", 2.0, 1.0).link_from(TableSourceBatchOp(t))
    chain = affine("x1", "x2", 0.5, -3.0).link_from(chain)
    chain.collect()

# -- 3. the observatory readout ---------------------------------------------
summary = profile_summary()
dev = summary["device"]
print(f"device: {dev['device_kind']}  "
      f"ridge {dev['ridge_flops_per_byte']} FLOP/byte "
      f"(peaks via {dev['source']}; override with "
      f"ALINK_PEAK_TFLOPS / ALINK_PEAK_HBM_GBS)")
hbm = summary["hbm"]
print("HBM watermark:", f"{hbm['peak_bytes']} bytes peak"
      if hbm["available"] else "unavailable on this backend (ok on CPU)")

print(f"\n{'kernel':<24}{'calls':>6}{'MFLOP':>9}{'MB acc':>8}"
      f"{'AI':>7}{'GFLOP/s':>9}  bound")
for k in summary["kernels"][:8]:
    r = k["roofline"]
    print(f"{k['kernel']:<24}{k['calls']:>6}"
          f"{(k['flops'] or 0) / 1e6:>9.2f}"
          f"{(k['bytes_accessed'] or 0) / 1e6:>8.2f}"
          f"{r['arithmetic_intensity'] or 0:>7.2f}"
          f"{(k['achieved_flops_per_s'] or 0) / 1e9:>9.2f}"
          f"  {r['bound'] or '—'}")

report = job_report()                 # the last traced run
prof = report.get("profile", {})
print(f"\njob_report(): {len(report.get('spans', []))} spans, "
      f"profile table of {len(prof.get('kernels', []))} kernels "
      f"attached under report['profile']")
