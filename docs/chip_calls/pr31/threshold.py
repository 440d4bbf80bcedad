"""PR 31, on the chip: the fused attention core against XLA's materialised
attention. Run from the root of a checkout on one TPU v5e:

    python docs/chip_calls/pr31/threshold.py [out_dir [parts, e.g. 34]]

1. values: the kernel against ``full_attention`` on the chip, outputs and
   gradients, bfloat16 and float32, with a mask that ends mid-block and a
   fully masked row;
2. the core alone, forward and forward+backward, at BERT-base's 12 heads of
   64 and 16,384 tokens: 128 x 128, 64 x 256, 32 x 512 (rows x positions),
   and 16 x 1024;
3. the BERT-base encoder (dl/modules.TransformerEncoder, the default
   configuration), forward alone and forward with backward, in the same
   shapes, kernel against XLA: the readings beside ``_FUSED_MIN_SEQ``;
4. a profiler trace of the encoder's forward+backward at 32 x 512 with the
   kernel on, reduced as the benchmark reduces it (reducers/trace.py), to
   show how the kernels' device events are labelled.

The knob is read at trace time; the script lowers the threshold itself to
time the kernel at lengths the program would not take it at."""
import json
import os
import sys
import time

sys.path.insert(0, os.getcwd())
import jax
import jax.numpy as jnp
import numpy as np

from alink_tpu.dl import attn_pallas
from alink_tpu.dl.attention import full_attention, packed_attention
from alink_tpu.dl.modules import BertConfig, TransformerEncoder
from alink_tpu.native.kernels import interpret_mode

out_dir = sys.argv[1] if len(sys.argv) > 1 else "chiprun_out/pr31_threshold"
PARTS = sys.argv[2] if len(sys.argv) > 2 else "1234"
os.makedirs(out_dir, exist_ok=True)
attn_pallas._FUSED_MIN_SEQ = 128
SHAPES = ((128, 128), (64, 256), (32, 512), (16, 1024))
VALUE_SHAPES = ((3, 512, 12, 64), (2, 256, 4, 128), (1, 128, 2, 64))
BASE = dict()
if os.environ.get("REHEARSAL"):     # the CPU, toy width, to find wrong paths
    SHAPES, VALUE_SHAPES = ((2, 128),), ((2, 128, 2, 64),)
    BASE = dict(hidden_size=128, num_heads=2, num_layers=2,
                intermediate_size=256, vocab_size=30522)
result = {"device": jax.devices()[0].device_kind, "values": [], "core": [],
          "encoder": []}
print(result["device"], flush=True)


def knob(on):
    os.environ["ALINK_ATTN_PALLAS"] = "1" if on else "0"


def timed(f, *args, reps=20):
    jax.block_until_ready(f(*args))
    jax.block_until_ready(f(*args))
    ts = []
    for _ in range(3):
        t = time.perf_counter()
        for _ in range(reps):
            r = f(*args)
        jax.block_until_ready(r)
        ts.append((time.perf_counter() - t) / reps * 1e3)
    return min(ts)


# 1. values
def reference(qkv, mask, h):
    b, s, _, hd = qkv.shape
    q, k, v = (qkv[:, :, i].reshape(b, s, h, hd // h) for i in range(3))
    return full_attention(q, k, v, mask).reshape(b, s, hd)


for dtype in (jnp.bfloat16, jnp.float32) if "1" in PARTS else ():
    for b, s, h, d in VALUE_SHAPES:
        rng = np.random.default_rng(s)
        qkv = jnp.asarray(rng.normal(size=(b, s, 3, h * d)), dtype)
        lens = rng.integers(1, s, size=b)
        lens[0] = s - 58
        mask = (np.arange(s)[None] < lens[:, None]).astype(np.int32)
        if b > 1:
            mask[-1] = 0
        mask = jnp.asarray(mask)
        w = jnp.asarray(rng.normal(size=(b, s, h * d)), jnp.float32)
        fused = lambda x: attn_pallas.fused_attention(
            x, mask, num_heads=h, interpret=interpret_mode())
        plain = lambda x: reference(x, mask, h)
        row = {"dtype": jnp.dtype(dtype).name, "shape": [b, s, h, d]}
        row["out"] = float(jnp.abs(jax.jit(fused)(qkv).astype(jnp.float32)
                                   - jax.jit(plain)(qkv).astype(jnp.float32)).max())
        g1 = jax.jit(jax.grad(lambda x: (fused(x).astype(jnp.float32) * w).sum()))(qkv)
        g2 = jax.jit(jax.grad(lambda x: (plain(x).astype(jnp.float32) * w).sum()))(qkv)
        for i, n in enumerate("qkv"):
            row["d" + n] = float(jnp.abs(g1[:, :, i].astype(jnp.float32)
                                         - g2[:, :, i].astype(jnp.float32)).max())
            row["d" + n + "_scale"] = float(jnp.abs(g2[:, :, i]).max())
        result["values"].append(row)
        print("values", row, flush=True)

# 2. the core alone
for rows, s in SHAPES if "2" in PARTS else ():
    h, d = BASE.get("num_heads", 12), 64
    rng = np.random.default_rng(0)
    qkv = jnp.asarray(rng.normal(size=(rows, s, 3, h * d)), jnp.bfloat16)
    mask = jnp.ones((rows, s), jnp.int32)
    row = {"rows": rows, "seq": s}
    for name, on in (("kernel", True), ("xla", False)):
        knob(on)
        f = lambda x, m: packed_attention(x, m, num_heads=h)
        fwd = jax.jit(f)
        both = jax.jit(jax.grad(lambda x, m: f(x, m).astype(jnp.float32).sum()))
        row[name + "_fwd_ms"] = timed(fwd, qkv, mask)
        row[name + "_fwd_bwd_ms"] = timed(both, qkv, mask)
    result["core"].append(row)
    print("core", row, flush=True)

# 3. the encoder, and 4. the kernels' names in a device trace of it, as the
# benchmark reads them
def encoder_fns(cfg):
    model = TransformerEncoder(cfg)

    def fwd(params, ids, mask):
        return model.apply({"params": params}, ids, mask, deterministic=True)

    def loss(params, ids, mask):
        return fwd(params, ids, mask).astype(jnp.float32).sum()

    return jax.jit(fwd), jax.jit(jax.grad(loss))


def trace_names(grad, params, ids, mask, steps=3):
    import glob
    import shutil

    from benchmark.reducers import trace as trace_reducer

    trace_dir = os.path.join(out_dir, "trace")
    jax.profiler.start_trace(trace_dir)
    for _ in range(steps):
        r = grad(params, ids, mask)
    jax.block_until_ready(r)
    jax.profiler.stop_trace()
    path = sorted(glob.glob(os.path.join(
        trace_dir, "plugins/profile/*/*.xplane.pb")))[-1]
    per, raw = {}, {}
    for plane, _, name, _, dur, detail in trace_reducer.read_events(path):
        if trace_reducer.DEVICE_PLANE.match(plane):
            lab = trace_reducer.op_label(name, detail)
            tot, cnt = per.get(lab, (0, 0))
            per[lab] = (tot + dur, cnt + 1)
            raw.setdefault(lab, detail[:600])
    top = sorted(per.items(), key=lambda x: -x[1][0])[:30]
    result["trace_top"] = [(lab, tot / 1e6 / steps, cnt // steps)
                           for lab, (tot, cnt) in top]
    result["trace_kernels"] = {lab: raw[lab] for lab in per
                               if "pallas" in lab or "custom" in lab}
    for lab, ms, cnt in result["trace_top"]:
        print("trace  %8.3f ms/step x%-4d %s" % (ms, cnt, lab), flush=True)
    for lab, det in result["trace_kernels"].items():
        print("kernel label:", lab, "|", det, flush=True)
    shutil.rmtree(trace_dir, ignore_errors=True)


for rows, s in SHAPES if "3" in PARTS else ():
    cfg = BertConfig.base(max_position=max(512, s), dropout=0.0, **BASE)
    model = TransformerEncoder(cfg)
    ids = jnp.asarray(np.random.default_rng(1).integers(0, 30000, (rows, s)), jnp.int32)
    mask = jnp.ones((rows, s), jnp.int32)
    knob(False)
    params = jax.jit(lambda k: model.init(k, ids[:1], mask[:1])["params"])(
        jax.random.PRNGKey(0))
    row = {"rows": rows, "seq": s}
    for name, on in (("xla", False), ("kernel", True)):
        knob(on)
        fwd, grad = encoder_fns(cfg)
        row[name + "_fwd_ms"] = timed(fwd, params, ids, mask, reps=10)
        row[name + "_fwd_bwd_ms"] = timed(grad, params, ids, mask, reps=10)
    result["encoder"].append(row)
    print("encoder", row, flush=True)
    if "4" in PARTS and (rows, s) == SHAPES[min(2, len(SHAPES) - 1)]:
        trace_names(grad, params, ids, mask)

with open(os.path.join(out_dir, "threshold.json"), "w") as f:
    json.dump(result, f, indent=1)
