import os, sys, json, time
os.environ.setdefault("TPU_LOG_DIR","disabled")
os.environ["JAX_PLATFORMS"]="cpu"
ROOT=os.path.abspath(os.path.join(os.path.dirname(__file__),"..","..",".."))  # the checkout
sys.path.insert(0,ROOT)
import jax, jax.numpy as jnp, numpy as np
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding
jax.config.update("jax_enable_compilation_cache", False)
from benchmark import gen_moonlight as G
from benchmark.reference import moonlight as M
PREC=sys.argv[1]; BLOCK=int(sys.argv[2]); FAULT=sys.argv[3] if len(sys.argv)>3 and sys.argv[3]!="none" else None
cfg=json.load(open(os.path.join(ROOT,"benchmark/configs/moonlight_16b_a3b_train.json")))
topo=topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
dev=SingleDeviceSharding(topo.devices[0])
f32=lambda s: jax.ShapeDtypeStruct(tuple(s), jnp.float32, sharding=dev)
lo,hi=cfg["deployment"]["experts_held"]
w={"model.embed_tokens.weight":f32((cfg["vocab_size"],cfg["hidden_size"])),"model.norm.weight":f32((cfg["hidden_size"],)),"lm_head.weight":f32((cfg["vocab_size"],cfg["hidden_size"]))}
for i,ffn in enumerate(G.layer_ffns(cfg)):
    for name,shape,kind in G.layer_specs(cfg,ffn):
        if kind=="z": continue
        if name.startswith("mlp.experts."):
            leaf=name[len("mlp.experts."):]
            for e in range(hi-lo): w[f"model.layers.{i}.mlp.experts.{lo+e}.{leaf}"]=f32(shape[1:])
        else: w[f"model.layers.{i}.{name}"]=f32(shape)
bias=f32((5,64)); ids=jax.ShapeDtypeStruct((8192,),jnp.int32,sharding=dev)
t0=time.time()
low=M.row_loss.lower(w,bias,ids,spec=M.spec_of(cfg),precision=PREC,block=BLOCK,fault=FAULT,alpha=1e-4)
print("lowered",time.time()-t0,flush=True)
try:
    c=low.compile()
    ma=c.memory_analysis()
    print(PREC,BLOCK,FAULT,"compiled %.1f s"%(time.time()-t0),"args %.2f temp %.2f out %.2f GB"%(ma.argument_size_in_bytes/1e9,ma.temp_size_in_bytes/1e9,ma.output_size_in_bytes/1e9))
except Exception as e:
    print(PREC,BLOCK,FAULT,"FAILED %.1f s"%(time.time()-t0),str(e)[:600])
import resource
print("max RSS %.2f GB" % (resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1e6))
