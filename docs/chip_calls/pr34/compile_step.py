import os, sys, json, time
os.environ.setdefault("TPU_LOG_DIR","disabled")
os.environ["JAX_PLATFORMS"]="cpu"
ROOT=os.path.abspath(os.path.join(os.path.dirname(__file__),"..","..",".."))  # the checkout
sys.path.insert(0,ROOT)
import jax, jax.numpy as jnp, numpy as np, optax
from functools import partial
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding
jax.config.update("jax_enable_compilation_cache", False)
from alink_tpu.dl import lm as L, mla as A, moe as E
from benchmark import gen_moonlight as G
ROWS=int(sys.argv[1]) if len(sys.argv)>1 else 2
BLOCK=int(sys.argv[2]) if len(sys.argv)>2 else 1024
A.CAUSAL_BLOCK=BLOCK
cfgj=json.load(open(os.path.join(ROOT,"benchmark/configs/moonlight_16b_a3b_train.json")))
cfg=L.CausalLMConfig.from_hf(G.hf_config(cfgj))
print(cfg)
jax.default_backend=lambda: "tpu"
topo=topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
dev=SingleDeviceSharding(topo.devices[0])
shapes=L.tensor_shapes(cfg)
# build the param tree of shapes
f32=lambda s: jax.ShapeDtypeStruct(s, jnp.float32, sharding=dev)
lo,hi=cfg.experts_held
layers=[]
for i in range(cfg.num_hidden_layers):
    d={}
    for name,shape in shapes.items():
        if not name.startswith(f"model.layers.{i}."): continue
        path=L.tree_path(name)
        if len(path)==5 or path[-1]=="expert_bias": continue
        d[path[-1]]=f32(shape)
    if cfg.ffn_type(i)=="experts":
        d["experts_gate_up"]=f32((hi-lo,cfg.hidden_size,2*cfg.moe_intermediate_size))
        d["experts_down"]=f32((hi-lo,cfg.moe_intermediate_size,cfg.hidden_size))
    layers.append(d)
params={"embed_tokens":f32((cfg.vocab_size,cfg.hidden_size)),"norm":f32((cfg.hidden_size,)),
        "lm_head":f32((cfg.vocab_size,cfg.hidden_size)),"layers":layers}
ne=cfg.ffn_types.count("experts")
router={"expert_bias":f32((ne,cfg.num_experts)),"load":jax.ShapeDtypeStruct((ne,cfg.num_experts),jnp.int32,sharding=dev)}
n=sum(int(np.prod(a.shape)) for a in jax.tree.leaves(params))
print("params",n)
tx=optax.adamw(optax.warmup_cosine_decay_schedule(0.0,2e-5,7,72),weight_decay=0.01)
opt_shapes=jax.eval_shape(tx.init, params)
opt_shapes=jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape,a.dtype,sharding=dev), opt_shapes)
model=L.CausalLMTrainer(cfg)
def step(variables,opt_state,tokens,w):
    p=variables["params"]; stats={"router":variables["router"]}
    def loss(p):
        rows,new=model.apply({"params":p,**stats},tokens=tokens,deterministic=False,mutable=["router"])
        return (rows*w).sum()/jnp.maximum(w.sum(),1.0), new
    (l,new),g=jax.value_and_grad(loss,has_aux=True)(p)
    with jax.named_scope("optimizer"):
        up,opt_state=tx.update(g,opt_state,p)
        p=optax.apply_updates(p,up)
    return {"params":p,**new},opt_state,l
tokens=jax.ShapeDtypeStruct((ROWS,8192),jnp.int32,sharding=dev)
w=jax.ShapeDtypeStruct((ROWS,),jnp.float32,sharding=dev)
t0=time.time()
lowered=jax.jit(step,donate_argnums=(0,1)).lower({"params":params,"router":router},opt_shapes,tokens,w)
print("lowered",time.time()-t0)
compiled=lowered.compile()
print("compiled",time.time()-t0)
ma=compiled.memory_analysis()
print(ma)
gb=lambda x: x/1e9
print("args %.2f out %.2f temp %.2f alias %.2f total %.2f GB"%(gb(ma.argument_size_in_bytes),gb(ma.output_size_in_bytes),gb(ma.temp_size_in_bytes),gb(ma.alias_size_in_bytes),gb(ma.argument_size_in_bytes+ma.output_size_in_bytes+ma.temp_size_in_bytes-ma.alias_size_in_bytes)))
txt=compiled.as_text()
os.makedirs(os.path.join(ROOT,".scratch"),exist_ok=True)  # listed in .gitignore
open(os.path.join(ROOT,".scratch",f"step_{ROWS}_{BLOCK}.hlo.txt"),"w").write(txt)
import re
big=set(re.findall(r"\[[\d,]*8192,[\d,]*8192[\d,]*\]",txt))
print("tensors with two 8192 dims:",big)
# what `moe_roofline.train` matches by name: the custom fusions that write
# tokens x hidden in float32, by the scope each was traced under
import collections
label=re.compile(r"= f32\[%d,%d\]\S* fusion\(.*kind=kCustom.*?op_name=\"([^\"]*)\""%(ROWS*8192,cfg.hidden_size))
print("fusion_kCustom f32[%d,%d] by op_name:"%(ROWS*8192,cfg.hidden_size))
for name,n in collections.Counter(re.sub(r"\d+","#",m.group(1)) for m in label.finditer(txt)).most_common():
    print("  x%d %s"%(n,name))
