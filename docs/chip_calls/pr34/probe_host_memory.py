"""What this process's resident host memory does on the chip's machine as
arrays go to the device and come back (PR 34: the training cell's process
stood at 35 GB resident after its fit, with 8 GB of its own arrays)."""
import gc
import resource
import sys
import time

import numpy as np


def rss():
    now = float("nan")
    with open("/proc/self/status") as f:
        for line in f:
            if line.startswith("VmRSS:"):
                now = int(line.split()[1]) / 1e6
    return now, resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1e6


def say(what):
    gc.collect()
    print("%-58s %.2f GB resident, %.2f highest" % ((what,) + rss()), flush=True)


say("python and numpy")
import jax
import jax.numpy as jnp

say(f"jax imported, {jax.devices()[0].device_kind}")
x = [jnp.zeros((2048, 2048 * 40), jnp.float32) + i for i in range(8)]  # 5.4 GB
jax.block_until_ready(x)
say("5.4 GB made on the device")
y = [a + 1 for a in x[:4]]
h = jax.device_get(y)
say("2.7 GB brought to the host and held")
del h, y
say("... and let go, with their device arrays")
y = [a + 2 for a in x[:6]]
h = [np.asarray(a) for a in y]
say("4 GB more brought to the host and held")
del h, y
say("... and let go, with their device arrays")
n = [np.ones((2048, 2048 * 40), np.float32) for _ in range(4)]
say("2.7 GB made on the host")
d = [jnp.asarray(a) for a in n]
jax.block_until_ready(d)
say("... and put on the device")
del n
say("... host copy let go")
del d, x
say("all device arrays let go")
f = jax.jit(lambda a: (a @ a.T).sum())
print(float(f(jnp.ones((4096, 4096), jnp.bfloat16))))
say("one program compiled and run")
