"""Root pytest bootstrap: the test suite runs on the CPU, never on a chip.

pytest imports this file before any test module, so before anything has
imported jax. It pins the process (and every child it spawns) to the CPU
backend with 8 virtual devices — the reference's
MiniCluster-with-N-TaskManagers test strategy mapped to a virtual mesh
(reference: test_utils/.../LocalEnvFactoryImpl.java:20-41) — and asks for
the Pallas interpreter, since Mosaic only compiles for a TPU.
"""

import os
import sys

assert "jax" not in sys.modules, \
    "jax was imported before the root conftest could pin it to the CPU"
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["ALINK_PALLAS_INTERPRET"] = "1"
# tests count traces and compiles, and must not feed CPU executables into a
# cache the chip path was handed; the tests that want a cache make their own
os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()
