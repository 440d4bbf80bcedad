"""Session / environment layer.

Capability parity with the reference's L1 (reference:
core/src/main/java/com/alibaba/alink/common/MLEnvironment.java:45,
MLEnvironmentFactory, AlinkGlobalConfiguration.java:6-101,
operator/local/AlinkLocalSession.java:20-45).

Re-design: there is no Flink; an :class:`MLEnvironment` is a lightweight session
holding (a) the JAX device mesh used for distributed execution, (b) the lazy-
evaluation manager for deferred sinks, and (c) a thread pool for host-side
parallel work (the ``AlinkLocalSession`` analog). Environments are registered in
a factory keyed by session id so operators can reference them by id, exactly as
in the reference.
"""

from __future__ import annotations

import os
import threading
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, Optional

from .exceptions import AkIllegalArgumentException

_FALSEY = ("0", "off", "false", "no", "")


def env_int(name: str, default: int) -> int:
    """Integer env knob; malformed values fall back to the default (config
    typos must never crash a running job)."""
    try:
        raw = os.environ.get(name)
        return default if raw is None or raw.strip() == "" else int(raw)
    except ValueError:
        return default


def env_float(name: str, default: "float | None") -> "float | None":
    try:
        raw = os.environ.get(name)
        return default if raw is None or raw.strip() == "" else float(raw)
    except ValueError:
        return default


def env_str(name: str, default: "str | None" = None) -> "str | None":
    """String env knob: the raw value when set and non-empty, else the
    default (empty/whitespace counts as unset — an exported-but-blank knob
    must behave like an absent one)."""
    raw = os.environ.get(name)
    if raw is None or raw.strip() == "":
        return default
    return raw


def env_raw(name: str) -> "str | None":
    """The value exactly as set (blank included); ``None`` only when absent.
    For topology knobs (NUM_PROCESSES, PROCESS_ID) where an exported-but-
    blank value — e.g. an unexpanded ``${WORLD_SIZE}`` in a launcher
    manifest — must fail loudly downstream rather than read as unset and
    silently degrade a multi-host job to single-process."""
    return os.environ.get(name)


def env_flag(name: str, default: bool = False) -> bool:
    """Boolean env knob: "0"/"off"/"false"/"no" are false, anything else
    present is true, absent is the default."""
    raw = os.environ.get(name)
    if raw is None:
        return default
    return raw.strip().lower() not in _FALSEY


class AlinkGlobalConfiguration:
    """Process-global config (reference: common/AlinkGlobalConfiguration.java).
    Resolution order: env var > explicitly set value > default."""

    _print_process_info = False
    _plugin_dir = "plugins"
    _auto_plugin_download = False

    @classmethod
    def set_print_process_info(cls, v: bool):
        cls._print_process_info = v

    @classmethod
    def is_print_process_info(cls) -> bool:
        env = os.environ.get("ALINK_PRINT_PROCESS_INFO")
        if env is not None:
            return env.lower() in ("1", "true")
        return cls._print_process_info

    @classmethod
    def get_plugin_dir(cls) -> str:
        return os.environ.get("ALINK_PLUGINS_DIR", cls._plugin_dir)

    @classmethod
    def set_plugin_dir(cls, d: str):
        cls._plugin_dir = d

    @classmethod
    def get_flink_version(cls) -> str:
        # kept for API parity; identifies the execution substrate instead
        return "jax-xla"

    _wire_precision = "auto"

    @classmethod
    def get_wire_precision(cls) -> str:
        """Host->device wire policy for float blocks: "auto" (precision-safe
        default — bf16 only above a size threshold AND on a measured-slow
        host->device link, exact fp32 otherwise), "bf16" (always downcast, explicit
        opt-in), or "fp32" (never downcast)."""
        return cls._wire_precision

    @classmethod
    def set_wire_precision(cls, p: str):
        if p not in ("auto", "bf16", "fp32"):
            raise AkIllegalArgumentException(
                f"wire precision must be auto|bf16|fp32, got {p!r}")
        cls._wire_precision = p


class MLEnvironment:
    """One session: device mesh + lazy manager + host thread pool."""

    def __init__(self, parallelism: Optional[int] = None, mesh=None):
        from .lazy import LazyObjectsManager

        self._mesh = mesh
        self._parallelism = parallelism
        self.lazy_manager = LazyObjectsManager()
        self._pool: Optional[ThreadPoolExecutor] = None
        self._dag_pool: Optional[ThreadPoolExecutor] = None
        self._lock = threading.Lock()

    # -- host-side thread pool (AlinkLocalSession analog) ------------------
    @property
    def executor(self) -> ThreadPoolExecutor:
        with self._lock:
            if self._pool is None:
                self._pool = ThreadPoolExecutor(
                    max_workers=self.parallelism, thread_name_prefix="alink-local"
                )
            return self._pool

    # -- DAG scheduler pool -------------------------------------------------
    @property
    def dag_pool(self) -> ThreadPoolExecutor:
        """Threads running DAG *node* tasks (common/executor.py). Separate
        from ``executor`` so a node blocking on intra-op shard futures can
        never starve the pool those shards run on (two-level submit to one
        pool deadlocks once every worker waits on queued inner tasks)."""
        from .executor import _dag_pool_size

        with self._lock:
            if self._dag_pool is None:
                self._dag_pool = ThreadPoolExecutor(
                    max_workers=_dag_pool_size(self),
                    thread_name_prefix="alink-dag")
            return self._dag_pool

    @property
    def parallelism(self) -> int:
        if self._parallelism is not None:
            return self._parallelism
        return max(1, os.cpu_count() or 1)

    # -- device mesh -------------------------------------------------------
    @property
    def mesh(self):
        # double-checked: lock-free once initialized (every op execution
        # reads this, including pool workers), single-shot lazy init
        m = self._mesh
        if m is not None:
            return m
        with self._lock:
            if self._mesh is None:
                from ..parallel.mesh import default_mesh

                self._mesh = default_mesh()
            return self._mesh

    def set_mesh(self, mesh):
        with self._lock:  # must not race the lazy init in `mesh`
            self._mesh = mesh
        return self

    def close(self):
        if self._pool is not None:
            self._pool.shutdown(wait=False)
            self._pool = None
        if self._dag_pool is not None:
            self._dag_pool.shutdown(wait=False)
            self._dag_pool = None


class MLEnvironmentFactory:
    """Session registry keyed by id (reference: common/MLEnvironmentFactory.java)."""

    _envs: Dict[int, MLEnvironment] = {}
    _next_id = 1
    _lock = threading.Lock()
    DEFAULT_ML_ENVIRONMENT_ID = 0

    @classmethod
    def get_default(cls) -> MLEnvironment:
        return cls.get(cls.DEFAULT_ML_ENVIRONMENT_ID)

    @classmethod
    def get(cls, session_id: int) -> MLEnvironment:
        with cls._lock:
            if session_id not in cls._envs:
                if session_id == cls.DEFAULT_ML_ENVIRONMENT_ID:
                    cls._envs[session_id] = MLEnvironment()
                else:
                    raise AkIllegalArgumentException(f"unknown session id {session_id}")
            return cls._envs[session_id]

    @classmethod
    def get_new_environment_id(cls, env: Optional[MLEnvironment] = None) -> int:
        with cls._lock:
            sid = cls._next_id
            cls._next_id += 1
            cls._envs[sid] = env or MLEnvironment()
            return sid

    @classmethod
    def remove(cls, session_id: int):
        with cls._lock:
            env = cls._envs.pop(session_id, None)
        if env is not None:
            env.close()

    @classmethod
    def reset_default(cls):
        """Force-reset the default session (test harness parity with
        reference AlinkTestBase.java:83-97)."""
        with cls._lock:
            env = cls._envs.pop(cls.DEFAULT_ML_ENVIRONMENT_ID, None)
        if env is not None:
            env.close()
