"""Persistent compile artifacts (common/jitcache.py persistence layer):
cross-process cache hits in fresh interpreters, corruption fallback, knob
resolution, the on-disk LRU cap, warmup-spec persistence, and
profiling-record survival across persist hits.

The cross-process tests are the PR's reason to exist: two FRESH interpreters
handed one ``JAX_COMPILATION_CACHE_DIR`` must produce bit-identical results,
with the second reaching them on ``jit.persist_hit`` instead of backend
compiles — and a truncated cache entry must degrade to a fresh compile
(counted), never to a wrong answer or a crash.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from alink_tpu.common import jitcache
from alink_tpu.common.jitcache import (
    cached_jit,
    clear_program_cache,
    compile_cache_dir,
    disable_persistent_cache,
    enable_persistent_cache,
    persist_summary,
    prune_persistent_cache,
    save_warmup_specs,
    seen_warmup_specs,
    warmup,
)
from alink_tpu.common.metrics import metrics

pytestmark = pytest.mark.compile

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# ---------------------------------------------------------------------------
# cross-process drills (fresh interpreters sharing one cache dir)
# ---------------------------------------------------------------------------

_CHILD = """
import json, os, sys
sys.path.insert(0, {repo!r})
import numpy as np

import alink_tpu  # noqa: F401 — wires the persistent cache from env
from alink_tpu.common.metrics import metrics
from alink_tpu.common.profiling import program_costs
from alink_tpu.operator.batch.base import CsvSourceBatchOp
from alink_tpu.pipeline import KMeans, Pipeline

src = CsvSourceBatchOp(
    filePath=os.path.join({repo!r}, "data", "iris.csv"),
    schemaStr="sl double, sw double, pl double, pw double, species string")
pipe = Pipeline(KMeans(k=3, maxIter=5, featureCols=["sl", "sw", "pl", "pw"],
                       predictionCol="pred"))
out = pipe.fit(src).transform(src).collect()
print(json.dumps({{
    "labels": [int(x) for x in np.asarray(out.col("pred"))],
    "persist_hit": metrics.counter("jit.persist_hit"),
    "persist_miss": metrics.counter("jit.persist_miss"),
    "persist_error": metrics.counter("jit.persist_error"),
    "compiles": metrics.counter("jit.compile"),
    "profile_records": len(program_costs(resolve=False)),
}}))
"""


def _run_child(cache_dir: str) -> dict:
    env = dict(os.environ)
    env["JAX_COMPILATION_CACHE_DIR"] = str(cache_dir)
    env.setdefault("JAX_PLATFORMS", "cpu")
    proc = subprocess.run(
        [sys.executable, "-c", _CHILD.format(repo=REPO_ROOT)],
        env=env, capture_output=True, text=True, timeout=240)
    assert proc.returncode == 0, f"child failed:\n{proc.stderr[-2000:]}"
    return json.loads(proc.stdout.strip().splitlines()[-1])


def _corrupt_entries(cache_dir) -> int:
    n = 0
    for name in os.listdir(cache_dir):
        if name.endswith("-cache"):
            path = os.path.join(cache_dir, name)
            with open(path, "rb") as f:
                data = f.read()
            with open(path, "wb") as f:
                f.write(data[: max(1, len(data) // 3)])
            n += 1
    return n


def test_cross_process_persist_hit_bit_identical(tmp_path):
    """The acceptance drill: kmeans_iris in two fresh interpreters sharing
    one cache dir — the second must land persist hits (no fresh backend
    compiles served it wrong), produce bit-identical predictions, and still
    carry profiling cost records (a persist-hit that skips the compiler
    must not skip the observatory)."""
    cache = tmp_path / "cc"
    cache.mkdir()
    first = _run_child(str(cache))
    assert first["persist_miss"] > 0          # cold machine: populated
    assert first["persist_error"] == 0
    entries = [f for f in os.listdir(cache) if f.endswith("-cache")]
    assert entries, "first process must write cache entries"

    second = _run_child(str(cache))
    assert second["persist_hit"] > 0, second   # served from disk
    assert second["persist_error"] == 0
    assert second["labels"] == first["labels"]  # bit-identical
    assert second["profile_records"] > 0        # observatory survived


def test_corrupt_cache_entry_falls_back_to_fresh_compile(tmp_path):
    """Truncate every on-disk entry between two processes: the second must
    count ``jit.persist_error``, compile fresh (zero hits), and still
    produce bit-identical predictions with exit code 0."""
    cache = tmp_path / "cc"
    cache.mkdir()
    first = _run_child(str(cache))
    assert _corrupt_entries(cache) > 0

    second = _run_child(str(cache))
    assert second["persist_error"] > 0, second  # corruption was seen
    assert second["persist_hit"] == 0, second   # nothing served from disk
    assert second["labels"] == first["labels"]  # fresh compile: same answer


# ---------------------------------------------------------------------------
# knob resolution + lifecycle (in-process)
# ---------------------------------------------------------------------------

def test_cache_dir_resolution(monkeypatch, tmp_path):
    """The cache is placed from outside. ``JAX_COMPILATION_CACHE_DIR`` set:
    that directory, untouched, and nothing else may be configured. Unset:
    off on the CPU (this suite), else the in-checkout default."""
    # the root conftest removes the variable: default is OFF under cpu
    assert "JAX_COMPILATION_CACHE_DIR" not in os.environ
    assert jitcache._resolve_persist_dir(None) is None
    # an explicit argument (tests, drills) is honoured while nothing else
    # placed the cache
    assert jitcache._resolve_persist_dir(str(tmp_path / "c")) == \
        str(tmp_path / "c")
    # placed from outside: used as is, on any platform ...
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "x"))
    assert jitcache._resolve_persist_dir(None) == str(tmp_path / "x")
    assert jitcache._resolve_persist_dir(str(tmp_path / "x")) == \
        str(tmp_path / "x")
    # ... and a second directory is refused, not layered on top
    with pytest.raises(ValueError, match="places the compile cache"):
        jitcache._resolve_persist_dir(str(tmp_path / "c"))
    # blank counts as unset (jax itself reads '' as no cache)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", " ")
    assert jitcache._resolve_persist_dir(None) is None
    # off the CPU with nothing set: the fixed in-checkout directory
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    assert jitcache._resolve_persist_dir(None) == \
        os.path.join(REPO_ROOT, ".jax_cache") == jitcache.default_cache_dir()


_RESOLVE_CHILD = """
import json, os, sys
sys.path.insert(0, {repo!r})
import alink_tpu
print(json.dumps({{"dir": alink_tpu.compile_cache_dir(),
                   "env": os.environ.get("JAX_COMPILATION_CACHE_DIR"),
                   "jax": "jax" in sys.modules}}))
"""


def _resolve_in_fresh_process(tmp_path, **env_over):
    env = dict(os.environ, JAX_PLATFORMS="tpu", **env_over)
    proc = subprocess.run(
        [sys.executable, "-c", _RESOLVE_CHILD.format(repo=REPO_ROOT)],
        env=env, cwd=str(tmp_path), capture_output=True, text=True,
        timeout=60)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_fresh_processes_resolve_one_fixed_dir_or_the_placed_one(tmp_path):
    """Two fresh processes (started from another cwd) resolve the SAME
    directory inside the checkout — never a temp name, pid or time — and
    export it for jax and for their own children, without importing jax.
    With the variable set, that directory is the answer and stays as set."""
    first = _resolve_in_fresh_process(tmp_path)
    second = _resolve_in_fresh_process(tmp_path)
    want = os.path.join(REPO_ROOT, ".jax_cache")
    assert first == second == {"dir": want, "env": want, "jax": False}

    placed = str(tmp_path / "x")
    got = _resolve_in_fresh_process(tmp_path,
                                    JAX_COMPILATION_CACHE_DIR=placed)
    assert got == {"dir": placed, "env": placed, "jax": False}
    assert os.listdir(placed) == []        # created, nothing else written


def _build_scale(factor):
    import jax

    return jax.jit(lambda x: x * factor)


def test_in_process_persist_hit_and_profiling_survival(tmp_path):
    """Enable → compile → drop every in-memory cache → recompile: the
    executable must come off disk (``jit.persist_hit``), results must be
    bit-identical, and the profiling registry must still resolve static XLA
    costs for the persist-hit program (lazy lower() needs no compiler)."""
    import jax

    from alink_tpu.common.profiling import program_costs

    try:
        d = enable_persistent_cache(str(tmp_path / "cc"))
        assert d == str(tmp_path / "cc") == compile_cache_dir()
        prog = cached_jit("test.persist_prof", _build_scale, 2.5)
        x = np.arange(64, dtype=np.float32)
        out1 = np.asarray(prog(x))
        assert persist_summary()["entries"] >= 1

        clear_program_cache()
        jax.clear_caches()
        h0 = metrics.counter("jit.persist_hit")
        prog2 = cached_jit("test.persist_prof", _build_scale, 2.5)
        out2 = np.asarray(prog2(x))
        assert metrics.counter("jit.persist_hit") > h0
        assert np.array_equal(out1, out2)

        recs = [r for r in program_costs("test.persist_prof")
                if r["capture"] in ("cost", "deep")]
        assert recs, "persist-hit program must still resolve XLA costs"
        assert any(r.get("persist") == "hit" for r in
                   program_costs("test.persist_prof", resolve=False))
    finally:
        disable_persistent_cache()
        clear_program_cache()
    assert compile_cache_dir() is None
    # compile_summary embeds the (now disabled) persistence readout
    from alink_tpu.common.jitcache import compile_summary

    assert compile_summary()["persist"]["enabled"] is False


def test_entries_without_atime_companions_do_not_break_writes(tmp_path):
    """A directory placed from outside can hold entries any other jax wrote
    with default settings — no ``-atime`` companion. jax's own eviction
    fails every later write on the first such entry (seen on the chip), so
    this module keeps it off and bounds the directory itself."""
    import jax

    d = tmp_path / "cc"
    d.mkdir()
    (d / "foreign-cache").write_bytes(b"x" * 64)      # no foreign-atime
    e0 = metrics.counter("jit.persist_error")
    try:
        assert enable_persistent_cache(str(d)) == str(d)
        assert jax.config.jax_compilation_cache_max_size == -1
        prog = cached_jit("test.persist_foreign", _build_scale, 1.75)
        prog(np.arange(32, dtype=np.float32))
        assert persist_summary()["entries"] >= 2        # ours landed too
        assert metrics.counter("jit.persist_error") == e0
    finally:
        disable_persistent_cache()
        clear_program_cache()


def test_disabled_writes_nothing(tmp_path):
    """Persistence off (the default in this CPU test env): compiling adds
    no on-disk entries anywhere under the would-be cache dir."""
    assert compile_cache_dir() is None
    prog = cached_jit("test.persist_off", _build_scale, 7.5)
    prog(np.ones(16, np.float32))
    s = persist_summary()
    assert s["enabled"] is False and s["dir"] is None
    assert s["entries"] == 0 and s["bytes"] == 0


# ---------------------------------------------------------------------------
# on-disk LRU cap
# ---------------------------------------------------------------------------

def _fake_entry(d, name, size, age):
    path = os.path.join(d, f"{name}-cache")
    with open(path, "wb") as f:
        f.write(b"x" * size)
    stamp = os.path.join(d, f"{name}-atime")
    with open(stamp, "w") as f:
        f.write("")
    os.utime(stamp, (age, age))
    return path


def test_prune_lru_evicts_oldest_first(tmp_path):
    d = str(tmp_path)
    old = _fake_entry(d, "old", 600, 1_000)
    mid = _fake_entry(d, "mid", 600, 2_000)
    new = _fake_entry(d, "new", 600, 3_000)
    ev0 = metrics.counter("jit.persist_evict")
    out = prune_persistent_cache(d, max_bytes=1300)
    assert not os.path.exists(old)            # LRU goes first
    assert os.path.exists(mid) and os.path.exists(new)
    assert not os.path.exists(os.path.join(d, "old-atime"))
    assert out["removed"] == 1 and out["bytes"] == 1200
    assert metrics.counter("jit.persist_evict") == ev0 + 1
    # under the cap: a no-op
    assert prune_persistent_cache(d, max_bytes=1300)["removed"] == 0
    # cap 0 = unbounded
    assert prune_persistent_cache(d, max_bytes=0)["removed"] == 0


# ---------------------------------------------------------------------------
# warmup-spec persistence (the disk half of zero-trace readiness)
# ---------------------------------------------------------------------------

def test_warmup_specs_roundtrip_from_disk(tmp_path):
    prog = cached_jit("test.persist_warm", _build_scale, 3.25)
    prog(np.ones((40, 2), np.float32))
    specs = [s for s in seen_warmup_specs() if s[0] == "test.persist_warm"]
    assert (("test.persist_warm", [((40, 2), "<f4")]) in
            [(k, list(v)) for k, v in specs])
    path = str(tmp_path / "warm.jsonl")
    assert save_warmup_specs(path, specs) == len(specs)
    # a process that never compiled replays the file: simulate by dropping
    # the program and warming from the path (string arg = read from disk)
    jitcache.clear_kernel("test.persist_warm")
    prog2 = cached_jit("test.persist_warm", _build_scale, 3.25)
    res = warmup(path, block=True)
    assert res["errors"] == 0 and res["compiled"] >= 1
    c0 = metrics.counter("jit.compile")
    prog2(np.ones((40, 2), np.float32))
    assert metrics.counter("jit.compile") == c0, \
        "disk-spec-warmed shape must not compile on first real call"


def test_prejax_enable_env_writes_are_taken_back_on_disable(monkeypatch,
                                                           tmp_path):
    """A pre-jax enable hands config to jax via env vars; disable must take
    back exactly what it wrote — a user-exported JAX_* tuning knob is
    neither clobbered nor deleted."""
    monkeypatch.setenv("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "2.5")
    monkeypatch.delenv("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES",
                       raising=False)
    with jitcache._persist_lock:
        saved = dict(jitcache._persist)
        jitcache._persist.update(enabled=False, dir=None, configured=False,
                                 wrote_env=set())
    real_modules = jitcache.sys.modules

    class _NoJax(dict):
        def __contains__(self, k):
            return False if k == "jax" else k in real_modules

    try:
        # pretend jax is not imported yet, the state at `import alink_tpu`
        monkeypatch.setattr(jitcache.sys, "modules", _NoJax())
        d = jitcache.enable_persistent_cache(str(tmp_path / "cc"))
        monkeypatch.setattr(jitcache.sys, "modules", real_modules)
        assert d == str(tmp_path / "cc")
        # user's min-compile floor survived; our writes landed
        assert os.environ[
            "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] == "2.5"
        assert os.environ["JAX_COMPILATION_CACHE_DIR"] == d
        assert os.environ[
            "JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] == "-1"
        jitcache.disable_persistent_cache()
        # ours removed, the user's untouched
        assert "JAX_COMPILATION_CACHE_DIR" not in os.environ
        assert "JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES" not in os.environ
        assert os.environ[
            "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] == "2.5"
    finally:
        monkeypatch.setattr(jitcache.sys, "modules", real_modules)
        with jitcache._persist_lock:
            jitcache._persist.update(saved)
