"""chip_smoke.py, as far as a CPU can take it: the same phases at toy width on
the 8-virtual-device mesh, and the two ways the script must refuse to report
success — no ``tpu`` backend, and a phase that fails."""

import os
import subprocess
import sys

import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)

import chip_smoke  # noqa: E402


def test_phases_run_at_toy_width_on_the_cpu_mesh(capsys):
    """Train op -> loss falls on the repeated batch, spread over all 8
    devices; blockwise op steps; ModelServer answers with the predict op's
    labels and zero traces after warm-up; every registered kernel's caller
    runs; the staging path ships fp32 exactly."""
    device = chip_smoke.run(chip_smoke.TOY, require_tpu=False)
    assert device == {"platform": "cpu", "kind": "cpu", "count": 8}
    out = capsys.readouterr().out
    for name, _ in chip_smoke.PHASES:
        assert f"[{name}] wall " in out
    assert '"batch_shards_on_devices": 8' in out
    assert '"jit_trace_growth_after_warmup": 0' in out
    assert "[cache] dir " in out


def test_without_a_tpu_backend_the_script_exits_nonzero_and_reports_nothing():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=REPO_ROOT,
                          env=env, capture_output=True, text=True,
                          timeout=120)
    assert proc.returncode != 0
    assert "no chip" in proc.stderr
    assert '"ok"' not in proc.stdout


def test_a_failing_phase_fails_the_run(monkeypatch, capsys):
    """No phase's error is downgraded to a field in the output: it
    propagates out of main() (a non-zero exit for the script) and the
    closing JSON line is never printed."""
    ran = []

    def fine(ctx):
        ran.append("fine")
        ctx["device"] = {"platform": "tpu", "kind": "fake", "count": 1}
        return {}

    def broken(ctx):
        raise RuntimeError("phase made to fail")

    def never(ctx):
        ran.append("never")
        return {}

    monkeypatch.setattr(chip_smoke, "PHASES",
                        [("device", fine), ("train", broken),
                         ("serve", never)])
    with pytest.raises(RuntimeError, match="phase made to fail"):
        chip_smoke.main()
    assert ran == ["fine"]
    assert '"ok"' not in capsys.readouterr().out
