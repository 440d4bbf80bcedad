"""The training kind at toy width on the CPU, end to end through ``run.py``
in a process of its own: the program agrees with ``reference/moonlight.py``;
each of the three controls, standing in for the program, fails the comparison
by its own number; the traced rehearsal prints the counter metrics and leaves
every device metric out; ``reducers/moonlight.py`` on the cell's shapes.

Run by hand: ``python -m pytest benchmark/tests/test_moonlight.py -q``.
"""

import json
import os
import subprocess
import sys

import pytest

from benchmark.reducers import moonlight

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
TOY = os.path.join(ROOT, "benchmark", "tests", "data", "BENCHMARK.moonlight.toy.json")
CELL = "toy_moonlight.pretrain_toy"
# each control and the number it has to fail by
CONTROLS = {"fp8": "first_grad_diff_median",
            "router_grad_dropped": "first_grad_diff_router",
            "bias_frozen": "bias_diff_share"}


def run(*extra, trace=0, seed=2147483659):
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_COMPILATION_CACHE_DIR", "BENCH_RUN",
                        "ALINK_SHAPE_BUCKETS")}
    env["JAX_PLATFORMS"] = "cpu"
    cmd = [sys.executable, os.path.join(ROOT, "benchmark", "run.py"), "--workload",
           CELL, "--seed", str(seed), "--seconds", "1", "--trace", str(trace),
           "--benchmark-file", TOY, "--rehearsal", "1"]
    p = subprocess.run(cmd + list(extra), cwd=ROOT, env=env, text=True,
                       capture_output=True, timeout=900)
    lines = p.stdout.strip().splitlines()
    return p, (json.loads(lines[-1]) if p.returncode == 0 and lines else None)


def failing(result):
    return sorted(n for n, c in result["compared"].items()
                  if not c["value"] <= c["limit"])


@pytest.fixture(scope="module")
def with_controls():
    """One run with every control standing in beside the program."""
    p, result = run("--stand-in", ",".join(CONTROLS))
    assert p.returncode == 0, p.stderr[-2000:]
    return result


def test_rehearsal_agrees_with_the_reference(with_controls):
    result = with_controls
    assert result["failed"] == 0 and result["attempted"] > 0
    assert set(result["metrics"]) == {"train_rows_per_s_per_chip", "setup_s"}
    assert {"rows_unmatched", "loss_gap", "first_grad_diff_median",
            "first_grad_diff_router", "bias_diff_share"} <= set(result["compared"])
    assert not [n for n in failing(result) if "." not in n]


@pytest.mark.parametrize("stand_in", sorted(CONTROLS))
def test_the_controls_fail_the_comparison(with_controls, stand_in):
    assert with_controls["correct"] is False
    assert f"{stand_in}.{CONTROLS[stand_in]}" in failing(with_controls)


def test_traced_rehearsal_prints_the_counter_metrics():
    p, result = run(trace=1)
    assert p.returncode == 0, p.stderr[-2000:]
    assert result["correct"] is True
    m = {k: v["value"] for k, v in result["metrics"].items()}
    assert {"jit.compiles_in_window.train", "train.feed_wait_share_pct",
            "train.step_ms_p50", "moe.held_share_pct.train",
            "moe.load_max_over_mean.train",
            "train.tokens_per_expert", "setup.ingest_s"} <= set(m)
    assert m["jit.compiles_in_window.train"] == 0
    assert 25 < m["moe.held_share_pct.train"] < 75       # 8 of 16 held
    # no device, no peak: nothing measured on a chip is printed
    assert not {"device.idle_pct.train", "mla_roofline.train",
                "moe_roofline.train", "train.moe_step_mfu_pct",
                "train.optimizer_hbm_share_pct"} & set(m)


def test_required_operations_of_the_cell():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "moonlight_16b_a3b_train.json")) as f:
        cfg = json.load(f)
    assert moonlight.parameters(cfg) == 668860416   # the norms' 30,000 apart
    flops = moonlight.step_flops(cfg, 8192, 1.0, 0.75)
    assert abs(sum(flops.values()) / 21.55e12 - 1) < 0.01
    assert abs(flops["core"] / sum(flops.values()) - 0.287) < 0.01
    facts = {"config": cfg, "peaks": {"bf16_flops_per_s": 197e12,
                                      "hbm_bytes_per_s": 819e9},
             "rows": 128, "seq_len": 8192, "window_s": 45.0, "chips": 1,
             "mode": "train", "steps": 64,
             "counters_window": {"counters": {"moe.assignments": 800,
                                              "moe.assignments_held": 100}}}
    assert abs(moonlight.mfu_pct(facts) - 100 * 128 * 21.55e12 / 45 / 197e12) < 0.2
    assert moonlight.mfu_pct(dict(facts, counters_window={})) is None
    assert abs(moonlight.optimizer_hbm_share_pct(facts)
               - 100 * 28 * 668860416 * 64 / 45 / 819e9) < 1e-6
    assert moonlight.tokens_per_expert(facts) == 100 / 64 / 5 / 8
    assert moonlight.mla_roofline_pct(facts, ["x"], 1024) is None   # no trace
