"""The generator kind at toy width on the CPU, end to end through ``run.py``
in a process of its own: the program agrees with the reference; the reference
in float8, with two rows' states exchanged and with the state lost between
prompt chunks, standing in for the program, fail the comparison; the traced rehearsal prints the span and counter metrics
and leaves every device metric out; ``reducers/lm.py`` on hand-made shapes.

Run by hand: ``python -m pytest benchmark/tests/test_lm.py -q``.
"""

import json
import os
import subprocess
import sys

import pytest

from benchmark.reducers import lm

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
TOY = os.path.join(ROOT, "benchmark", "tests", "data", "BENCHMARK.lm.toy.json")
CELL = "toy_lm.serve_gen_toy"


def run(*extra, trace=0, seed=2147483659):
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_COMPILATION_CACHE_DIR", "BENCH_RUN")}
    env["JAX_PLATFORMS"] = "cpu"
    cmd = [sys.executable, os.path.join(ROOT, "benchmark", "run.py"), "--workload",
           CELL, "--seed", str(seed), "--seconds", "1", "--trace", str(trace),
           "--benchmark-file", TOY, "--rehearsal", "1"]
    p = subprocess.run(cmd + list(extra), cwd=ROOT, env=env, text=True,
                       capture_output=True, timeout=600)
    lines = p.stdout.strip().splitlines()
    return p, (json.loads(lines[-1]) if p.returncode == 0 and lines else None)


def failing(result):
    return sorted(n for n, c in result["compared"].items()
                  if not c["value"] <= c["limit"])


def test_rehearsal_agrees_with_the_reference():
    p, result = run()
    assert p.returncode == 0, p.stderr[-2000:]
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    assert set(result["metrics"]) == {"infer_rows_per_s", "setup_s"}
    assert {"logprob_gap_rel", "argmax_margin_rel", "tokens_short",
            "requests_unanswered", "rows_malformed"} <= set(result["compared"])


@pytest.mark.parametrize("stand_in", ["fp8", "swapped", "chunk_state_dropped"])
def test_the_controls_fail_the_comparison(stand_in):
    p, result = run("--stand-in", stand_in)
    assert p.returncode == 0, p.stderr[-2000:]
    assert result["correct"] is False
    assert f"{stand_in}.logprob_gap_rel" in failing(result)
    # the program itself, in the same run, is within its limits
    assert not [n for n in failing(result) if "." not in n]


def test_traced_rehearsal_prints_the_generators_metrics():
    p, result = run(trace=1)
    assert p.returncode == 0, p.stderr[-2000:]
    m = {k: v["value"] for k, v in result["metrics"].items()}
    assert {"decode.step_ms_p50", "serve.prefill_share_pct",
            "serve.decode_share_pct", "serve.lm_tokenize_share_pct",
            "lm.state_fill_pct", "lm.loads_in_window", "serve.batch_fill_pct",
            "jit.compiles_in_window.serve"} <= set(m)
    assert m["lm.loads_in_window"] == 0 and m["jit.compiles_in_window.serve"] == 0
    assert m["lm.state_fill_pct"] == 100.0
    assert m["serve.decode_share_pct"] > 0 and m["serve.prefill_share_pct"] > 0
    # the CPU has no device plane and no peaks: device metrics stay out
    assert not any("roofline" in n or "idle" in n or "mfu" in n or "hbm" in n
                   for n in m)


CFG = {"num_hidden_layers": 2, "hidden_size": 8, "intermediate_size": 16,
       "head_dim": 4, "num_attention_heads": 4, "num_key_value_heads": 2,
       "vocab_size": 100}


def test_counts_from_hand_made_shapes():
    assert lm.phi_dim(CFG) == 10
    # q 8x16, k and v 8x8 each, o 16x8, gate 2x8, three FFN matrices 8x16
    assert lm.layer_weights(CFG) == 128 + 64 + 64 + 128 + 16 + 3 * 128
    # 2 layers x 2 heads x 10 x (4 + 1) floats
    assert lm.state_bytes_per_row(CFG) == 2 * 2 * 10 * 5 * 4
    step = lm.retention_step(CFG, rows=3)
    assert step["bytes"] == 3 * (2 * 800 + 2 * (8 + 4) * 4 * 4)
    assert step["flops"] == 3 * 2 * 2.0 * 10 * 5 * 6
    assert lm.retention_prompt(CFG, tokens=7)["flops"] == 7 * 2 * 2.0 * 10 * 5 * 6
    assert lm.decode_step_bytes(CFG, rows=3) == 2.0 * (2 * 784 + 800) + 2 * 3 * 800
    assert lm.dense_flops_per_token(CFG) == 2.0 * 2 * 784
    assert lm.head_flops(CFG) == 2.0 * 8 * 100


def _facts(steps=10, rows=2, new=6):
    return {"config": CFG, "new_tokens": new, "rows": rows, "chips": 1,
            "prompt_tokens": 40, "window_s": 2.0, "state_slots": 4,
            "peaks": {"bf16_flops_per_s": 1e6, "hbm_bytes_per_s": 1e6},
            "counters_window": {
                "counters": {"lm.decode_tokens": rows * new, "lm.prefill_tokens": 40},
                "hists": {"lm.decode_step_s": {"count": steps, "sum": 1.5},
                          "lm.step_slots_in_use": {"count": steps,
                                                   "sum": rows * steps}}}}


def test_shares_from_hand_made_facts():
    f = _facts()
    tokens = 40 + 2 * 5
    ops = tokens * 2.0 * 2 * 784 + tokens * 1200.0 + 2 * 6 * 1600.0
    assert lm.gen_mfu_pct(f) == pytest.approx(100 * ops / 2.0 / 1e6)
    need = 10 * lm.decode_step_bytes(CFG, rows=2 * 5 / 10)
    assert lm.decode_hbm_share_pct(f) == pytest.approx(100 * need / 2.0 / 1e6)
    assert lm.state_fill_pct(f) == 50.0
    f["trace"] = {"ops_all": [("fusion f32[2,2,10,4]", 0.5, 10, "f32[2,2,10,4]"),
                              ("while s32[] f32[2,2,10,4]", 7.0, 2, "f32[2,2,10,4]"),
                              ("fusion f32[2,8]", 9.0, 10, "bf16[8,8]"),
                              ("fusion f32[2,7,3,4]", 0.125, 10, "f32[2,7,4]"),
                              ("slice f32[2,7,15]", 0.125, 10, "f32[2,7,16]"),
                              ("fusion f32[2,2,2]", 0.25, 10, "f32[2,2,10]")]}
    least = max(lm.retention_step(CFG, 10)["bytes"], lm.retention_step(CFG, 10)["flops"]) / 1e6 \
        + lm.retention_prompt(CFG, 40)["flops"] / 1e6
    got = lm.retention_roofline_pct(
        f, json.load(open(os.path.join(ROOT, "benchmark", "metrics",
                                       "retention_roofline.serve.json")))["args"]["patterns"])
    # the loop is a container and the dense fusion is no part of the core
    assert got == pytest.approx(100 * least / 1.0)


def test_a_program_without_the_generator_reads_nothing():
    f = _facts()
    f["counters_window"] = {"counters": {}, "hists": {"span.serving.batch_s":
                                                      {"count": 1, "sum": 1.0}}}
    f["trace"] = {"ops_all": []}
    assert lm.decode_hbm_share_pct(f) is None
    assert lm.state_fill_pct(f) is None
    assert lm.retention_roofline_pct(f, ["retention"]) is None
