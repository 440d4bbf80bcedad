"""The hybrid generator kind at toy width on the CPU, end to end through
``run.py`` in a process of its own: the program agrees with
``reference/ling.py``; each of the four controls, standing in for the
program, fails the comparison; the traced rehearsal prints the counter metrics
and leaves every device metric out; ``reducers/ling.py`` on hand-made shapes.

Run by hand: ``python -m pytest benchmark/tests/test_ling.py -q``.
"""

import json
import os
import subprocess
import sys

import pytest

from benchmark.reducers import ling

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
TOY = os.path.join(ROOT, "benchmark", "tests", "data", "BENCHMARK.ling.toy.json")
CELL = "toy_ling.serve_gen_ling_toy"
# each control and the number it has to fail by (the ungrouped choice moves a
# log-probability little more than bfloat16 does: the routing's own number
# parts it)
CONTROLS = {"fp8": "logprob_gap_rel", "swapped": "logprob_gap_rel",
            "chunk_state_dropped": "logprob_gap_rel",
            "route_ungrouped": "routing_diff_share"}


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
    assert set(result["metrics"]) == {"infer_rows_per_s", "setup_s"}
    assert {"logprob_gap_rel", "argmax_margin_rel", "routing_diff_share",
            "tokens_short", "requests_unanswered",
            "rows_malformed"} <= set(result["compared"])
    # the program itself is within its limits
    assert not [n for n in failing(result) if "." not in n]


@pytest.mark.parametrize("stand_in", sorted(CONTROLS))
def test_the_controls_fail_the_comparison(with_controls, stand_in):
    assert with_controls["correct"] is False
    assert f"{stand_in}.{CONTROLS[stand_in]}" in failing(with_controls)


def test_traced_rehearsal_prints_the_hybrid_metrics():
    p, result = run(trace=1)
    assert p.returncode == 0, p.stderr[-2000:]
    assert result["correct"] is True
    m = {k: v["value"] for k, v in result["metrics"].items()}
    assert {"decode.step_ms_p50", "serve.prefill_share_pct",
            "serve.decode_share_pct", "lm.state_fill_pct", "lm.loads_in_window",
            "moe.held_share_pct", "moe.load_max_over_mean",
            "lm.latent_cache_fill_pct", "jit.compiles_in_window.serve"} <= set(m)
    assert m["jit.compiles_in_window.serve"] == 0 and m["lm.state_fill_pct"] == 100.0
    assert 10 < m["moe.held_share_pct"] < 45          # 8 of 32 held
    assert m["moe.load_max_over_mean"] >= 1.0
    assert 0 < m["lm.latent_cache_fill_pct"] <= 100
    # the CPU has no device plane and no peaks: device metrics stay out
    assert not any("roofline" in n or "idle" in n or "mfu" in n or "hbm" in n
                   for n in m)


CFG = {"num_hidden_layers": 7, "layer_group_size": 6, "first_k_dense_replace": 1,
       "hidden_size": 8, "intermediate_size": 16, "head_dim": 4,
       "num_attention_heads": 2, "short_conv_kernel_size": 4, "kv_lora_rank": 6,
       "qk_nope_head_dim": 4, "qk_rope_head_dim": 2, "v_head_dim": 4,
       "num_experts": 4, "num_experts_per_tok": 2, "moe_intermediate_size": 3,
       "moe_shared_expert_intermediate_size": 5, "vocab_size": 100}


def test_counts_from_hand_made_shapes():
    assert ling.kinds(CFG) == [("kda", "dense")] + [("kda", "experts")] * 4 \
        + [("mla", "experts"), ("kda", "experts")]
    # q, k, v, f, g, o 8x8 each and beta 2x8
    assert ling.mixer_weights(CFG, "kda") == 6 * 64 + 16
    # q 12x8, kv_a 8x8, kv_b 16x6, head gate 2x8, o 8x8
    assert ling.mixer_weights(CFG, "mla") == 96 + 64 + 96 + 16 + 64
    assert ling.expert_weights(CFG) == 3 * 8 * 3
    assert ling.dense_weights(CFG) == 6 * 400 + 336 + 3 * 8 * 16 + 6 * 3 * 8 * 5
    # 6 layers x (2 heads x 4 x 4 + 3 tails x 3 x 8) floats
    assert ling.kda_state_bytes_per_row(CFG) == 6 * (32 + 72) * 4
    step = ling.kda_step(CFG, rows=3)
    assert step["bytes"] == 3 * (2 * 2496 + 6 * 5 * 8 * 4)
    assert step["flops"] == 3 * 6 * 2 * 7.0 * 16
    prompt = ling.kda_prompt(CFG, tokens=10, chunk=4)
    assert prompt["flops"] == 10 * 6 * 2 * (4.0 * 4 * 4 + 6 * 16)
    assert prompt["bytes"] == 10 * 6 * 2 * (2.0 * 16 * 4 / 4 + 5 * 4 * 4)
    core = ling.mla_core(CFG, queries=5, seen=40)
    assert core["flops"] == 2 * 2.0 * (5 * 6 * 8 + 40 * 14)
    assert ling.latent_bytes_per_position(CFG) == 16.0
    assert ling.experts_touched(CFG, 0) == 0
    assert ling.experts_touched(CFG, 2) == pytest.approx(4 * (1 - 0.75 ** 2))
    assert ling.experts_touched(CFG, 1e4) == pytest.approx(4.0)


def _facts(steps=10, rows=2, new=6, prompt=40):
    tokens = prompt + rows * (new - 1)
    return {"config": CFG, "new_tokens": new, "rows": rows, "chips": 1, "batch": 2,
            "prompt_tokens": prompt, "window_s": 2.0, "latent_positions": 32,
            "peaks": {"bf16_flops_per_s": 1e6, "hbm_bytes_per_s": 1e6},
            "counters_window": {
                "counters": {"lm.decode_tokens": rows * new,
                             "lm.prefill_tokens": prompt,
                             "moe.assignments": tokens * 2 * 6,
                             "moe.assignments_held": tokens * 6},
                "hists": {"lm.decode_step_s": {"count": steps, "sum": 1.5},
                          "lm.step_latent_positions": {"count": steps,
                                                       "sum": 24.0 * steps},
                          "moe.expert_load_max_over_mean": {"count": 12,
                                                            "sum": 18.0}}}}


def _patterns(name):
    with open(os.path.join(ROOT, "benchmark", "metrics", name + ".json")) as f:
        return json.load(f)["args"]


def test_shares_from_hand_made_facts():
    f = _facts()
    tokens, one = 50, 72
    # half of the 2 choices a token are held: 1 a token a layer, 6 layers
    need_step = 10 * 6 * ling.experts_touched(CFG, 1.0) * one * 2.0
    mla_step = 10 * 24.0 * 16.0
    fixed = 2.0 * (ling.dense_weights(CFG) + 800)
    need = 10 * (fixed + 2.0 * 1 * 2496) + need_step + mla_step
    assert ling.decode_hbm_share_pct(f) == pytest.approx(100 * need / 2.0 / 1e6)
    ops = (tokens * 2.0 * ling.dense_weights(CFG) + tokens * 6 * 1 * 2.0 * one
           + ling.kda_prompt(CFG, tokens, 4)["flops"]
           + ling.mla_core(CFG, 10, 240.0)["flops"]
           + ling.mla_core(CFG, 40, 40 * 21 / 2)["flops"] + 2 * 6 * 2.0 * 800)
    assert ling.gen_mfu_pct(f, chunk=4) == pytest.approx(100 * ops / 2.0 / 1e6)
    assert ling.hist_mean(f, "window", "moe.expert_load_max_over_mean") == 1.5
    assert ling.latent_fill_pct(f) == 75.0


def test_rooflines_match_by_shape_and_name():
    f = _facts()
    f["trace"] = {"ops_all": [
        ("fusion f32[2,2,4,4]", 0.5, 10, "f32[2,2,4,4]"),           # kda state
        ("while s32[] f32[2,2,4,4]", 7.0, 2, "f32[2,2,4,4]"),       # a container
        ("custom-call f32[2,2,4,4]", 0.25, 4, "f32[2,2,4,4] f32[2,2,4,4]"),
        ("fusion f32[2,8]", 9.0, 10, "bf16[8,8]"),                  # a dense product
        ("fusion f32[2,2,1,32]", 0.125, 10, "bf16[2,32,8]"),        # scores
        ("ragged-dot-none f32[4,6]", 0.5, 20, "bf16[4,8] bf16[4,8,6]"),
        ("fusion f32[16,8]", 0.25, 20, "f32[16,8] s32[16]")]}       # unsort
    kda = ling.kda_roofline_pct(f, **_patterns("kda_roofline.serve") | {"chunk": 4})
    least = max(ling.kda_step(CFG, 10).values()) / 1e6 \
        + max(ling.kda_prompt(CFG, 40, 4).values()) / 1e6
    assert kda == pytest.approx(100 * least / 0.75)
    mla = ling.mla_roofline_pct(f, **_patterns("mla_roofline.serve") | {"chunk": 4})
    step = ling.mla_core(CFG, 10, 240.0)
    least = max(step["flops"], 10 * 24.0 * 16.0) / 1e6 \
        + ling.mla_core(CFG, 40, 40 * 21 / 2)["flops"] / 1e6
    assert mla == pytest.approx(100 * least / 0.125)
    moe = ling.moe_roofline_pct(f, **_patterns("moe_roofline.serve") | {"chunk": 4})
    flops = lambda t: 2.0 * 72 * 1 * t * 6
    least = max(flops(10), 10 * 6 * ling.experts_touched(CFG, 1.0) * 72 * 2.0) / 1e6 \
        + flops(40) / 1e6
    assert moe == pytest.approx(100 * least / 0.75)
    assert set(f["trace"]["matched"]) == {"ling"}


def test_a_program_without_the_hybrid_counters_reads_nothing():
    f = _facts()
    f["counters_window"] = {"counters": {"lm.decode_tokens": 12},
                            "hists": {"lm.decode_step_s": {"count": 10, "sum": 1.0}}}
    f["trace"] = {"ops_all": []}
    assert ling.decode_hbm_share_pct(f) is None
    assert ling.gen_mfu_pct(f, chunk=4) is None
    assert ling.latent_fill_pct(f) is None
    assert ling.hist_mean(f, "window", "moe.expert_load_max_over_mean") is None
    assert ling.moe_roofline_pct(f, ["ragged"], 4) is None
    assert ling.kda_roofline_pct(f, ["x"], 4) is None
    assert ling.mla_roofline_pct(f, ["x"], 4) is None
