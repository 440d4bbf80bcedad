"""The grouped-query generator kind over the ``afmoe`` family at toy width on
the CPU, end to end through ``run.py`` in a process of its own: the program
agrees with ``reference/trinity.py``; each of the seven controls, standing
in for the program, fails the comparison; the traced rehearsal prints the
counter metrics and leaves every device metric out; ``reducers/trinity.py``
on hand-made shapes and facts.

Run by hand: ``python -m pytest benchmark/tests/test_trinity.py -q``.
"""

import json
import os
import subprocess
import sys

import pytest

from benchmark.reducers import ling, mimo, trinity

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
TOY = os.path.join(ROOT, "benchmark", "tests", "data", "BENCHMARK.trinity.toy.json")
CELL = "toy_trinity.serve_gen_trinity_toy"
CONTROLS = ("fp8", "swapped", "chunk_cache_dropped", "window_as_full",
            "gate_dropped", "rope_on_full", "post_norms_dropped")


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


def failing(result, prefix=""):
    return sorted(n for n, c in result["compared"].items()
                  if n.startswith(prefix) and not c["value"] <= c["limit"])


@pytest.fixture(scope="module")
def with_controls():
    p, result = run("--stand-in", ",".join(CONTROLS))
    assert p.returncode == 0, p.stderr[-2000:]
    return result


def test_rehearsal_agrees_with_the_reference(with_controls):
    result = with_controls
    assert result["failed"] == 0 and result["attempted"] > 0
    assert set(result["metrics"]) == {"infer_rows_per_s", "setup_s"}
    assert {"logprob_gap_rel", "logprob_gap_p90_rel", "argmax_margin_p90_rel",
            "routing_diff_share", "tokens_short", "requests_unanswered",
            "rows_malformed"} <= set(result["compared"])
    assert not [n for n in failing(result) if "." not in n]


@pytest.mark.parametrize("stand_in", CONTROLS)
def test_the_controls_fail_the_comparison(with_controls, stand_in):
    assert with_controls["correct"] is False
    assert failing(with_controls, stand_in + ".")


def test_traced_rehearsal_prints_the_counter_metrics():
    p, result = run(trace=1, seed=3800000017)
    assert p.returncode == 0, p.stderr[-2000:]
    assert result["correct"] is True
    m = {k: v["value"] for k, v in result["metrics"].items()}
    assert {"decode.step_ms_p50", "serve.prefill_share_pct", "lm.state_fill_pct",
            "moe.held_share_pct", "moe.rows_through_share_pct",
            "lm.kv_cache_fill_pct", "jit.compiles_in_window.serve"} <= set(m)
    assert m["jit.compiles_in_window.serve"] == 0 and m["lm.state_fill_pct"] == 100.0
    assert 15 < m["moe.held_share_pct"] < 40          # 8 of 32 held
    # the CPU has no device plane and no peaks: device metrics stay out
    assert not any("roofline" in n or "idle" in n or "mfu" in n or "hbm" in n
                   for n in m)


def test_the_parent_of_the_family_fails_at_once(monkeypatch):
    """A program that does not know ``afmoe`` is refused before anything is
    drawn: the kind asks the program's ``from_hf`` first."""
    from alink_tpu.dl import lm

    from benchmark import gen_trinity

    with open(os.path.join(ROOT, "benchmark", "configs",
                           "trinity_large_gen.json")) as f:
        cfg = json.load(f)
    hf = gen_trinity.hf_config(cfg)
    assert hf["num_experts"] == 256 and hf["experts_held"] == [0, 32]
    assert not set(hf) & set(gen_trinity.OWN_KEYS)
    assert lm.CausalLMConfig.from_hf(hf).prefill_chunk == 64
    monkeypatch.setitem(hf, "model_type", "unknown")
    with pytest.raises(NotImplementedError):
        lm.CausalLMConfig.from_hf(hf)


CFG = {"num_hidden_layers": 5, "num_dense_layers": 1,
       "layer_types": ["sliding_attention"] * 3 + ["full_attention"] * 2,
       "hidden_size": 8, "intermediate_size": 16, "num_attention_heads": 6,
       "num_key_value_heads": 1, "head_dim": 4, "sliding_window": 3,
       "num_experts": 4, "published": {"num_experts": 16},
       "num_experts_per_tok": 2, "moe_intermediate_size": 3,
       "num_shared_experts": 1, "vocab_size": 100}


V = trinity.as_mimo(CFG)


def test_counts_from_hand_made_shapes():
    assert trinity.kinds(CFG) == [("window", "dense"), ("window", "experts"),
                                  ("window", "experts"), ("attention", "experts"),
                                  ("attention", "experts")]
    # the view reducers/mimo.py reads: the same layers, one key/value width
    assert mimo.kinds(V) == trinity.kinds(CFG)
    # q and the gate 24x8 each, k and v 4x8 each, o 8x24: mimo's four and
    # the gate
    assert trinity.mixer_weights(CFG) == 8 * 4 * (3 * 6 + 2)
    assert trinity.mixer_weights(CFG) - mimo.mixer_weights(V, "window") == 8 * 24
    assert ling.expert_weights(CFG) == 3 * 8 * 3
    # mixers, one dense FFN, four expert layers' routers and shared experts
    assert trinity.dense_weights(CFG) == 5 * 640 + 3 * 8 * 16 + 4 * (16 * 8 + 72)
    assert mimo.kv_bytes_per_position(V, "window") == 1 * 8 * 2.0
    assert mimo.kv_bytes_per_position(V, "attention") == 1 * 8 * 2.0
    assert mimo.core_flops(V, "attention", 7) == 2 * 6 * 2.0 * 8 * 7
    assert mimo.core_flops(V, "window", 7) == 3 * 6 * 2.0 * 8 * 7
    assert ling.experts_touched(CFG, 0) == 0
    assert ling.experts_touched(CFG, 1e4) == pytest.approx(4.0)


def _facts(steps=10, rows=2, new=6, prompt=40):
    tokens = prompt + rows * (new - 1)
    full_steps = 2 * rows * (new - 1) * 24.0
    return {"config": CFG, "new_tokens": new, "rows": rows, "chips": 1,
            "batch": 2, "prefill_chunk": 4, "prompt_tokens": prompt,
            "window_s": 2.0, "kv_positions": 32, "prompt_pairs": 2 * 210,
            "peaks": {"bf16_flops_per_s": 1e6, "hbm_bytes_per_s": 1e6},
            "counters_window": {
                "counters": {"lm.decode_tokens": rows * new,
                             "lm.prefill_tokens": prompt,
                             "moe.assignments": tokens * 2 * 4,
                             "moe.assignments_held": tokens * 4,
                             "attention.full_positions_read": full_steps + 300,
                             "attention.window_positions_read": 999},
                "hists": {"lm.decode_step_s": {"count": steps, "sum": 1.5},
                          "lm.step_kv_positions": {"count": steps,
                                                   "sum": 24.0 * steps}}}}


def _patterns(name):
    with open(os.path.join(ROOT, "benchmark", "metrics", name + ".json")) as f:
        return json.load(f)["args"]


def test_shares_from_hand_made_facts():
    f = _facts()
    tokens, one = 50, 72
    full_step = {"flops": mimo.core_flops(V, "attention", 10 * 24.0),
                 "bytes": 2 * 10 * 24.0 * 16.0}
    ring_step = {"flops": mimo.core_flops(V, "window", 10 * 3),
                 "bytes": 3 * 10 * 4 * 16.0}
    prompt_full = mimo.core_flops(V, "attention", 420)
    prompt_window = mimo.core_flops(V, "window", 3 * 40 - 2 * 3)
    touched = ling.experts_touched(CFG, 1.0)
    need = (10 * 2.0 * (trinity.dense_weights(CFG) + 800)
            + 10 * 4 * touched * one * 2.0 + full_step["bytes"]
            + ring_step["bytes"])
    assert trinity.decode_hbm_share_pct(f) == pytest.approx(100 * need / 2.0 / 1e6)
    ops = (tokens * 2.0 * trinity.dense_weights(CFG) + tokens * 4 * 1 * 2.0 * one
           + full_step["flops"] + ring_step["flops"] + prompt_full
           + prompt_window + 2 * 6 * 2.0 * 800)
    assert trinity.gen_mfu_pct(f) == pytest.approx(100 * ops / 2.0 / 1e6)


def test_rooflines_match_the_kernels_by_name():
    f = _facts()
    f["trace"] = {"ops_all": [
        ("gqa_attend f32[2,1,8,4]", 0.5, 20, "bf16[2,1,6,32]"),     # the kernel
        ("while s32[] f32[2,2,2,4,3]", 7.0, 2, "f32[2,2,2,4,3]"),   # a container
        ("window_attend f32[2,1,8,4]", 0.25, 50, "bf16[2,1,8,7]"),  # the other
        ("fusion f32[2,8]", 9.0, 10, "bf16[8,8]")]}                 # a product
    gqa = trinity.gqa_roofline_pct(f, **_patterns("nope_gqa_roofline.serve"))
    least = max(mimo.core_flops(V, "attention", 240.0), 2 * 240.0 * 16) / 1e6 \
        + max(mimo.core_flops(V, "attention", 420), 300 * 16.0) / 1e6
    assert gqa == pytest.approx(100 * least / 0.5)
    window = trinity.window_roofline_pct(f, **_patterns("wide_window_roofline.serve"))
    least = max(mimo.core_flops(V, "window", 30), 3 * 10 * 4 * 16.0) / 1e6 \
        + mimo.core_flops(V, "window", 114) / 1e6
    assert window == pytest.approx(100 * least / 0.25)


def test_the_expert_roofline_leaves_the_rungs_switch_out():
    f = _facts()
    f["trace"] = {"ops_all": [
        ("ragged-dot f32[8,3]", 0.2, 40, "bf16[8,8] bf16[4,8,3]"),  # products
        ("cond.4.clone f32[2,4,8]", 5.0, 40, "s32[256] bf16[8,8]"),  # the switch
        ("sort s32[256]", 0.05, 10, "s32[256]"),    # 2 rows x 64 x 2 a token
        ("fusion f32[8,16]", 0.05, 10, "bf16[8,8]"),                # router
        ("fusion f32[2,8]", 9.0, 10, "bf16[8,8]")]}                 # a product
    got = trinity.moe_roofline_pct(f, **_patterns("held_moe_roofline.serve"))
    # held a token 2 x 1/2; steps 10 x 4 layers x 1 expert reached x 72 x 2 B;
    # prompt 40 tokens and steps 10 tokens x 4 layers x 2 x 72
    step = max(2 * 72 * 10 * 4, 10 * 4 * 1.0 * 72 * 2.0) / 1e6
    assert got == pytest.approx(100 * (step + 2 * 72 * 40 * 4 / 1e6) / 0.3)
    assert [n for n, _ in f["trace"]["matched"]["trinity"]] == [
        "ragged-dot f32[8,3]", "sort s32[256]", "fusion f32[8,16]"]


def test_a_program_without_the_attention_counters_reads_nothing():
    f = _facts()
    f["counters_window"]["counters"] = {"lm.decode_tokens": 12,
                                        "moe.assignments": 10}
    f["counters_window"]["hists"] = {"lm.decode_step_s": {"count": 10, "sum": 1.0}}
    f["trace"] = {"ops_all": [("gqa_attend f32[2]", 1.0, 1, "")]}
    assert trinity.decode_hbm_share_pct(f) is None
    assert trinity.gen_mfu_pct(f) is None
    assert trinity.gqa_roofline_pct(f, ["gqa"]) is None
    assert trinity.window_roofline_pct(f, ["x"]) is None
    f["counters_window"]["counters"] = {}
    assert trinity.moe_roofline_pct(f, ["ragged"], 4) is None
