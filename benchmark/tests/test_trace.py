"""The reduction from a trace to busy time, idle share, per-operation time and
named idle gaps, on a small hand-made trace (``data/trace_small.json``):

- window: from the end of ``bench.window_open`` (501,000 ns) to the start of
  ``bench.window_close`` (20,000,000 ns), 19.499 ms; ``fusion.7`` ends before
  it and is dropped, ``fusion.9`` straddles its end and is cut to 0.5 ms;
- busy: fusion.1 (1-3 ms) and fusion.2 (2.5-4 ms) overlap, union 3 ms; copy
  10-11 ms; fusion.1 again 11.5-13.5 ms; fusion.9 19.5-20 ms: 6.5 ms;
- gaps: 0.499 ms (before any annotation) and 0.5 ms (under ``bench.batch``)
  are pooled as short, by annotation; 4-10 ms starts under
  ``bench.load_model`` and the pjit call; 13.5-19.5 ms starts under
  ``bench.batch`` and ``shard_args``.
"""

import json
import os

import pytest

from benchmark.reducers import model, trace

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def reduced():
    with open(os.path.join(HERE, "data", "trace_small.json")) as f:
        events = [tuple(e) for e in json.load(f)["events"]]
    events = [e for e in events
              if not (trace.DEVICE_PLANE.match(e[0]) and e[1] != trace.OPS_LINE)]
    return trace.reduce_events(events)


def test_busy_is_the_union_inside_the_window(reduced):
    assert reduced["busy_s"] == pytest.approx(6.5e-3)
    assert reduced["window_s"] == pytest.approx(19.499e-3)


def test_idle_share(reduced):
    facts = {"trace": reduced, "mode": "train"}
    assert trace.idle_pct(facts, "train") == pytest.approx(
        100 * (1 - 6.5 / 19.499))
    assert trace.idle_pct(facts, "serve") is None
    assert trace.idle_pct({"trace": None, "mode": "train"}, "train") is None


def test_time_per_operation(reduced):
    ops = {name: (s, n) for name, s, n, _ in reduced["ops_all"]}
    assert ops["fusion_kOutput f32[8,12,512,512]"] == (pytest.approx(4e-3), 2)
    assert ops["fusion_kLoop bf16[8,512,3072]"] == (pytest.approx(1.5e-3), 1)
    assert ops["copy bf16[8,512,768]"] == (pytest.approx(1e-3), 1)
    assert ops["fusion_kInput f32[768]"] == (pytest.approx(0.5e-3), 1)
    assert not any("bf16[8,512,768]" in n and n.startswith("fusion_kLoop")
                   for n in ops)           # fusion.7 ran before the window
    assert reduced["device_ops"][0][0] == "fusion_kOutput f32[8,12,512,512] x2"


def test_gaps_are_named_by_annotation_and_host_call(reduced):
    gaps = dict(reduced["idle_gaps"])
    assert gaps["bench.load_model / PjitFunction(convert_element_type)"] == \
        pytest.approx(6e-3)
    assert gaps["bench.batch / shard_args"] == pytest.approx(6e-3)
    assert gaps["no_annotation / 1 gaps under 1.333 ms"] == pytest.approx(0.499e-3)
    assert gaps["bench.batch / 1 gaps under 1.333 ms"] == pytest.approx(0.5e-3)
    assert sum(gaps.values()) + reduced["busy_s"] == pytest.approx(
        reduced["window_s"])


def test_attention_roofline_matches_by_result_and_by_operand(reduced):
    cfg = {"hidden_size": 768, "num_hidden_layers": 12, "num_attention_heads": 12}
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    facts = {"trace": dict(reduced), "peaks": peaks, "mode": "serve", "config": cfg,
             "batch": 8, "chips": 1, "seq_len": 512, "trace_rows": 16}
    need = model.attention_core(cfg, 512, 16, "serve")
    least = max(need["flops"] / 197e12, need["bytes"] / 819e9)
    got = model.attn_roofline_pct(facts, "serve", ["\\[{B},{H},{S},{S}\\]"])
    assert got == pytest.approx(100 * least / 4e-3)
    by_operand = model.attn_roofline_pct(facts, "serve", ["\\[{B},{S},{H},{D}\\]"])
    assert by_operand == pytest.approx(100 * least / 4e-3)
    assert model.attn_roofline_pct(facts, "serve", ["no_such_kernel"]) is None
    assert model.attn_roofline_pct(facts, "train", ["."]) is None


def test_unknown_device_kind_is_an_error():
    assert model.device_peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        model.device_peaks("cpu")
