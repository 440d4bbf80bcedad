"""Toy-width CPU rehearsals of each kind, end to end through ``run.py`` in a
process of their own (one device, no compile cache): the last line has the
contract's keys; the program agrees with the reference; the reference in a
lower precision, or broken, standing in for the program fails the comparison;
the program's timed path broken underneath fails it; a run without a TPU that
does not ask for the rehearsal fails and prints no result.

Run by hand: ``python -m pytest benchmark/tests -q`` (about five minutes).
"""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
TOY = os.path.join(ROOT, "benchmark", "tests", "data", "BENCHMARK.toy.json")
KEYS = {"correct", "attempted", "failed", "metrics", "device"}
# each toy traffic file rehearses one committed traffic file
REHEARSES = {"tests/data/traffic/finetune_toy.json": "traffic/finetune_doc512.json",
             "tests/data/traffic/serve_toy.json": "traffic/serve_doc512_c256.json"}


def run(workload, *extra, fault=None, rehearsal=True, trace=0, seed=2147483659):
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_COMPILATION_CACHE_DIR", "BENCH_RUN")}
    env["JAX_PLATFORMS"] = "cpu"
    head = [sys.executable, "-m", "benchmark.tests.faults", fault] if fault \
        else [sys.executable, os.path.join(ROOT, "benchmark", "run.py")]
    cmd = head + ["--workload", workload, "--seed", str(seed), "--seconds", "1",
                  "--trace", str(trace), "--benchmark-file", TOY]
    if rehearsal:
        cmd += ["--rehearsal", "1"]
    p = subprocess.run(cmd + list(extra), cwd=ROOT, env=env, text=True,
                       capture_output=True, timeout=600)
    lines = p.stdout.strip().splitlines()
    return p, (json.loads(lines[-1]) if p.returncode == 0 and lines else None)


def failing(result):
    return sorted(n for n, c in result["compared"].items()
                  if not c["value"] <= c["limit"])


@pytest.mark.parametrize("workload,metrics", [
    ("toy_cls.finetune_toy", {"train_rows_per_s_per_chip", "setup_s"}),
    ("toy_cls.serve_toy", {"infer_rows_per_s", "setup_s"})])
def test_rehearsal_agrees_with_the_reference(workload, metrics):
    p, result = run(workload)
    assert p.returncode == 0, p.stderr[-2000:]
    assert KEYS <= set(result) and list(result)[-1] == "compared"
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    assert set(result["metrics"]) == metrics
    assert all(m["value"] > 0 and m["unit"] for m in result["metrics"].values())
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(result["device"])
    tail = p.stderr.strip().splitlines()[-len(result["compared"]):]
    assert all(line.startswith("compared ") and " limit " in line for line in tail)


@pytest.mark.parametrize("workload", ["toy_cls.finetune_toy", "toy_cls.serve_toy"])
def test_traced_rehearsal_prints_per_layer_metrics(workload):
    p, result = run(workload, trace=1)
    assert p.returncode == 0, p.stderr[-2000:]
    assert result["correct"] is True
    assert result["metrics"] and "setup_s" not in result["metrics"]
    # the CPU has no device plane: every device metric stays out of the line
    assert not any("roofline" in n or "idle" in n or "mfu" in n
                   for n in result["metrics"])


@pytest.mark.parametrize("toy,cell", sorted(REHEARSES.items()))
def test_the_toy_limits_stand_as_the_committed_limits_do(toy, cell):
    """The toy cell compares the numbers the committed cell compares, and each
    toy limit stands to the toy's own lower reading (the largest that sound
    runs gave, ``limits_from`` in the file) about as the committed limit
    stands to the cell's: what the tests below show of the toy limits, they
    show of limits placed as the committed ones are."""
    with open(os.path.join(ROOT, "benchmark", toy)) as f:
        toy = json.load(f)
    with open(os.path.join(ROOT, "benchmark", cell)) as f:
        cell = json.load(f)
    assert set(toy["limits"]) == set(cell["limits"])
    for name, limit in cell["limits"].items():
        room = limit / cell["limits_from"][name]["lower"]
        toy_room = toy["limits"][name] / toy["limits_from"][name]["lower"]
        assert 0.6 * room <= toy_room <= 1.6 * room, name
        for side in (toy, cell):
            read = side["limits_from"][name]
            assert read["lower"] < side["limits"][name] < read["upper"], name
            assert read["upper"] >= 3 * read["lower"], name


@pytest.mark.parametrize("workload,stand_in,fails", [
    ("toy_cls.finetune_toy", "fp8,half_batch,frozen",
     ["fp8.first_grad_diff_median", "half_batch.change_norm_gap",
      "half_batch.first_grad_diff_median", "frozen.change_norm_gap"]),
    ("toy_cls.serve_toy", "fp8,swapped", ["fp8.prob_gap_rel", "swapped.prob_gap_rel"])])
def test_the_control_fails_the_comparison(workload, stand_in, fails):
    p, result = run(workload, "--stand-in", stand_in)
    assert p.returncode == 0, p.stderr[-2000:]
    assert result["correct"] is False
    wrong = failing(result)
    assert set(fails) <= set(wrong)
    assert all("." in n for n in wrong)     # the program itself still agrees


@pytest.mark.parametrize("workload,fault,fails", [
    ("toy_cls.finetune_toy", "frozen_step", "change_norm_gap"),
    ("toy_cls.finetune_toy", "half_batch", "change_norm_gap"),
    ("toy_cls.serve_toy", "altered_answer", "prob_gap_rel")])
def test_a_broken_timed_path_fails_the_run(workload, fault, fails):
    p, result = run(workload, fault=fault)
    assert p.returncode == 0, p.stderr[-2000:]
    assert result["correct"] is False
    assert fails in failing(result)


def test_no_tpu_no_result():
    p, result = run("toy_cls.finetune_toy", rehearsal=False)
    assert p.returncode != 0 and result is None
    assert "{" not in p.stdout
    assert "TPU" in p.stderr


def test_unknown_workload_is_refused():
    p, result = run("no_such.cell")
    assert p.returncode != 0 and result is None
