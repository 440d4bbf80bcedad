"""The readers of the program's per-name span sums (``reducers/spans.py``) on
hand-made facts, and the toy serve cell traced through
``data/BENCHMARK.spans.toy.json``, which lists the toy cells of
``BENCHMARK.toy.json`` and the per-layer metrics that read the spans: every
one is printed, and the eight shares of the window add up to the window."""

import json
import os

import pytest

from benchmark.reducers import spans
from benchmark.tests.test_run import ROOT, run

SPANS_TOY = os.path.join(ROOT, "benchmark", "tests", "data",
                         "BENCHMARK.spans.toy.json")
SHARES = ["serve.client_wait_share_pct", "serve.build_table_share_pct",
          "serve.reply_share_pct", "serve.mapper_load_share_pct",
          "serve.tokenize_share_pct", "serve.place_params_share_pct",
          "serve.apply_share_pct", "serve.unnamed_share_pct"]


def hist(total, count=1):
    return {"buckets": [1.0], "counts": [count, 0], "count": count, "sum": total}


FACTS = {
    "window_s": 10.0,
    "counters_window": {"seconds": 10.001, "counters": {"mapper.model_loads": 4},
                        "hists": {"span.serving.batch_s": hist(8.0, 4),
                                  "span.mapper.load_model_s": hist(5.0, 4),
                                  "span.bert.tokenize_s": hist(1.5, 4),
                                  "serving.queue_s": hist(0.4, 1024)}},
    "counters_setup": {"seconds": 50.0, "counters": {},
                       "hists": {"span.train.tokenize_s": hist(2.0),
                                 "span.train.place_state_s": hist(3.0),
                                 "jit.persist_load_s": hist(7.0, 113)}},
    # a program from before its spans were summed by name
    "counters_before": {"seconds": 10.0, "counters": {"jit.compile": 0},
                        "hists": {"trace.span_s": hist(9.0, 40),
                                  "serving.queue_s": hist(0.4, 1024)}},
}


@pytest.mark.parametrize("reader,args,expected", [
    (spans.seconds, {"phase": "setup", "names": ["span.train.tokenize_s",
                                                 "span.train.place_state_s"]}, 5.0),
    (spans.seconds, {"phase": "setup", "names": ["jit.persist_load_s"]}, 7.0),
    (spans.share_pct, {"phase": "window", "names": ["span.mapper.load_model_s"]}, 50.0),
    (spans.share_pct, {"phase": "setup", "names": ["span.train.tokenize_s"]}, 4.0),
    (spans.rest_share_pct, {"phase": "window", "whole": ["span.serving.batch_s"],
                            "parts": ["span.mapper.load_model_s",
                                      "span.bert.tokenize_s"]}, 15.0),
    (spans.counter, {"phase": "window", "name": "mapper.model_loads"}, 4.0),
    # a name that did not occur in the phase counts 0
    (spans.seconds, {"phase": "window", "names": ["span.serving.wait_s"]}, 0.0),
    (spans.share_pct, {"phase": "window", "names": ["span.serving.wait_s",
                                                    "span.bert.tokenize_s"]}, 15.0),
    (spans.rest_share_pct, {"phase": "window", "whole": ["span.serving.batch_s"],
                            "parts": ["span.dl.predict.apply_s"]}, 80.0),
    (spans.counter, {"phase": "setup", "name": "mapper.model_loads"}, 0.0),
    # no span histogram at all, or no such phase: nothing to read
    (spans.seconds, {"phase": "before", "names": ["span.serving.batch_s"]}, None),
    (spans.share_pct, {"phase": "before", "names": ["serving.queue_s"]}, None),
    (spans.rest_share_pct, {"phase": "before", "whole": ["span.serving.batch_s"],
                            "parts": []}, None),
    (spans.counter, {"phase": "before", "name": "mapper.model_loads"}, None),
    (spans.seconds, {"phase": "absent", "names": ["span.serving.batch_s"]}, None),
])
def test_readers_on_hand_made_facts(reader, args, expected):
    got = reader(FACTS, **args)
    assert got is None if expected is None else got == pytest.approx(expected)


def test_the_toy_benchmark_file_lists_the_committed_entries():
    """The span metrics stand in the toy file as in ``BENCHMARK.json``, cell
    names apart, and each names a reader of ``reducers/spans.py``."""
    def span_entries(path):
        with open(path) as f:
            entries = json.load(f)["per_layer"]
        out = {}
        for m in entries:
            with open(os.path.join(ROOT, "benchmark", "metrics",
                                   m["name"] + ".json")) as f:
                if json.load(f)["reducer"].startswith("spans."):
                    out[m["name"]] = dict(m, workloads="workloads" in m)
        return out

    real = span_entries(os.path.join(ROOT, "BENCHMARK.json"))
    assert len(real) == 12 and set(SHARES) < set(real)
    assert span_entries(SPANS_TOY) == real


def test_traced_toy_serve_prints_every_span_metric_and_the_shares_add_up():
    p, result = run("toy_cls.serve_toy", "--benchmark-file", SPANS_TOY, trace=1)
    assert p.returncode == 0, p.stderr[-2000:]
    got = {n: m["value"] for n, m in result["metrics"].items()}
    print({n: round(v, 3) for n, v in got.items() if n.startswith(("serve.", "setup."))})
    assert set(SHARES) | {"serve.model_loads_in_window", "setup.cache_load_s",
                          "setup.ingest_s", "setup.server_warmup_s"} <= set(got)
    assert all(got[n] >= 0 for n in SHARES)
    assert 97.0 <= sum(got[n] for n in SHARES) <= 101.0
    # one model load a batch today, timed from inside as from outside
    assert got["serve.model_loads_in_window"] >= 1
    assert got["serve.mapper_load_share_pct"] == pytest.approx(
        got["serve.model_load_share_pct"], abs=3.0)
    assert got["setup.ingest_s"] > 0 and got["setup.server_warmup_s"] > 0


def test_traced_toy_fit_prints_the_set_up_metrics():
    p, result = run("toy_cls.finetune_toy", "--benchmark-file", SPANS_TOY, trace=1)
    assert p.returncode == 0, p.stderr[-2000:]
    got = result["metrics"]
    assert got["setup.ingest_s"]["value"] > 0 and "setup.cache_load_s" in got
    assert not any(n.startswith("serve.") for n in got)
