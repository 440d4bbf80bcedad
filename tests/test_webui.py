"""WebUI server: catalog API, experiment CRUD, DAG build/run/inspect
(reference: webui/server ServerApplication.java + controllers)."""

import json
import urllib.error
import urllib.request

import pytest

from alink_tpu.webui import ExperimentStore, WebUIServer, run_experiment


def _req(port, path, method="GET", body=None):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}", method=method,
        data=None if body is None else json.dumps(body).encode())
    with urllib.request.urlopen(req, timeout=30) as r:
        return json.loads(r.read())


@pytest.fixture()
def server(tmp_path):
    srv = WebUIServer(port=0, store=ExperimentStore(
        str(tmp_path / "exp.json")))
    srv.start(background=True)
    yield srv
    srv.stop()


THREE_NODE_DAG = {
    "name": "demo",
    "nodes": [
        {"id": "src", "op": "MemSourceBatchOp",
         "params": {"rows": [[1, "a", 2.0], [2, "b", 4.0], [3, "a", 9.0]],
                    "schemaStr": "id long, g string, x double"}},
        {"id": "sql", "op": "SqlQueryBatchOp",
         "params": {"query":
                    "SELECT g, SUM(x) AS total FROM t GROUP BY g"}},
        {"id": "sel", "op": "SelectBatchOp",
         "params": {"__args__": ["total"]}},
    ],
    "edges": [{"src": "src", "dst": "sql"},
              {"src": "sql", "dst": "sel"}],
}


def test_run_experiment_directly():
    results = run_experiment(THREE_NODE_DAG)
    assert results["src"]["status"] == "ok"
    assert results["sql"]["status"] == "ok"
    tbl = results["sql"]["table"]
    assert [c["name"] for c in tbl["schema"]] == ["g", "total"]
    got = {row[0]: row[1] for row in tbl["rows"]}
    assert got == {"a": 11.0, "b": 4.0}
    assert results["sel"]["table"]["schema"][0]["name"] == "total"


def test_ops_catalog_api(server):
    cats = _req(server.port, "/api/ops")["categories"]
    all_ops = [o for v in cats.values() for o in v]
    assert "KMeansTrainBatchOp" in all_ops and "SqlQueryBatchOp" in all_ops
    info = _req(server.port, "/api/ops/SqlQueryBatchOp")
    assert any(p["name"] == "query" for p in info["params"])
    assert info["ports"]["outputs"] == ["DATA"]


def test_experiment_crud_and_run(server):
    created = _req(server.port, "/api/experiments", "POST", THREE_NODE_DAG)
    eid = created["id"]
    assert _req(server.port, f"/api/experiments/{eid}")["name"] == "demo"
    listed = _req(server.port, "/api/experiments")["experiments"]
    assert any(e["id"] == eid for e in listed)

    out = _req(server.port, f"/api/experiments/{eid}/run", "POST")
    assert out["results"]["sql"]["status"] == "ok"

    upd = _req(server.port, f"/api/experiments/{eid}", "PUT",
               {"name": "renamed"})
    assert upd["name"] == "renamed"
    assert _req(server.port, f"/api/experiments/{eid}", "DELETE")[
        "deleted"] == eid


def test_store_persists_across_instances(tmp_path):
    p = str(tmp_path / "exp.json")
    s1 = ExperimentStore(p)
    eid = s1.create({"name": "keep", "nodes": [], "edges": []})["id"]
    s2 = ExperimentStore(p)
    assert s2.get(eid)["name"] == "keep"


def test_run_surfaces_node_errors(server):
    bad = {"name": "bad", "nodes": [
        {"id": "a", "op": "SqlQueryBatchOp", "params": {"query": "x"}}],
        "edges": []}
    eid = _req(server.port, "/api/experiments", "POST", bad)["id"]
    out = _req(server.port, f"/api/experiments/{eid}/run", "POST")
    assert out["results"]["a"]["status"] == "error"


def test_index_page_serves(server):
    with urllib.request.urlopen(
            f"http://127.0.0.1:{server.port}/", timeout=10) as r:
        html = r.read().decode()
    assert "alink_tpu" in html and "api/ops" in html


def test_canvas_multiport_dag():
    """The canvas drag-to-connect payload: a 3-node train/predict DAG where
    the predict node takes TWO inputs wired by dstPort (model=0, data=1)."""
    exp = {
        "name": "canvas-3node",
        "nodes": [
            {"id": "n1", "op": "MemSourceBatchOp", "params": {
                "rows": [[0.1, 0.2], [0.2, 0.1], [5.1, 5.0],
                         [4.9, 5.2], [0.0, 0.1], [5.0, 4.8]],
                "schemaStr": "x double, y double"}},
            {"id": "n2", "op": "KMeansTrainBatchOp", "params": {
                "k": 2, "featureCols": ["x", "y"], "maxIter": 10}},
            {"id": "n3", "op": "KMeansPredictBatchOp", "params": {
                "predictionCol": "cluster"}},
        ],
        "edges": [
            {"src": "n1", "dst": "n2", "dstPort": 0},
            {"src": "n2", "dst": "n3", "dstPort": 0},
            {"src": "n1", "dst": "n3", "dstPort": 1},
        ],
    }
    results = run_experiment(exp)
    results.pop("__trace_id__", None)  # reserved key, not a node result
    assert all(r["status"] == "ok" for r in results.values()), results
    tbl = results["n3"]["table"]
    assert [c["name"] for c in tbl["schema"]] == ["x", "y", "cluster"]
    clusters = [row[2] for row in tbl["rows"]]
    assert clusters[0] == clusters[1] == clusters[4]
    assert clusters[2] == clusters[3] == clusters[5]
    assert clusters[0] != clusters[2]


@pytest.mark.observability
def test_metrics_endpoint_and_traces(server, monkeypatch):
    """GET /metrics serves Prometheus text exposition; a run returns its
    trace id and /api/traces/<id> reports the experiment's span tree."""
    import re

    monkeypatch.setenv("ALINK_TRACING", "on")
    eid = _req(server.port, "/api/experiments", "POST", THREE_NODE_DAG)["id"]
    out = _req(server.port, f"/api/experiments/{eid}/run", "POST")
    assert out["results"]["sql"]["status"] == "ok"
    tid = out["trace_id"]
    assert tid

    traces = _req(server.port, "/api/traces")["traces"]
    assert any(t["trace_id"] == tid for t in traces)
    rep = _req(server.port, f"/api/traces/{tid}")
    assert rep["root"]["name"] == "webui.run_experiment"
    assert all(s["trace_id"] == tid for s in rep["spans"])
    with pytest.raises(urllib.error.HTTPError) as ei:
        _req(server.port, "/api/traces/deadbeef00000000")
    assert ei.value.code == 404

    with urllib.request.urlopen(
            f"http://127.0.0.1:{server.port}/metrics", timeout=30) as r:
        ctype = r.headers.get("Content-Type", "")
        text = r.read().decode()
    assert ctype.startswith("text/plain")
    body = [l for l in text.splitlines() if l and not l.startswith("#")]
    label = r'[a-zA-Z_][a-zA-Z0-9_]*="[^"]*"'
    assert body and all(
        re.match(r'^alink_[a-zA-Z0-9_]+(\{%s(,%s)*\})? \S+$' % (label, label),
                 l)
        for l in body), body[:5]
    assert any("_bucket{le=" in l for l in body)   # >= one histogram
    # every span's wall is summed under its name
    assert any(l.startswith("alink_span_webui_run_experiment_seconds_count")
               for l in body)


def test_canvas_page_has_ports_and_forms(server):
    import urllib.request

    with urllib.request.urlopen(
            f"http://127.0.0.1:{server.port}/", timeout=10) as r:
        html = r.read().decode()
    # drag-to-connect surface + generated param forms + edge delete
    for marker in ("port out", "port in", "startConnect", "data-param",
                   "edge-hit", "dragstart"):
        assert marker in html, marker
