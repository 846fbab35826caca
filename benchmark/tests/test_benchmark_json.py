"""BENCHMARK.json against the files it names: every cell, configuration
and per-layer metric is found by its name, as the README promises."""

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
REPO = os.path.dirname(ROOT)


def load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def test_every_entry_has_its_file():
    bench = load(REPO, "BENCHMARK.json")
    configs = {c["name"]: c for c in bench["configs"]}
    for c in bench["configs"]:
        body = load(REPO, c["file"])
        assert c["file"] == f"benchmark/configs/{c['name']}.json"
        assert body["source"] == c["source"]
        assert sorted(body["reduced"]) == sorted(c["reduced"])
        assert all(k in body for k in c["reduced"])
    e2e = {m["name"] for m in bench["end_to_end"]}
    cells = set()
    for w in bench["workloads"]:
        body = load(ROOT, "workloads", w["name"] + ".json")
        assert w["name"] == f"{w['config']}.{w['traffic']}"
        assert body["config"] == w["config"] and w["config"] in configs
        assert body["chips"] == w["chips"] and body["why"] == w["why"]
        cells.add(w["name"])
    for m in bench["per_layer"]:
        spec = load(ROOT, "metrics", m["name"] + ".json")
        reader = spec.get("reader", m["name"]) + ".py"
        assert os.path.isfile(os.path.join(ROOT, "metrics", reader))
        assert spec["layer"] == m["layer"] and spec["unit"] == m["unit"]
        assert spec["moves"] == m["moves"] and m["moves"] in e2e
        assert set(m["workloads"]) <= cells
    assert "setup_s" in e2e
