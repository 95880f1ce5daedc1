"""What the tests of a fixture family share: the manifest as its
`model_config` PR would leave it."""
import json
import os

import harness.manifest as mf


def as_a_model_config_pr(monkeypatch, fixture_dir: str, configs, like: str, but=()):
    """BENCHMARK.json and the benchmark's directories as a `model_config` PR
    for the family under `fixture_dir` would leave them: a `configs` entry
    for each of `configs`, its `<config>.eval-batch` cell, the cell's name on
    every list that names the cell `like` except the metrics `but`, and
    `manifest._path` looking under `fixture_dir` before the benchmark's own
    directories. Nothing under harness/ is touched. Returns that manifest."""
    bench = mf.benchmark_json()
    listed = {m["name"] for m in bench["end_to_end"] + bench["per_layer"]
              if like in m.get("workloads", ())} - set(but)
    for name in configs:
        bench["configs"].append({
            "name": name, "source": "fixture", "reduced": [], "why": "fixture",
            "file": os.path.relpath(os.path.join(fixture_dir, "configs", name + ".json"), mf.ROOT)})
        bench["workloads"].append({"name": name + ".eval-batch", "config": name,
                                   "traffic": "eval-batch", "chips": 1, "why": "fixture"})
        for m in bench["end_to_end"] + bench["per_layer"]:
            if m["name"] in listed:
                m["workloads"].append(name + ".eval-batch")
    monkeypatch.setattr(mf, "benchmark_json", lambda: json.loads(json.dumps(bench)))
    own = mf._path

    def path(kind, filename):
        fixture = os.path.join(fixture_dir, kind, filename)
        return fixture if os.path.exists(fixture) else own(kind, filename)

    monkeypatch.setattr(mf, "_path", path)
    return bench

