"""A new configuration, traffic mix and per-layer metric take only new
files and new entries in ``BENCHMARK.json``: the harness finds each by
its name, and no file it already has is edited."""
import hashlib
import json
import os

import harness
import tiny


def _digests(root):
    out = {}
    for dirpath, _, files in os.walk(os.path.join(root, "bench")):
        for f in files:
            if "__pycache__" in dirpath:
                continue
            path = os.path.join(dirpath, f)
            out[path] = hashlib.sha256(open(path, "rb").read()).hexdigest()
    return out


def test_new_config_traffic_and_metric_are_found(tmp_path):
    root = tiny.make_root(str(tmp_path))
    before = _digests(root)
    bench = os.path.join(root, "bench")

    cfg = json.load(open(os.path.join(bench, "configs",
                                      "sift1m-f32-250k.json")))
    cfg["n"] = 1200
    json.dump(cfg, open(os.path.join(bench, "configs", "new-cfg.json"),
                        "w"))
    json.dump({"batch": 8, "k": 10, "query_pool": 32, "jitter": 0.2},
              open(os.path.join(bench, "traffic", "new-mix.json"), "w"))
    with open(os.path.join(bench, "metrics", "batches.new.py"), "w") as f:
        f.write("def read(run):\n    return len(run.batches)\n")

    spec = json.load(open(os.path.join(root, "BENCHMARK.json")))
    spec["configs"].append({
        "name": "new-cfg", "source": "a test", "why": "a test",
        "file": "bench/configs/new-cfg.json",
        "reduced": spec["configs"][0]["reduced"]})
    spec["workloads"].append({
        "name": "new-cell", "config": "new-cfg", "traffic": "new-mix",
        "chips": 1, "why": "a test"})
    for m in spec["end_to_end"]:
        if m["name"] == "qps":
            m["workloads"].append("new-cell")
    spec["per_layer"].append({
        "name": "batches.new", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "batcher", "moves": "qps",
        "workloads": ["new-cell"]})
    json.dump(spec, open(os.path.join(root, "BENCHMARK.json"), "w"))

    traced = tiny.run(root, "new-cell", trace=True)
    plain = tiny.run(root, "new-cell", trace=False)
    assert traced["correct"] and plain["correct"]
    assert traced["metrics"]["batches.new"]["value"] >= 1
    assert "qps" in plain["metrics"]
    assert traced["attempted"] % 8 == 0
    spec = harness.load_spec(root)
    tiny.check_line(plain, spec, "new-cell", False)
    tiny.check_line(traced, spec, "new-cell", True)
    after = _digests(root)
    assert {p: h for p, h in after.items() if p in before} == before
