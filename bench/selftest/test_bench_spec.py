"""``BENCHMARK.json`` against the format the driver holds it to, and
every name in it against the files that the harness finds by it."""
import json
import math
import os
import re

import pytest

import tiny

SPEC = json.load(open(os.path.join(tiny.ROOT, "BENCHMARK.json")))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter",
           "host_clock"}


def _line(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level_keys_and_sizes():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert len(json.dumps(SPEC)) < 64 * 1024
    assert 1 <= len(SPEC["paths"]) <= 16
    assert len(SPEC["command"]) <= 32
    assert all(_line(w) for w in SPEC["command"])
    assert isinstance(SPEC["run_seconds"], int)
    assert 1 <= SPEC["run_seconds"] <= 51
    # a full check of 24 cells fits the driver's 43,200 s
    assert (2 + 14 * 24) * (SPEC["run_seconds"] + 60) + 24 * 180 \
        + 1200 <= 43200


def test_command_stays_inside_paths():
    for word in SPEC["command"][1:]:
        if "/" in word:
            assert not word.startswith("/") and ".." not in word
            assert any(word.startswith(p + "/") for p in SPEC["paths"])
            assert os.path.exists(os.path.join(tiny.ROOT, word))


def test_configs():
    used = {w["config"] for w in SPEC["workloads"]}
    names = [c["name"] for c in SPEC["configs"]]
    assert len(set(names)) == len(names) == len(used) and set(names) == used
    files = [c["file"] for c in SPEC["configs"]]
    assert len(set(files)) == len(files)
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and _line(c["source"])
        assert _line(c["why"]) and len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])
        assert any(c["file"].startswith(p + "/") for p in SPEC["paths"])
        cfg = json.load(open(os.path.join(tiny.ROOT, c["file"])))
        assert sorted(cfg["reduced_from_source"]) == sorted(c["reduced"])
        for k in c["reduced"]:
            assert not k.endswith(("_dim", "_rank")) and k != "dim"


def test_workloads():
    cells = SPEC["workloads"]
    names = [w["name"] for w in cells]
    assert 1 <= len(cells) <= 24 and len(set(names)) == len(names)
    assert len({(w["config"], w["traffic"]) for w in cells}) == len(cells)
    four = [w for w in cells if w["chips"] == 4]
    assert len(four) <= max(1, len(cells) // 2)
    for w in cells:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and _line(w["why"])
        assert os.path.exists(os.path.join(
            tiny.BENCH, "traffic", w["traffic"] + ".json"))


@pytest.mark.parametrize("kind", ["end_to_end", "per_layer"])
def test_metrics(kind):
    cells = {w["name"] for w in SPEC["workloads"]}
    all_names = [m["name"] for k in ("end_to_end", "per_layer")
                 for m in SPEC[k]]
    assert len(set(all_names)) == len(all_names)
    keys = {"name", "unit", "better", "source", "workloads"}
    keys |= {"bound"} if kind == "end_to_end" else {"layer", "moves"}
    for m in SPEC[kind]:
        assert set(m) - {"workloads"} == keys - {"workloads"}, m["name"]
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
        assert set(m.get("workloads", cells)) <= cells
        assert os.path.exists(os.path.join(
            tiny.BENCH, "metrics", m["name"] + ".py"))
        if kind == "end_to_end":
            assert m["source"] in ("host_clock", "device_trace")
            assert 0.01 <= m["bound"] <= 0.25
        else:
            assert _line(m["layer"])
            moves = {e["name"]: e for e in SPEC["end_to_end"]}
            assert m["moves"] in moves
            # every cell it lists reports the metric it moves
            assert set(m.get("workloads", cells)) <= set(
                moves[m["moves"]].get("workloads", cells))
            if "roofline" in m["name"] or "mfu" in m["name"]:
                assert m["unit"] == "%"


def test_every_cell_reports_enough():
    names = {e["name"] for e in SPEC["end_to_end"]}
    assert "setup_s" in names
    for w in SPEC["workloads"]:
        mine = [m for m in SPEC["end_to_end"]
                if w["name"] in m.get("workloads", [w["name"]])]
        assert "setup_s" in {m["name"] for m in mine} and len(mine) >= 2
        assert any(w["name"] in m.get("workloads", [w["name"]])
                   for m in SPEC["per_layer"])


def test_layer_names_are_consistent():
    layers = {m["layer"] for m in SPEC["per_layer"]}
    perf = open(os.path.join(tiny.ROOT, "PERF.md")).read()
    for layer in layers:
        assert f"`{layer}`" in perf, layer
