"""BENCHMARK.json against the limits its contract sets, as far as a file can
show them: a PR that adds a cell, a configuration or a metric runs this
before the driver does."""

import json
import os
import re

from benchmarks.harness import cells

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}

with open(os.path.join(cells.REPO_DIR, "BENCHMARK.json")) as f:
    RAW = f.read()
BENCH = json.loads(RAW)


def one_line(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert len(RAW.encode()) <= 64 * 1024
    assert 1 <= BENCH["run_seconds"] <= 51
    assert len(BENCH["command"]) <= 32 and all(map(one_line,
                                                   BENCH["command"]))
    assert all(os.path.isdir(os.path.join(cells.REPO_DIR, p))
               for p in BENCH["paths"])


def test_configs_and_cells():
    names = [c["name"] for c in BENCH["configs"]]
    files = [c["file"] for c in BENCH["configs"]]
    assert len(set(names)) == len(names) and len(set(files)) == len(files)
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and one_line(c["source"]) \
            and one_line(c["why"])
        assert any(c["file"].startswith(p + "/") for p in BENCH["paths"])
        assert len(c["reduced"]) <= 16 and all(map(NAME.match,
                                                   c["reduced"]))
        with open(os.path.join(cells.REPO_DIR, c["file"])) as f:
            held = json.load(f)
        assert held["source"] == c["source"]
        assert held["reduced"] == c["reduced"]
    cell_names = [w["name"] for w in BENCH["workloads"]]
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(set(cell_names)) == len(cell_names)
    assert len(set(pairs)) == len(pairs)
    assert {w["config"] for w in BENCH["workloads"]} == set(names)
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and one_line(w["why"])
        assert cells.load_cell(w["name"]).config["chips"] == w["chips"]
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(cell_names) // 2)


def test_metrics():
    cell_names = {w["name"] for w in BENCH["workloads"]}
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    names = [m["name"] for m in metrics]
    assert len(set(names)) == len(names)
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e and len(e2e) >= 2
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["moves"] in e2e and one_line(m["layer"])
        # its reader is a file of its own
        assert cells.metric_spec(m["name"])["source"]
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
        assert set(m.get("workloads", cell_names)) <= cell_names


def test_files_under_paths_are_named_from_a_names_characters():
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for p in BENCH["paths"]:
        for root, dirs, files in os.walk(os.path.join(cells.REPO_DIR, p)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for name in files:
                rel = os.path.relpath(os.path.join(root, name),
                                      cells.REPO_DIR)
                assert ok.match(rel), rel
