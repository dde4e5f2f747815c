"""BENCHMARK.json in the form the benchmark's contract sets, and every
name in it found by the harness."""

import json
import re
from pathlib import Path

import pytest

from bench import spec

B = spec.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
KEYS = {
    "configs": {"name", "source", "file", "reduced", "why"},
    "workloads": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}


def _line(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level():
    assert set(B) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert B["paths"] == ["bench"] and B["command"][1] == "bench/run.py"
    assert 1 <= B["run_seconds"] <= 51
    assert (spec.ROOT / "BENCHMARK.json").stat().st_size <= 64 * 1024


@pytest.mark.parametrize("section", sorted(KEYS))
def test_entries(section):
    names = [e["name"] for e in B[section]]
    assert len(names) == len(set(names))
    for e in B[section]:
        extra = {"workloads"} if section in ("end_to_end", "per_layer") \
            else set()
        assert KEYS[section] <= set(e) <= KEYS[section] | extra, e["name"]
        assert NAME.match(e["name"])
        for k in ("why", "layer", "source"):
            if k in e and section in ("configs", "workloads", "per_layer"):
                assert _line(e[k]), (e["name"], k)
        if "unit" in e:
            assert UNIT.match(e["unit"]) and e["better"] in ("lower",
                                                             "higher")


def test_cells_and_their_files():
    configs = {c["name"]: c for c in B["configs"]}
    used = {w["config"] for w in B["workloads"]}
    assert used == set(configs)
    assert len({(w["config"], w["traffic"]) for w in B["workloads"]}) == \
        len(B["workloads"])
    for w in B["workloads"]:
        assert w["chips"] in (1, 4)
        cell = spec.load_cell(w["name"])
        assert cell.traffic["kind"] in ("train", "serve")
        reports = [m["name"] for m in cell.end_to_end]
        assert "setup_s" in reports and len(reports) >= 2
        assert cell.per_layer
        for m in cell.per_layer:
            assert m["moves"] in reports
    for c in configs.values():
        conf = json.load(open(spec.ROOT / c["file"]))
        assert conf["name"] == c["name"] and conf["reduced"] == c["reduced"]
        assert Path(c["file"]).parts[0] == "bench"


def test_every_metric_has_a_reader_or_a_runner():
    for m in B["per_layer"]:
        assert callable(spec.metric_reader(m["name"]))
    for m in B["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
