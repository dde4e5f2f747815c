"""Finds a cell's parts by the names in ``BENCHMARK.json``.

A cell (an entry of ``workloads``) names a configuration and a traffic
mix.  The configuration's file is the one its ``configs`` entry names;
the traffic mix is ``bench/traffic/<traffic>.json``; the limits of the
comparison that decides ``correct`` are ``bench/limits/<cell>.json``;
each per-layer metric is read by ``bench/metrics/<metric>.py``.  Adding
a cell, a mix or a metric adds files and entries and edits none.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from typing import Callable, Dict, List, Optional

ROOT = Path(__file__).resolve().parent.parent
BENCH = ROOT / "bench"


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict             # the configuration file, as run
    traffic: dict            # the traffic mix's parameters
    limits: dict             # {number: limit} of the correctness check
    end_to_end: List[dict]   # BENCHMARK.json metrics this cell reports
    per_layer: List[dict]


def _for_cell(metrics: List[dict], cell: str) -> List[dict]:
    return [m for m in metrics if cell in m.get("workloads", [cell])]


def load_benchmark(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; there are "
                       f"{sorted(cells)}")
    w = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    with open(root / conf["file"]) as f:
        config = json.load(f)
    with open(root / "bench" / "traffic" / f"{w['traffic']}.json") as f:
        traffic = json.load(f)
    with open(root / "bench" / "limits" / f"{name}.json") as f:
        limits = json.load(f)["limits"]
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic, limits=limits,
                end_to_end=_for_cell(bench["end_to_end"], name),
                per_layer=_for_cell(bench["per_layer"], name))


def cell_from_files(config: str, traffic: str, root: Path = ROOT) -> Cell:
    """A cell that BENCHMARK.json does not hold (a study of one left
    out): configuration and traffic by their file names, no limits and
    no metrics."""
    with open(root / "bench" / "configs" / f"{config}.json") as f:
        conf = json.load(f)
    with open(root / "bench" / "traffic" / f"{traffic}.json") as f:
        mix = json.load(f)
    return Cell(name=f"{config}.{traffic}", chips=1, config=conf,
                traffic=mix, limits={},
                end_to_end=[], per_layer=[])


def metric_reader(name: str, root: Path = ROOT) -> Callable[[dict], Optional[float]]:
    """``bench/metrics/<name>.py``'s ``read(record)``: a number, or None
    when the run gave it nothing to read."""
    path = root / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def read_json(name: str, root: Path = ROOT) -> Dict:
    with open(root / "bench" / name) as f:
        return json.load(f)


def peaks_for(device_kind: str) -> dict:
    """The peaks of ``peaks.json`` for a device; an unknown device is an
    error, never a default."""
    table = read_json("peaks.json")["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json (it has {sorted(table)})")
    return table[device_kind]
