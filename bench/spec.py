"""Finds a cell's parts by the names in ``BENCHMARK.json``.

A cell (an entry of ``workloads``) names a configuration and a traffic
mix.  The configuration's file is the one its ``configs`` entry names;
the traffic mix is ``bench/traffic/<traffic>.json``; the limits of the
comparison that decides ``correct`` are ``bench/limits/<cell>.json``;
each per-layer metric is read by ``bench/metrics/<metric>.py``; the
architecture is ``bench/archs/<model_type>.py``, by the configuration's
``model_type``; kernel families are ``bench/kernel_names.json``'s and one
more for each ``bench/kernels/<family>.json``.  Adding a cell, a mix, a
metric, an architecture or a kernel family adds files and entries and
edits none.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import re
from pathlib import Path
from types import ModuleType
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
    root: Path = ROOT        # the checkout whose files the cell was read from


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
                per_layer=_for_cell(bench["per_layer"], name), root=root)


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
                end_to_end=[], per_layer=[], root=root)


def metric_reader(name: str, root: Path = ROOT) -> Callable[[dict], Optional[float]]:
    """``bench/metrics/<name>.py``'s ``read(record)``: a number, or None
    when the run gave it nothing to read."""
    path = root / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


ARCH_NAME = re.compile(r"^[A-Za-z0-9][A-Za-z0-9_.-]{0,63}$")
_ARCHS: Dict[Path, ModuleType] = {}


def arch(model_type: str, root: Path = ROOT) -> ModuleType:
    """``bench/archs/<model_type>.py``, the architecture a configuration
    names by its ``model_type`` (what it provides: ``bench/archs``).  An
    unknown type is an error that names the modules there are."""
    folder = root / "bench" / "archs"
    path = (folder / f"{model_type}.py").resolve()
    if not (ARCH_NAME.match(model_type) and path.is_file()):
        have = sorted(p.stem for p in folder.glob("*.py")
                      if ARCH_NAME.match(p.stem))
        raise ValueError(f"no architecture for model_type {model_type!r}: "
                         f"{folder} has {have}")
    if path not in _ARCHS:
        spec = importlib.util.spec_from_file_location(
            "bench_arch_" + re.sub(r"\W", "_", model_type), path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _ARCHS[path] = mod
    return _ARCHS[path]


def read_json(name: str, root: Path = ROOT) -> Dict:
    with open(root / "bench" / name) as f:
        return json.load(f)


def kernel_families(root: Path = ROOT) -> Dict[str, List[str]]:
    """Kernel families by name, each a list of patterns searched in a
    device operation's name: ``kernel_names.json``'s, then one for each
    ``bench/kernels/<family>.json`` (its ``patterns``).  An operation
    belongs to the first family that matches it.  A family named twice
    is an error."""
    fams = dict(read_json("kernel_names.json", root)["families"])
    for path in sorted((root / "bench" / "kernels").glob("*.json")):
        if path.stem in fams:
            raise ValueError(f"kernel family {path.stem!r} is named twice: "
                             f"by {path} and by bench/kernel_names.json")
        with open(path) as f:
            patterns = json.load(f)["patterns"]
        if not (isinstance(patterns, list) and patterns
                and all(isinstance(p, str) for p in patterns)):
            raise ValueError(f"{path}: patterns must be a list of strings")
        for p in patterns:
            re.compile(p)
        fams[path.stem] = patterns
    return fams


def peaks_for(device_kind: str) -> dict:
    """The peaks of ``peaks.json`` for a device; an unknown device is an
    error, never a default."""
    table = read_json("peaks.json")["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json (it has {sorted(table)})")
    return table[device_kind]
