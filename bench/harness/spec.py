"""What one run needs from ``BENCHMARK.json`` and the data files under bench/.

Everything is found by name: a cell names its configuration and traffic
mix; the configuration names its file, and that file names its family, the
module ``bench/families/<family>.py`` that knows its layers
(``model.py``), and the mesh it is served on, if any; the traffic mix is
``bench/traffic/<traffic>.json``; the limits of the output check are
``bench/limits/<cell>.json``; a metric is read by ``bench/metrics/<name>.py``
(or, for a metric split by a suffix such as ``step_ms.arrivals``, by the
reader of the part before the first dot).  Adding a cell, a configuration,
a family or a metric therefore adds files and entries and edits none.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
import os
from types import ModuleType
from typing import Callable, Dict, List


@dataclasses.dataclass(frozen=True)
class Metric:
    name: str
    unit: str


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    config: str
    traffic: str
    chips: int
    config_data: dict
    family: ModuleType             # bench/families/<family>.py
    traffic_data: dict
    limits: dict
    end_to_end: tuple              # Metric, in BENCHMARK.json order
    per_layer: tuple


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _reports(metric: dict, cell: str) -> bool:
    ws = metric.get("workloads")
    return ws is None or cell in ws


def _module(path: str, name: str) -> ModuleType:
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def family(root: str, name: str) -> ModuleType:
    """The module ``bench/families/<name>.py``."""
    path = os.path.join(root, "bench", "families", name + ".py")
    if not os.path.exists(path):
        raise FileNotFoundError(f"no family {name!r}: looked for {path}")
    return _module(path, "bench_family_" + name.replace(".", "_")
                   .replace("-", "_"))


def mesh_size(config: dict) -> int:
    """Chips the configuration's ``mesh`` spans; 1 without one."""
    return math.prod(int(v) for v in (config.get("mesh") or {}).values())


def load_cell(root: str, name: str) -> Cell:
    """Resolve cell ``name`` of ``<root>/BENCHMARK.json``; raises KeyError
    for an unknown cell or a configuration that names no family,
    FileNotFoundError for a missing data file or family module, and
    ValueError for a cell whose chips are not its mesh's."""
    bench = _load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have: {sorted(cells)})")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    c = configs[w["config"]]
    e2e = tuple(Metric(m["name"], m["unit"]) for m in bench["end_to_end"]
                if _reports(m, name))
    moved = {m.name for m in e2e}
    # a per-layer metric is read in the cells that report what it moves
    per_layer = tuple(Metric(m["name"], m["unit"]) for m in bench["per_layer"]
                      if m["moves"] in moved and _reports(m, name))
    bench_dir = os.path.join(root, "bench")
    config = _load_json(os.path.join(root, c["file"]))
    if "family" not in config:
        raise KeyError(f"{c['file']} names no family (a module under "
                       f"{os.path.join(bench_dir, 'families')})")
    if mesh_size(config) != int(w["chips"]):
        raise ValueError(f"cell {name!r} asks for {w['chips']} chips, but "
                         f"{c['file']} serves on a mesh of "
                         f"{mesh_size(config)}: {config.get('mesh')}")
    return Cell(
        name=name, config=w["config"], traffic=w["traffic"],
        chips=int(w["chips"]), config_data=config,
        family=family(root, config["family"]),
        traffic_data=_load_json(os.path.join(bench_dir, "traffic",
                                             w["traffic"] + ".json")),
        limits=_load_json(os.path.join(bench_dir, "limits", name + ".json")),
        end_to_end=e2e, per_layer=per_layer)


def reader(root: str, metric: str) -> Callable:
    """The ``read(run)`` function of ``bench/metrics/<metric>.py``, falling
    back to the reader of the name's part before the first dot."""
    d = os.path.join(root, "bench", "metrics")
    for stem in (metric, metric.split(".")[0]):
        path = os.path.join(d, stem + ".py")
        if os.path.exists(path):
            return _module(path, "bench_metric_" + stem.replace(".", "_")
                           .replace("-", "_")).read
    raise FileNotFoundError(f"no reader for metric {metric!r} under {d}")


def metrics_of(root: str, metrics: List[Metric], run) -> Dict[str, dict]:
    """Read each metric; a reader that finds nothing returns None and the
    metric is left out of the result."""
    out = {}
    for m in metrics:
        v = reader(root, m.name)(run)
        if v is not None:
            out[m.name] = {"value": float(v), "unit": m.unit}
    return out
