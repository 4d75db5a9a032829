"""Finds each piece of a cell by the names in ``BENCHMARK.json``.

- configuration: ``configs/<config>.json`` (the workload's ``config``)
- traffic mix: ``traffic/<traffic>.json``; its ``kind`` names the generator
  module ``traffic/<kind>.py``
- metric: ``metrics/<metric name>.py``, whose ``read(run)`` gives the number
- correctness limits: ``limits/<workload>.json``
- model family: ``families/<model_type>.py`` (the configuration's published
  ``model_type``): how the benchmark reads that family's configuration,
  which weights the system holds, the reference's layer, and the
  operation counts (``families/dense_decoder.py`` lists what a family module
  provides)

Adding a cell, a metric or a configuration adds files; no file here needs an
edit.  A configuration of a family the benchmark has adds
``configs/<name>.json``; one of a new ``model_type`` adds
``families/<model_type>.py`` beside it.  Each cell adds its traffic,
limits and ``BENCHMARK.json`` entries.
"""

from __future__ import annotations

import importlib.util
import json
import sys
from pathlib import Path
from types import ModuleType
from typing import List

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return load_json(ROOT / "BENCHMARK.json")


def workload(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def config(name: str) -> dict:
    return load_json(HERE / "configs" / f"{name}.json")


def traffic(name: str) -> dict:
    return load_json(HERE / "traffic" / f"{name}.json")


def limits(workload_name: str) -> dict:
    return load_json(HERE / "limits" / f"{workload_name}.json")


def _module(path: Path, name: str) -> ModuleType:
    if not path.is_file():
        raise FileNotFoundError(path)
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def generator(kind: str) -> ModuleType:
    d = str(HERE / "traffic")
    if d not in sys.path:
        sys.path.insert(0, d)
    return _module(HERE / "traffic" / f"{kind}.py", f"traffic_{kind}")


def family(model_type: str) -> ModuleType:
    """The family module of ``model_type``, loaded once per process (its
    spec types and jitted functions then stay the same objects)."""
    name = "family_" + model_type
    if name not in sys.modules:
        d = str(HERE / "families")
        if d not in sys.path:
            sys.path.insert(0, d)
        sys.modules[name] = _module(HERE / "families" / f"{model_type}.py", name)
    return sys.modules[name]


def reader(metric: str) -> ModuleType:
    return _module(HERE / "metrics" / f"{metric}.py",
                   "metric_" + metric.replace(".", "_"))


def _listed(metric: dict, cell: str) -> bool:
    return cell in metric.get("workloads", [cell])


def metrics_for(bench: dict, cell: str, trace: bool) -> List[dict]:
    """The cell's end-to-end metrics (``trace`` off) or per-layer metrics
    (``trace`` on): those that list the cell, or list no cells and move an
    end-to-end metric the cell reports."""
    e2e = [m for m in bench["end_to_end"] if _listed(m, cell)]
    if not trace:
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if cell in m.get("workloads", ()) or ("workloads" not in m
                                          and m["moves"] in names)]

