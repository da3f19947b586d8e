"""Find a cell's files by the names in ``BENCHMARK.json``.

* configuration ``<c>``: the ``file`` its entry names (a JSON object of
  sizes as run) and its plain reference ``chipbench/reference/<c>.py``;
* traffic ``<t>``: ``chipbench/traffic/<t>.json``;
* metric ``<m>``: ``chipbench/metrics/<m>.py``, whose ``read(r)`` takes
  the run's readings and returns a number, or None where it found
  nothing to read.

No list of cells, mixes or metrics lives in code: a later change adds
one by adding its files and its entries.
"""
from __future__ import annotations

import importlib.util
import json
import os
import sys
from types import ModuleType
from typing import Dict, List

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def load_module(path: str, name: str) -> ModuleType:
    if not os.path.exists(path):
        raise FileNotFoundError(path)
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return load_json(os.path.join(ROOT, "BENCHMARK.json"))


def metrics_for(bench: dict, cell: str) -> Dict[str, List[dict]]:
    """The end-to-end and per-layer metric entries this cell reports."""
    e2e = [m for m in bench["end_to_end"]
           if cell in m.get("workloads", [cell])]
    names = {m["name"] for m in e2e}
    per = [m for m in bench["per_layer"]
           if cell in m.get("workloads", [cell] if m["moves"] in names
                            else [])]
    return {"end_to_end": e2e, "per_layer": per}


def reader(name: str) -> ModuleType:
    return load_module(os.path.join(BENCH_DIR, "metrics", name + ".py"),
                       "chipbench_metric_" + name.replace(".", "_"))


def resolve(bench: dict, name: str) -> dict:
    """Everything a run of cell ``name`` needs, loaded from its files."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    conf_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    ms = metrics_for(bench, name)
    return {
        "name": name,
        "chips": w["chips"],
        "conf": load_json(os.path.join(ROOT, conf_entry["file"])),
        "mix": load_json(os.path.join(BENCH_DIR, "traffic",
                                      w["traffic"] + ".json")),
        "ref": load_module(os.path.join(BENCH_DIR, "reference",
                                        w["config"] + ".py"),
                           "chipbench_reference_" + w["config"]),
        "end_to_end": ms["end_to_end"],
        "per_layer": ms["per_layer"],
        "readers": {m["name"]: reader(m["name"])
                    for m in ms["end_to_end"] + ms["per_layer"]},
    }
