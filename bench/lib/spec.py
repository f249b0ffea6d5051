"""Finds everything by the names in ``BENCHMARK.json``: a configuration's
file and its reference module, a traffic mix's file, a cell's limits and
each metric's reader. Adding any of them is adding files; nothing here
lists them."""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from typing import Callable, Dict, List, Optional

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    config: dict           # the configuration file
    traffic: dict          # the traffic file
    limits: dict           # bench/limits/<cell>.json
    chips: int
    reduced: List[str]     # the configuration's keys changed from its source
    end_to_end: List[dict]
    per_layer: List[dict]

    def metrics(self, trace: bool) -> List[dict]:
        """The metrics this cell reports in a run with/without trace."""
        group = self.per_layer if trace else self.end_to_end
        return [m for m in group
                if self.name in m.get("workloads", [self.name])]


def load_cell(workload: str) -> Cell:
    bench = _json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; have {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg = _json(os.path.join(ROOT, configs[w["config"]]["file"]))
    traffic = _json(os.path.join(BENCH, "traffic", w["traffic"] + ".json"))
    limits = _json(os.path.join(BENCH, "limits", workload + ".json"))
    return Cell(workload, cfg, traffic, limits, int(w["chips"]),
                list(configs[w["config"]]["reduced"]), bench["end_to_end"],
                bench["per_layer"])


def reference(cfg: dict):
    """The plain reference module a configuration names."""
    return _module(os.path.join(BENCH, "reference", cfg["reference"] + ".py"),
                   "bench_reference_" + cfg["reference"])


def reader(metric: str) -> Callable:
    """``read(run)`` of ``bench/metrics/<metric>.py``."""
    return _module(os.path.join(BENCH, "metrics", metric + ".py"),
                   "bench_metric_" + metric.replace(".", "_")).read


def cache_dir() -> str:
    """Where the benchmark keeps what it derives once per checkout (the
    PCA calibration): a fixed directory inside the checkout."""
    return os.path.join(BENCH, ".cache")


def read_metrics(cell: Cell, run, trace: bool) -> Dict[str, dict]:
    """Every metric of the cell that its reader finds something to read."""
    out: Dict[str, dict] = {}
    for m in cell.metrics(trace):
        got: Optional[object] = reader(m["name"])(run)
        if got is None:
            continue
        entry = dict(got) if isinstance(got, dict) else {"value": got}
        entry["value"] = float(entry["value"])
        out[m["name"]] = {"value": entry.pop("value"), "unit": m["unit"],
                          **entry}
    return out
