"""`BENCHMARK.json` and the files it names, resolved by name.

A cell names a configuration and a traffic mix; each lives in a file of
its own (`configs/<file>`, `traffic/<traffic>.json`), and every metric has
a reader `metrics/<name>.py`.  The traffic's `kind` names its runner
(`bench/runners/<kind>.py`), the configuration's `reference` its plain
reference (`references/<reference>.py`), and `limits/<cell>.json` holds
the cell's correctness limits.  Adding a cell is adding files and entries.
"""
from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import os
from typing import Dict, List, Optional

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict                 # the configuration file's contents
    traffic: dict                # the traffic file's contents
    traffic_name: str
    limits: dict                 # number name -> limit
    end_to_end: List[dict]       # metric entries this cell reports
    per_layer: List[dict]
    bench_dir: str

    @property
    def kind(self) -> str:
        return self.traffic["kind"]

    def reference(self):
        return importlib.import_module(
            f"references.{self.config['reference']}")

    def runner(self):
        return importlib.import_module(f"bench.runners.{self.kind}")


def reports(metric: dict, cell: str, e2e_names: List[str]) -> bool:
    """Whether `cell` reports `metric`: listed in its `workloads`, or, for a
    per-layer metric without the key, whenever the cell reports the
    end-to-end metric it moves."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    if "moves" in metric:
        return metric["moves"] in e2e_names
    return True


def resolve(name: str, bench: Optional[dict] = None,
            root: str = ROOT) -> Cell:
    bench = bench if bench is not None else load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                       f"{sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg_entry = configs[w["config"]]
    bench_dir = os.path.join(root, bench["paths"][0])
    config = _json(os.path.join(root, cfg_entry["file"]))
    traffic = _json(os.path.join(bench_dir, "traffic", f"{w['traffic']}.json"))
    limits_path = os.path.join(bench_dir, "limits", f"{name}.json")
    limits = _json(limits_path) if os.path.exists(limits_path) else {}
    e2e = [m for m in bench["end_to_end"] if reports(m, name, [])]
    e2e_names = [m["name"] for m in e2e]
    per_layer = [m for m in bench["per_layer"]
                 if reports(m, name, e2e_names)]
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic, traffic_name=w["traffic"],
                limits=limits, end_to_end=e2e, per_layer=per_layer,
                bench_dir=bench_dir)


def metric_module(name: str, bench_dir: str = BENCH_DIR):
    """`metrics/<name>.py`, loaded by its path (names hold dots)."""
    path = os.path.join(bench_dir, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        "perfbench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str, bench_dir: str = BENCH_DIR):
    """The `read(run)` function of `metrics/<name>.py`."""
    return metric_module(name, bench_dir).read


def read_metrics(entries: List[dict], run, bench_dir: str = BENCH_DIR
                 ) -> Dict[str, dict]:
    """{name: {'value', 'unit'}} for every entry whose reader finds
    something to read; a reader that returns None leaves its metric out."""
    out = {}
    for m in entries:
        v = metric_reader(m["name"], bench_dir)(run)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out
