"""Discovery: every configuration, traffic mix, driver and metric is a file
of its own under ``bench/``, found by the name ``BENCHMARK.json`` gives it.

    bench/configs/<config>.json    a deployment
    bench/traffic/<traffic>.json   a traffic mix; its ``driver`` names
    bench/drivers/<driver>.py      the entry of the system it drives
    bench/metrics/<metric>.py      one metric: ``read(run) -> float|None``

A later cell adds files and a ``workloads`` entry; nothing here changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from typing import Dict, List, Optional

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)


class UnknownName(LookupError):
    """A name that no file under ``bench/`` answers to."""


def _json(kind: str, name: str, bench: str) -> dict:
    path = os.path.join(bench, kind, f"{name}.json")
    if not os.path.isfile(path):
        raise UnknownName(f"no {kind} file for {name!r} ({path})")
    with open(path) as f:
        return json.load(f)


def config(name: str, bench: str = BENCH) -> dict:
    return _json("configs", name, bench)


def traffic(name: str, bench: str = BENCH) -> dict:
    return _json("traffic", name, bench)


def _module(kind: str, name: str, bench: str):
    path = os.path.join(bench, kind, f"{name}.py")
    if not os.path.isfile(path):
        raise UnknownName(f"no {kind} file for {name!r} ({path})")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def driver(name: str, bench: str = BENCH):
    return _module("drivers", name, bench)


def metric(name: str, bench: str = BENCH):
    return _module("metrics", name, bench)


def benchmark(path: Optional[str] = None) -> dict:
    path = path or os.path.join(REPO, "BENCHMARK.json")
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]

    def metrics(self, trace: bool) -> List[dict]:
        return self.per_layer if trace else self.end_to_end


def of_driver(driver: str, spec: Optional[dict] = None,
              bench: str = BENCH) -> List[str]:
    """The names of the cells whose traffic ``driver`` runs, in the order
    of ``BENCHMARK.json``."""
    spec = spec if spec is not None else benchmark()
    return [w["name"] for w in spec["workloads"]
            if traffic(w["traffic"], bench)["driver"] == driver]


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def cell(name: str, bench_json: Optional[dict] = None,
         bench: str = BENCH) -> Cell:
    """The cell ``name`` of ``BENCHMARK.json`` with its files loaded and
    the metrics it reports."""
    spec = bench_json if bench_json is not None else benchmark()
    cells: Dict[str, dict] = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise UnknownName(f"no workload {name!r} in BENCHMARK.json "
                          f"(known: {', '.join(cells)})")
    w = cells[name]
    e2e = [m for m in spec["end_to_end"] if _reports(m, name)]
    moved = {m["name"] for m in e2e}
    layer = [m for m in spec["per_layer"]
             if (name in m["workloads"] if "workloads" in m
                 else m["moves"] in moved)]
    return Cell(name=name, chips=int(w["chips"]),
                config=config(w["config"], bench),
                traffic=traffic(w["traffic"], bench),
                end_to_end=e2e, per_layer=layer)
