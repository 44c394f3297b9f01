"""Find a cell's pieces by name.

Everything that belongs to one configuration, traffic mix or metric sits
in files of its own; this module resolves them from ``BENCHMARK.json``:

- a cell is an entry of ``workloads``, named ``<config>.<traffic>``;
- its configuration is the JSON file that the ``configs`` entry names;
- its traffic mix is ``bench_suite/traffic/<traffic>.json``, whose
  ``loop`` names its arrival process, ``bench_suite/loops/<loop>.py``;
- its system is ``bench_suite/systems/<system>.py``, named by the
  configuration's ``system`` key;
- a metric named ``m`` is read by ``bench_suite/metrics/<m>.py``, whose
  ``read(run)`` returns a number or None.

Adding a cell, a mix, an arrival process or a metric is adding files and entries; no file
here names one.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from typing import Callable, Dict, List, Optional

SUITE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(SUITE)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict          # the configuration file's contents
    traffic: dict         # the traffic file's contents
    end_to_end: List[dict]   # BENCHMARK.json entries this cell reports
    per_layer: List[dict]
    root: str = ROOT         # the checkout the pieces were found in


def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_config(name: str, bench: Optional[dict] = None,
                root: str = ROOT) -> dict:
    """The configuration file of the ``configs`` entry ``name``."""
    bench = bench if bench is not None else load_benchmark(root)
    entry = next(c for c in bench["configs"] if c["name"] == name)
    with open(os.path.join(root, entry["file"])) as f:
        return json.load(f)


def load_traffic(name: str, root: str = ROOT) -> dict:
    with open(os.path.join(root, "bench_suite", "traffic",
                           name + ".json")) as f:
        return json.load(f)


def find_cell(name: str, bench: Optional[dict] = None,
              root: str = ROOT) -> Cell:
    """The cell ``name`` with its configuration, traffic and metrics;
    KeyError when BENCHMARK.json has no such cell."""
    bench = bench if bench is not None else load_benchmark(root)
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    config = load_config(entry["config"], bench, root)
    traffic = load_traffic(entry["traffic"], root)
    e2e = [m for m in bench["end_to_end"] if _reports(m, name)]
    moved = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if m["moves"] in moved and _reports(m, name)]
    return Cell(name=name, chips=int(entry["chips"]), config=config,
                traffic=traffic, end_to_end=e2e, per_layer=per_layer,
                root=root)


def _load_file(path: str, modname: str):
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str, root: str = ROOT) -> Callable:
    """``read(run)`` of the metric ``name``."""
    path = os.path.join(root, "bench_suite", "metrics", name + ".py")
    return _load_file(path, "bench_suite_metric_" + name.replace(".", "_")
                      ).read


def loop_module(loop: str, root: str = ROOT):
    """The module whose ``run(session)`` offers a mix's requests."""
    path = os.path.join(root, "bench_suite", "loops", loop + ".py")
    return _load_file(path, "bench_suite_loop_" + loop)


def system_module(system: str, root: str = ROOT):
    """The module that builds and drives the system under test."""
    path = os.path.join(root, "bench_suite", "systems", system + ".py")
    return _load_file(path, "bench_suite_system_" + system)


def read_metrics(entries: List[dict], run, root: str = ROOT
                 ) -> Dict[str, dict]:
    """{name: {"value", "unit"}} of each metric whose reader found
    something to read; a reader that returns None is left out."""
    out = {}
    for m in entries:
        value = metric_reader(m["name"], root)(run)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out
