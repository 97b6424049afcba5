"""What a cell is, read from files: ``BENCHMARK.json`` at the checkout's
root names the cells, configurations and metrics; each configuration,
traffic mix, metric reader and correctness limit sits in a file of its own
under ``bench/``, found by its name.  Adding one is adding files."""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os

BENCH = os.path.dirname(os.path.abspath(__file__))


@dataclasses.dataclass(frozen=True)
class Cell:
    """One workload of ``BENCHMARK.json`` with everything it names."""

    name: str
    chips: int
    config: dict      # bench/configs/<config>.json
    traffic: dict     # bench/traffic/<traffic>.json
    limits: dict      # bench/limits/<workload>.json
    end_to_end: tuple  # metric entries of BENCHMARK.json this cell reports
    per_layer: tuple


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(root: str, workload: str, bench: str = BENCH) -> Cell:
    """The cell ``workload`` of ``root/BENCHMARK.json``; raises
    ``KeyError`` for a workload the file does not name."""
    spec = _load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[workload]
    e2e = tuple(m for m in spec["end_to_end"]
                if "workloads" not in m or workload in m["workloads"])
    layer = tuple(m for m in spec["per_layer"]
                  if workload in m["workloads"])
    return Cell(
        name=workload, chips=int(w["chips"]),
        config=_load_json(os.path.join(bench, "configs",
                                       w["config"] + ".json")),
        traffic=_load_json(os.path.join(bench, "traffic",
                                        w["traffic"] + ".json")),
        limits=_load_json(os.path.join(bench, "limits", workload + ".json")),
        end_to_end=e2e, per_layer=layer)


def load_reader(name: str, bench: str = BENCH):
    """The ``read(run)`` function of ``bench/metrics/<name>.py``."""
    path = os.path.join(bench, "metrics", name + ".py")
    mod_spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read
