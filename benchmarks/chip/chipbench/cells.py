"""Find a cell, its configuration, its metrics and the chip's peaks by
name.  Nothing here lists a cell, a configuration or a metric: each is a
file of its own, and ``BENCHMARK.json`` says which metrics a cell reports.
"""
from __future__ import annotations

import importlib.util
import json
import os
from typing import Callable, Dict, List, Optional

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(BENCH))


class BenchError(Exception):
    """A cell, configuration, metric or device the benchmark cannot run."""


def _load_json(path: str, what: str) -> Dict:
    try:
        with open(path) as fh:
            return json.load(fh)
    except FileNotFoundError:
        raise BenchError(f"no {what} file {os.path.relpath(path, ROOT)}")


def load_cell(name: str) -> Dict:
    cell = _load_json(os.path.join(BENCH, "workloads", f"{name}.json"),
                      f"workload {name!r}")
    cell["name"] = name
    return cell


def load_config(name: str) -> Dict:
    cfg = _load_json(os.path.join(BENCH, "configs", f"{name}.json"),
                     f"configuration {name!r}")
    cfg["name"] = name
    return cfg


def benchmark() -> Dict:
    return _load_json(os.path.join(ROOT, "BENCHMARK.json"), "BENCHMARK.json")


def metrics_of(cell: str, kind: str, bench: Optional[Dict] = None
               ) -> List[Dict]:
    """The ``end_to_end`` or ``per_layer`` entries that ``cell`` reports:
    those whose ``workloads`` name it, or that have no ``workloads``."""
    bench = bench if bench is not None else benchmark()
    return [m for m in bench[kind]
            if "workloads" not in m or cell in m["workloads"]]


def reader(metric: str) -> Callable[[Dict], Optional[float]]:
    """``metrics/<metric>.py``'s ``read(ctx)``."""
    path = os.path.join(BENCH, "metrics", f"{metric}.py")
    if not os.path.exists(path):
        raise BenchError(f"no reader metrics/{metric}.py")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_metric_{metric.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def peaks(device_kind: str) -> Dict:
    """The published peaks of one chip of ``device_kind``; a kind that is
    not in ``peaks.json`` is an error, never a default."""
    table = _load_json(os.path.join(BENCH, "peaks.json"), "peaks")
    if device_kind not in table["devices"]:
        raise BenchError(f"device kind {device_kind!r} is not in "
                         f"peaks.json ({sorted(table['devices'])})")
    return table["devices"][device_kind]
