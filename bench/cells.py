"""Resolve a cell of ``BENCHMARK.json`` to the files that define it."""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
from typing import Any, Callable, Dict, List

from bench import arch

ROOT = pathlib.Path(__file__).resolve().parents[1]
BENCH_DIR = ROOT / "bench"


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]        # bench/configs/<...>.json, as run
    traffic: Dict[str, Any]       # bench/traffic/<traffic>.json
    limits: Dict[str, Any]        # bench/limits/<cell>.json
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]


def load_benchmark(root: pathlib.Path = ROOT) -> Dict[str, Any]:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def _load_json(path: pathlib.Path) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def traffic_path(name: str, root: pathlib.Path = ROOT) -> pathlib.Path:
    return root / "bench" / "traffic" / f"{name}.json"


def limits_path(cell: str, root: pathlib.Path = ROOT) -> pathlib.Path:
    return root / "bench" / "limits" / f"{cell}.json"


def metric_path(name: str, root: pathlib.Path = ROOT) -> pathlib.Path:
    return root / "bench" / "metrics" / f"{name}.py"


def _applies(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def resolve(workload: str, root: pathlib.Path = ROOT) -> Cell:
    """The cell named ``workload``, with its configuration, traffic mix,
    limits and the metrics it reports.  Raises ``KeyError`` for a name
    that ``BENCHMARK.json`` does not hold, and where the configuration
    names no architecture description (``bench/arch.py``) or has a key
    that its description does not read."""
    bm = load_benchmark(root)
    cells = {w["name"]: w for w in bm["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; "
                       f"have {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in bm["configs"]}
    config = _load_json(root / configs[w["config"]]["file"])
    # the file's architecture description reads it here, before anything
    # compiles: a file without one, or with a key it does not read, fails
    arch.of(config, root).dims_of(config)
    return Cell(
        name=workload, chips=int(w["chips"]), config=config,
        traffic=_load_json(traffic_path(w["traffic"], root)),
        limits=_load_json(limits_path(workload, root)),
        end_to_end=[m for m in bm["end_to_end"] if _applies(m, workload)],
        per_layer=[m for m in bm["per_layer"] if _applies(m, workload)])


def peaks(kind: str, root: pathlib.Path = ROOT) -> Dict[str, float]:
    """The published peaks of one chip of ``kind`` (``device_kind``), from
    ``bench/peaks.json``.  A kind that is not in the table is an error."""
    table = _load_json(root / "bench" / "peaks.json")["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in "
                       f"bench/peaks.json (have {sorted(table)})")
    return table[kind]


def load_metric(name: str, root: pathlib.Path = ROOT
                ) -> Callable[..., Any]:
    """The ``compute(trace, ctx)`` function of ``bench/metrics/<name>.py``.
    Loaded by path, so a metric's name may hold dots."""
    path = metric_path(name, root)
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.compute
