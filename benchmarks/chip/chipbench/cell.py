"""Resolve a cell of ``BENCHMARK.json`` and the files named after its parts."""

from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parents[1]      # benchmarks/chip
REPO = BENCH_DIR.parents[1]                          # checkout root
BENCHMARK_JSON = REPO / "BENCHMARK.json"


@dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: tuple       # metric entries of BENCHMARK.json this cell reports
    per_layer: tuple
    limits: dict            # number compared -> limit


def load_benchmark(path: Path = BENCHMARK_JSON) -> dict:
    return json.loads(Path(path).read_text())


def _load_json(path: Path) -> dict:
    if not path.is_file():
        raise FileNotFoundError(f"benchmark file missing: {path}")
    return json.loads(path.read_text())


def traffic_path(name: str) -> Path:
    return BENCH_DIR / "traffic" / f"{name}.json"


def metric_path(name: str) -> Path:
    return BENCH_DIR / "metrics" / f"{name}.py"


def limits_path(workload: str) -> Path:
    return BENCH_DIR / "limits" / f"{workload}.json"


def reference_path(name: str) -> Path:
    return BENCH_DIR / "reference" / f"{name}.py"


def family_path(name: str) -> Path:
    return BENCH_DIR / "families" / f"{name}.py"


def _reports(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def resolve(workload: str, bench: dict | None = None) -> Cell:
    """The cell named ``workload``, with its config, traffic and limits."""
    bench = bench if bench is not None else load_benchmark()
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"unknown workload {workload!r}; known: {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = _load_json(REPO / configs[w["config"]]["file"])
    traffic = _load_json(traffic_path(w["traffic"]))
    limits = _load_json(limits_path(workload))
    return Cell(
        name=workload, chips=int(w["chips"]), config=config, traffic=traffic,
        end_to_end=tuple(m for m in bench["end_to_end"]
                         if _reports(m, workload)),
        per_layer=tuple(m for m in bench["per_layer"]
                        if _reports(m, workload)),
        limits={k: float(v) for k, v in limits["limits"].items()})


def _load_module(path: Path, name: str):
    if not path.is_file():
        raise FileNotFoundError(f"benchmark file missing: {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(metric: str):
    """The ``read(readings)`` function of per-layer metric ``metric``."""
    return _load_module(metric_path(metric),
                        "chipbench_metric_" + metric.replace(".", "_")).read


_FAMILIES: dict = {}


def load_family(name: str):
    """The module of model family ``name``: ``model_config(config)`` and
    the operation and byte counts of its architecture."""
    if name not in _FAMILIES:
        _FAMILIES[name] = _load_module(family_path(name),
                                       f"chipbench_family_{name}")
    return _FAMILIES[name]


def load_reference(name: str):
    """The plain reference module named by a configuration's ``reference``."""
    return _load_module(reference_path(name), f"chipbench_reference_{name}")


def load_driver(kind: str):
    """The module ``chipbench/<kind>.py`` that runs a traffic mix of
    ``kind``: ``run(cell, seed, seconds, trace_dir, t_start, devs, peaks)``."""
    import importlib
    return importlib.import_module(f"chipbench.{kind}")
