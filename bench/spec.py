"""Find a cell's files by the names in ``BENCHMARK.json``.

Everything that belongs to one configuration, one traffic mix, one metric
or one cell sits in a file of its own, found by name:

* ``bench/configs/<config>.json``   sizes, block kinds, engine sizes
* ``bench/traffic/<traffic>.json``  lengths, arrivals, lead-in, drain cap
* ``bench/metrics/<metric>.py``     a reader with ``read(run) -> float | None``
* ``bench/limits/<workload>.json``  the correctness limit and its readings

A new cell, configuration, mix or metric is a new file and a new entry in
``BENCHMARK.json``; no existing file changes.
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass, field
from pathlib import Path
from typing import Callable, Dict, List, Optional

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


@dataclass(frozen=True)
class Metric:
    name: str
    unit: str
    better: str
    source: str
    reader: Callable


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: List[Metric] = field(default_factory=list)
    per_layer: List[Metric] = field(default_factory=list)


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((Path(root) / "BENCHMARK.json").read_text())


def _json(path: Path) -> dict:
    if not path.is_file():
        raise FileNotFoundError(f"{path} is missing")
    return json.loads(path.read_text())


def load_reader(name: str, bench_dir: Path = BENCH_DIR) -> Callable:
    """The ``read`` function of ``bench/metrics/<name>.py``. Metric names
    may hold dots, so the file is loaded by path, not imported by name."""
    path = Path(bench_dir) / "metrics" / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"{path} is missing")
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    if not callable(getattr(mod, "read", None)):
        raise AttributeError(f"{path} has no read(run) function")
    return mod.read


def _applies(entry: dict, workload: str, reported: set) -> bool:
    """A metric with ``workloads`` is read in those cells; one without it
    in every cell that reports the end-to-end metric it moves (or, for an
    end-to-end metric, in every cell)."""
    if "workloads" in entry:
        return workload in entry["workloads"]
    if "moves" in entry:
        return entry["moves"] in reported
    return True


def resolve(workload: str, root: Path = ROOT,
            bench_dir: Optional[Path] = None) -> Cell:
    bench = load_benchmark(root)
    bench_dir = Path(bench_dir) if bench_dir else Path(root) / "bench"
    cells: Dict[str, dict] = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    if w["config"] not in configs:
        raise KeyError(f"workload {workload!r} names configuration "
                       f"{w['config']!r}, which BENCHMARK.json lacks")
    cfg_file = Path(root) / configs[w["config"]]["file"]
    config = _json(cfg_file)
    traffic = _json(bench_dir / "traffic" / f"{w['traffic']}.json")
    limits = _json(bench_dir / "limits" / f"{workload}.json")
    e2e = [m for m in bench["end_to_end"] if _applies(m, workload, set())]
    reported = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if _applies(m, workload, reported)]

    def metric(m):
        return Metric(m["name"], m["unit"], m["better"], m["source"],
                      load_reader(m["name"], bench_dir))

    return Cell(name=workload, chips=int(w["chips"]), config=config,
                traffic=traffic, limits=limits,
                end_to_end=[metric(m) for m in e2e],
                per_layer=[metric(m) for m in layer])
