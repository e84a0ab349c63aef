"""Find the benchmark's pieces by name.

Every cell, configuration, traffic mix and per-layer metric is a file of
its own, found from the name that ``BENCHMARK.json`` gives it:

    bench/workloads/<cell>.json     the cell: strategy, precision, limits
    bench/configs/<config>.json     the model's sizes as they are run
    bench/traffic/<traffic>.json    parameters of the traffic generator
    bench/metrics/<metric>.py       ``read(facts) -> float | None``

Adding one of them edits no file that is already here.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from typing import Callable, Optional

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent


def _load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: Path = ROOT) -> dict:
    return _load_json(root / "BENCHMARK.json")


def config(name: str, bench_dir: Path = BENCH_DIR) -> dict:
    return _load_json(bench_dir / "configs" / f"{name}.json")


def traffic(name: str, bench_dir: Path = BENCH_DIR) -> dict:
    return _load_json(bench_dir / "traffic" / f"{name}.json")


def metric_reader(name: str, bench_dir: Path = BENCH_DIR) -> Callable:
    """The ``read`` function of ``bench/metrics/<name>.py``."""
    path = bench_dir / "metrics" / f"{name}.py"
    mod_spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


@dataclasses.dataclass(frozen=True)
class Cell:
    """One entry of ``workloads`` with the files it names, loaded."""
    name: str
    chips: int
    workload: dict          # bench/workloads/<name>.json
    config: dict            # bench/configs/<config>.json
    traffic: dict           # bench/traffic/<traffic>.json
    end_to_end: tuple       # metric entries of BENCHMARK.json for this cell
    per_layer: tuple


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def cell(name: str, bench: Optional[dict] = None,
         bench_dir: Path = BENCH_DIR) -> Cell:
    bench = benchmark(bench_dir.parent) if bench is None else bench
    entries = {w["name"]: w for w in bench["workloads"]}
    if name not in entries:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"have {sorted(entries)}")
    entry = entries[name]
    workload = _load_json(bench_dir / "workloads" / f"{name}.json")
    if (workload["config"], workload["traffic"]) != (entry["config"],
                                                     entry["traffic"]):
        raise ValueError(f"{name}: BENCHMARK.json names config/traffic "
                         f"{entry['config']}/{entry['traffic']}, the cell "
                         f"file {workload['config']}/{workload['traffic']}")
    return Cell(
        name=name, chips=int(entry["chips"]), workload=workload,
        config=config(entry["config"], bench_dir),
        traffic=traffic(entry["traffic"], bench_dir),
        end_to_end=tuple(m for m in bench["end_to_end"] if _applies(m, name)),
        per_layer=tuple(m for m in bench["per_layer"] if _applies(m, name)))
