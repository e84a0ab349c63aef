"""Find the benchmark's pieces by name.

Every cell, configuration, traffic mix, model family and per-layer metric
is a file of its own, found from the name that ``BENCHMARK.json`` (or the
configuration, for its family) gives it:

    bench/workloads/<cell>.json     the cell: strategy, precision, limits
    bench/configs/<config>.json     the model's sizes as they are run, and
                                    its ``"family"``
    bench/families/<family>.py      the architecture (see :func:`family`)
    bench/traffic/<traffic>.json    parameters of the traffic generator
    bench/metrics/<metric>.py       ``read(facts) -> float | None``

Adding one of them edits no file that is already here.
"""
from __future__ import annotations

import dataclasses
import functools
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


def _load(kind: str, path: Path):
    name = path.stem.replace(".", "_").replace("-", "_")
    mod_spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str, bench_dir: Path = BENCH_DIR) -> Callable:
    """The ``read`` function of ``bench/metrics/<name>.py``."""
    return _load("metric", bench_dir / "metrics" / f"{name}.py").read


FAMILY_API = ("arch_config", "param_shapes", "units", "stacked", "loss",
              "step_flops")


@functools.lru_cache(maxsize=None)
def family(name: str, bench_dir: Path = BENCH_DIR):
    """The module ``bench/families/<name>.py``: all that differs between
    architectures.  ``c`` is a configuration file's dict; every family's
    gives ``vocab_size``, the range the traffic draws its tokens from.

    ``arch_config(c)``: the program's ``ArchConfig``; refuses a file that
    the program would run otherwise than the reference.
    ``param_shapes(c)``: ``{path: (shape, init scale)}`` in the program's
    layout (``"a/b"`` is ``params["a"]["b"]``); a scale of ``None`` is a
    norm scale (ones), ``0`` a bias (zeros).
    ``units(c)``: HiFT's units bottom to top, in the program's group order,
    each ``(key, index | None)``: block ``index`` of the stacked top-level
    ``key``, or the whole of ``key``.  A leading dense layer and an expert
    stack are two stacked keys.
    ``stacked(c)``: the top-level keys whose leaves carry a leading layer
    dimension.
    ``loss(c, nm, params, tokens, unit=None, active=None)``: the plain
    forward pass and mean next-token loss, computed as ``nm``
    (``lib.reference.Numerics``) says.  With ``unit``, ``active`` holds that
    unit's weights (a block's slice of a stacked key) and is the only input
    differentiated: every other weight is a constant.
    ``step_flops(c, batch, seq, group=None)``: the operations one training
    step requires that trains the units of ``group`` (``None``: all, FPFT).
    """
    path = bench_dir / "families" / f"{name}.py"
    have = sorted(f.name for f in path.parent.glob("*.py"))
    if f"{name}.py" not in have:
        raise ValueError(f"family {name!r} (a configuration's \"family\"): "
                         f"looked for {path}; {path.parent} has {have}")
    mod = _load("family", path)
    missing = [f for f in FAMILY_API if not callable(getattr(mod, f, None))]
    if missing:
        raise ValueError(f"family {name!r} lacks {missing}")
    return mod


@dataclasses.dataclass(frozen=True)
class Cell:
    """One entry of ``workloads`` with the files it names, loaded."""
    name: str
    chips: int
    workload: dict          # bench/workloads/<name>.json
    config: dict            # bench/configs/<config>.json
    family: object          # bench/families/<config["family"]>.py
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
    cfg = config(entry["config"], bench_dir)
    return Cell(
        name=name, chips=int(entry["chips"]), workload=workload,
        config=cfg, family=family(cfg.get("family"), bench_dir),
        traffic=traffic(entry["traffic"], bench_dir),
        end_to_end=tuple(m for m in bench["end_to_end"] if _applies(m, name)),
        per_layer=tuple(m for m in bench["per_layer"] if _applies(m, name)))
