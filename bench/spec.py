"""Finds a cell's parts by name, so that a new cell is only new files.

``BENCHMARK.json`` names each cell's configuration and traffic mix. The
configuration's file is the one its entry in ``configs`` names; the traffic
mix is ``bench/traffic/<traffic>.json``, whose ``loop`` names the client that
drives it, ``bench/loops/<loop>.py`` (``run``, ``check``, ``LIMITS`` and the
``END_TO_END`` readers it can give); a per-layer metric ``<name>`` is read by
``bench/metrics/<name>.py``, a module with ``read(ctx) -> float | None``.
Every path is relative to the checkout's root.
"""
from __future__ import annotations

import importlib.util
import json
import os
import re
from dataclasses import dataclass
from types import ModuleType
from typing import Callable, Dict, List, Optional, Sequence

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


class SpecError(ValueError):
    pass


def four_chip_ok(chips: Sequence[int]) -> bool:
    """The cells' chip counts keep the four-chip rule: each cell takes 1 or 4
    chips, and at most half of the cells, rounded down, take 4; one such
    cell is always allowed."""
    n_four = sum(c == 4 for c in chips)
    return all(c in (1, 4) for c in chips) and n_four <= max(1, len(chips) // 2)


@dataclass
class Metric:
    name: str
    unit: str


@dataclass
class Cell:
    name: str
    chips: int
    config: Dict
    traffic: Dict
    end_to_end: List[Metric]
    per_layer: List[Metric]


def load_benchmark(root: str = ROOT) -> Dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _load_json(path: str) -> Dict:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise SpecError(f"missing file {path}") from None


def _applies(entry: Dict, cell: str) -> bool:
    return "workloads" not in entry or cell in entry["workloads"]


def find_cell(name: str, bench: Optional[Dict] = None, root: str = ROOT) -> Cell:
    bench = bench if bench is not None else load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SpecError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    if w["config"] not in configs:
        raise SpecError(f"workload {name!r} names unknown config {w['config']!r}")
    config = _load_json(os.path.join(root, configs[w["config"]]["file"]))
    traffic = _load_json(os.path.join(root, "bench", "traffic", w["traffic"] + ".json"))

    def metrics(kind: str) -> List[Metric]:
        return [Metric(m["name"], m["unit"]) for m in bench[kind] if _applies(m, name)]

    return Cell(name, int(w["chips"]), config, traffic,
                metrics("end_to_end"), metrics("per_layer"))


def _load_module(kind: str, name: str, root: str) -> ModuleType:
    """``bench/<kind>/<name>.py`` of the checkout (the name may hold dots)."""
    path = os.path.join(root, "bench", kind, name + ".py")
    if not os.path.exists(path):
        raise SpecError(f"no file {path} for {kind[:-1]} {name!r}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_" + re.sub(r"[^A-Za-z0-9_]", "_", name), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(metric: str, root: str = ROOT) -> Callable[[Dict], Optional[float]]:
    """``read`` of ``bench/metrics/<metric>.py``."""
    return _load_module("metrics", metric, root).read


def load_loop(loop: str, root: str = ROOT) -> ModuleType:
    """The client loop ``bench/loops/<loop>.py`` that a traffic mix names."""
    return _load_module("loops", loop, root)
