"""The benchmark's data, found by name.

``BENCHMARK.json`` at the checkout's root indexes the cells; each cell names
a configuration (``configs/<name>.json``) and a traffic mix
(``traffic/<name>.json``), and has its correctness limits in
``limits/<cell>.json``.  Each per-layer metric is read by
``metrics/<metric>.py``.  A new cell or metric is new files and entries;
nothing here changes.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from typing import Callable, Dict, List

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def _load(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict          # the configuration's file
    traffic: dict         # the traffic mix's file
    limits: dict          # {number: limit} for ``correct``
    end_to_end: List[dict]
    per_layer: List[dict]

    @property
    def tokens_per_step(self) -> int:
        return self.traffic["global_batch"] * self.traffic["seq"]


def smoke(cell: Cell) -> Cell:
    """``cell`` with its traffic cut to the mix's smoke sizes (CPU tests)."""
    return dataclasses.replace(
        cell, traffic={**cell.traffic, **cell.traffic["smoke"]})


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str) -> Cell:
    bench = _load(ROOT / "BENCHMARK.json")
    entries = {w["name"]: w for w in bench["workloads"]}
    if name not in entries:
        raise SystemExit(f"unknown workload {name!r}; BENCHMARK.json has "
                         f"{sorted(entries)}")
    w = entries[name]
    return Cell(
        name=name, chips=int(w["chips"]),
        config=_load(BENCH / "configs" / f"{w['config']}.json"),
        traffic=_load(BENCH / "traffic" / f"{w['traffic']}.json"),
        limits=_load(BENCH / "limits" / f"{name}.json"),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)])


def metric_reader(name: str) -> Callable:
    """``read(ctx) -> float | None`` from ``metrics/<name>.py``."""
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def peaks(device_kind: str) -> Dict[str, float]:
    table = _load(BENCH / "peaks.json")["devices"]
    if device_kind not in table:
        raise SystemExit(f"no peaks for device kind {device_kind!r} in "
                         f"bench/peaks.json ({sorted(table)})")
    return table[device_kind]
