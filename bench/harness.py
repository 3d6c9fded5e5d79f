"""Cells, drivers and per-layer readers, found by name from BENCHMARK.json.

A cell (an entry of ``workloads``) names a configuration and a traffic mix:

* ``configs[].file``: the configuration's sizes (``bench/configs/*.json``);
* ``bench/traffic/<traffic>.json``: the mix's parameters, with the
  ``driver`` (``bench/drivers/<driver>.py``) that runs it;
* ``bench/limits/<workload>.json``: the cell's limits on the numbers that
  decide ``correct``;
* ``bench/metrics/<metric>.py``: one reader per per-layer metric, with a
  ``read(run)`` that returns the number or None.

So a new configuration, mix, cell or metric is new files and a new entry,
and no edit of a file already here.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
from typing import Any, Dict, List, Optional

BENCH = pathlib.Path(__file__).resolve().parent


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    spec: Dict[str, Any]      # the whole BENCHMARK.json
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    limits: Dict[str, float]
    bench: pathlib.Path

    def metrics(self, kind: str) -> List[Dict[str, Any]]:
        """The ``end_to_end`` or ``per_layer`` entries this cell reports."""
        return [
            m for m in self.spec[kind]
            if "workloads" not in m or self.name in m["workloads"]
        ]


@dataclasses.dataclass
class Outcome:
    """What a driver hands back to the harness."""

    correct: bool
    attempted: int
    failed: int
    end_to_end: Dict[str, float]
    compared: Dict[str, Dict[str, Optional[float]]]
    memory_peak_bytes: int
    counts: Dict[str, Any] = dataclasses.field(default_factory=dict)
    trace: Any = None  # bench.trace.Summary of the traced window


@dataclasses.dataclass
class Run:
    """What a per-layer reader reads."""

    cell: Cell
    trace: Any
    counts: Dict[str, Any]
    peaks: Dict[str, float]


def load_cell(root: pathlib.Path, name: str, bench: Optional[pathlib.Path] = None) -> Cell:
    """The cell ``name`` of ``root``/BENCHMARK.json; ``bench`` is the
    directory that holds its files (``root``/bench by default)."""
    root = pathlib.Path(root)
    bench = pathlib.Path(bench) if bench else root / "bench"
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r}; have {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    config = json.loads((root / configs[w["config"]]["file"]).read_text())
    traffic = json.loads((bench / "traffic" / f"{w['traffic']}.json").read_text())
    limits = json.loads((bench / "limits" / f"{name}.json").read_text())
    return Cell(name, int(w["chips"]), spec, config, traffic, limits["limits"], bench)


def load_module(path: pathlib.Path):
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def driver(cell: Cell):
    return load_module(cell.bench / "drivers" / f"{cell.traffic['driver']}.py")


def read_per_layer(cell: Cell, run: Run) -> Dict[str, Dict[str, Any]]:
    """Each per-layer metric's reader on ``run``; a reader that finds
    nothing to read returns None and the metric is left out."""
    out = {}
    for m in cell.metrics("per_layer"):
        value = load_module(cell.bench / "metrics" / f"{m['name']}.py").read(run)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out
