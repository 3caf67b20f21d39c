"""Finds a cell's files by the names in ``BENCHMARK.json``.

A configuration is ``bench/configs/<config>.json``, a traffic mix
``bench/traffic/<traffic>.json``, a cell's limits ``bench/limits/<cell>.json``
and a per-layer metric's reader ``bench/metrics/<metric>.py``.  Adding a cell
or a metric adds files and entries; no code changes.
"""

from __future__ import annotations

import dataclasses
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: tuple      # metric entries this cell reports with --trace 0
    per_layer: tuple       # metric entries this cell reports with --trace 1


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load(workload: str, benchmark: Path | None = None,
         bench: Path = BENCH) -> Cell:
    spec = json.loads(Path(benchmark or ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"unknown workload {workload!r}; known: "
                       f"{sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    config = json.loads(Path(bench.parent / configs[w["config"]]["file"])
                        .read_text())
    traffic = json.loads((bench / "traffic" / f"{w['traffic']}.json")
                         .read_text())
    limits = json.loads((bench / "limits" / f"{workload}.json")
                        .read_text())["limits"]
    e2e = tuple(m for m in spec["end_to_end"] if _reports(m, workload))
    e2e_names = {m["name"] for m in e2e}
    per_layer = tuple(m for m in spec["per_layer"]
                      if m["moves"] in e2e_names and _reports(m, workload))
    return Cell(workload, int(w["chips"]), config, traffic, limits, e2e,
                per_layer)
