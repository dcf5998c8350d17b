"""Finds one cell's files by the names in ``BENCHMARK.json``:

  configs/<config>.json   the model configuration as it is run
  traffic/<traffic>.json  the federated traffic: method, clients, batches
  limits/<workload>.json  the limit of each number ``correct`` compares
  metrics/<metric>.py     the reader of each per-layer metric

A later cell, configuration, traffic mix or metric is a new file and a new
entry, and nothing here changes.
"""

from __future__ import annotations

import dataclasses
import json
import pathlib

HERE = pathlib.Path(__file__).resolve().parents[1]      # benchmarks/chip
ROOT = HERE.parents[1]                                  # the checkout


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    model: dict          # the configuration file
    traffic: dict        # the traffic file
    limits: dict         # {number: limit}
    end_to_end: list     # BENCHMARK.json metric entries that apply
    per_layer: list


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def _json(path: pathlib.Path) -> dict:
    if not path.is_file():
        raise FileNotFoundError(f"missing {path}")
    return json.loads(path.read_text())


def load(workload: str, bench_file: pathlib.Path | None = None) -> Cell:
    bench = _json(bench_file or ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"unknown workload {workload!r}; known: {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    model = _json(ROOT / configs[w["config"]]["file"])
    return Cell(
        name=workload, chips=int(w["chips"]), model=model,
        traffic=_json(HERE / "traffic" / f"{w['traffic']}.json"),
        limits=_json(HERE / "limits" / f"{workload}.json"),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, workload)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, workload)],
    )
