"""Where the benchmark's pieces live, and how a cell is put together.

``BENCHMARK.json`` names each cell (a workload): a configuration, a traffic
mix and the chips it needs.  Everything that belongs to one of those sits
in a file of its own, found by name:

* ``bench/configs/<config>.json``  -- the model's sizes as published (with
  the cuts listed in ``reduced``), the plain reference that computes it
  (``bench/reference/<reference>.py``) and the serving layout;
* ``bench/traffic/<traffic>.json`` -- the parameters the one traffic
  generator (``lib/traffic.py``) reads;
* ``bench/cells/<workload>.json``  -- what belongs to the pair: the offered
  rate, the lead-in, the traced sub-window and the correctness limits;
* ``bench/metrics/<metric>.py``    -- one reader per per-layer metric.

A later cell is added by adding such files and entries; no code changes.
"""

from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass, field
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


def _load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclass
class Cell:
    """One workload of ``BENCHMARK.json`` with its files loaded."""

    name: str
    chips: int
    config: dict                  # bench/configs/<config>.json
    traffic: dict                 # bench/traffic/<traffic>.json
    params: dict                  # bench/cells/<name>.json
    end_to_end: list = field(default_factory=list)
    per_layer: list = field(default_factory=list)


def load_cell(name: str, benchmark: Path | None = None) -> Cell:
    """The cell ``name`` as ``BENCHMARK.json`` defines it."""
    spec = _load_json(benchmark or ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    cfg_entry = configs[w["config"]]

    def applies(metric):
        return "workloads" not in metric or name in metric["workloads"]

    return Cell(
        name=name,
        chips=int(w["chips"]),
        config=_load_json(ROOT / cfg_entry["file"]),
        traffic=_load_json(BENCH / "traffic" / f"{w['traffic']}.json"),
        params=_load_json(BENCH / "cells" / f"{name}.json"),
        end_to_end=[m for m in spec["end_to_end"] if applies(m)],
        per_layer=[m for m in spec["per_layer"] if applies(m)],
    )


def load_module(path: Path):
    """Import a Python file by path (metric readers and references are
    named after metrics and configurations, which may hold dots)."""
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reference_module(config: dict):
    """The plain reference named by a configuration file."""
    return load_module(BENCH / "reference" / f"{config['reference']}.py")


def metric_reader(name: str):
    """``read(run)`` of the per-layer metric ``name``."""
    return load_module(BENCH / "metrics" / f"{name}.py").read


def peaks(device_kind: str) -> dict:
    """Published peaks of one chip of ``device_kind``; a device missing
    from the table is an error, never a default."""
    table = _load_json(BENCH / "peaks.json")
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json (have {sorted(table)})")
    return table[device_kind]
