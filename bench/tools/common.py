"""Set-up shared by the chip tools: the cell built and warmed once, to be
served many times in one process."""

from __future__ import annotations

import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def setup(workload: str, seed: int):
    """(cell, system) for ``workload``, built from ``seed`` and warmed, on
    the TPU."""
    sys.path.insert(0, str(ROOT))
    sys.path.insert(0, str(ROOT / "src"))
    from bench.lib import cell as cellmod
    from bench.lib import spec, system, traffic

    cell = spec.load_cell(workload)
    cellmod.devices_for(cell.chips, require_tpu=True)
    cellmod.enable_compile_cache()
    sys_ = system.build(cell.config, spec.reference_module(cell.config),
                        seed, cell.chips)
    system.warm(sys_, traffic.grid_lengths(cell.traffic))
    return cell, sys_


def serve_once(cell, sys_, *, seed: int, seconds: float,
               rate: float | None = None):
    from bench.lib import system, traffic

    p = cell.params
    sched = traffic.schedule(cell.traffic,
                             rate_per_s=rate or p["rate_per_s"],
                             lead_in_s=p["lead_in_s"], seconds=seconds,
                             seed=seed, vocab_size=sys_.hp["v"])
    return system.serve(sys_, sched, lead_in_s=p["lead_in_s"],
                        seconds=seconds)
