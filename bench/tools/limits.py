#!/usr/bin/env python3
"""Readings that set a cell's ``logit_gap`` limit, program and control.

    python3 bench/tools/limits.py --workload ds7b-l16x2.chat \
        --seeds 1,2,3,4,5,6,7,8,9,10,11,12 --control 3 --seconds 10

Builds and warms the cell once.  For each seed it makes that seed's
weights (same shapes and placement, so nothing recompiles), serves the
cell's traffic at the cell's rate for ``--seconds``, and reads the widest
logit gap of the run's sample against the float32 reference -- the number
``bench/run.py`` compares.  On the first ``--control`` seeds it also reads
the control: the reference computed in the nearest precision below the
configuration's, the gap of the token it puts first, on the same sample.
One JSON line per seed, then the largest program reading and the smallest
control reading.
"""

from __future__ import annotations

import argparse
import json
import sys

from common import serve_once, setup


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    cell, sys_ = setup(args.workload, seeds[0])
    from bench.lib import check, system

    prog, ctrl = [], []
    for k, seed in enumerate(seeds):
        if k:
            system.set_weights(sys_, seed)
        run = serve_once(cell, sys_, seed=seed, seconds=args.seconds)
        sample = check.sample(run.window_reqs, seed,
                              cell.params["sample_tokens"])
        row = {"seed": seed, "requests": len(sample),
               "tokens": sum(r.new_tokens for r in sample),
               "logit_gap": check.logit_gap(sys_.ref, sys_.params, sys_.hp,
                                            sample),
               "heft_mismatch": check.heft_mismatches(run.rec.decisions,
                                                      run.avail0),
               "misrouted": check.misrouted(run.rec.decisions, run.reqs),
               "malformed": check.malformed(run.window_reqs, sys_.hp["v"])}
        prog.append(row["logit_gap"])
        if k < args.control:
            row["control_gap"] = check.logit_gap(
                sys_.ref, sys_.params, sys_.hp, sample, control=True)
            ctrl.append(row["control_gap"])
        print(json.dumps(row), flush=True)
    print(json.dumps({"program_max": max(prog),
                      "control_min": min(ctrl) if ctrl else None}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
