#!/usr/bin/env python3
"""Benchmark: open-loop serving through the paged HEFT_RT path, one cell.

    python3 bench/run.py --workload ds7b-l16x2.chat --seed 7 --seconds 40 \
        --trace 0

Runs from the root of a checkout on a machine with the chips the cell asks
for (``BENCHMARK.json``), and prints one JSON object as its last line of
output (see ``bench/lib/cell.py``).  With no TPU, too few chips, or without
the program beside it (``src/``) it exits non-zero and prints no result.
"""

from __future__ import annotations

import argparse
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, str(BENCH.parent))
    from bench.lib import cell, spec

    start = cell.process_start_perf()
    src = BENCH.parent / "src"
    if not (src / "repro").is_dir():
        print(f"bench: the program is not beside the benchmark ({src})",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(src))

    return cell.run(spec.load_cell(args.workload), seed=args.seed,
                    seconds=args.seconds, trace=bool(args.trace),
                    process_start=start)


if __name__ == "__main__":
    sys.exit(main())
