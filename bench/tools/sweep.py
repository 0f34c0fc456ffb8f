#!/usr/bin/env python3
"""Find a cell's knee: serve its traffic at several offered rates.

    python3 bench/tools/sweep.py --workload ds7b-l16x2.chat \
        --rates 1.5,2,2.5,3 --seconds 20 --seed 5

Builds and warms the cell once, then serves each rate's schedule in turn
and prints one JSON line per rate: the end-to-end metrics, every request's
tokens delivered in the window per second (``delivered_tok_s``: lead-in
requests' too, so above the knee it reads the tick's ceiling, where
``out_tok_s`` counts only the window's own requests), and the backlog
(requests due but not yet admitted) at the window's start and end with the
mean queue wait of the window's first and second halves.  The knee is the
highest rate at which the backlog does not grow over the window.
"""

from __future__ import annotations

import argparse
import json
import sys

import numpy as np

from common import serve_once, setup


def backlog(reqs, t: float) -> int:
    return sum(1 for r in reqs if r.due <= t and not r.admit_t0 <= t)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    cell, sys_ = setup(args.workload, args.seed)
    from bench.lib import timings

    for rate in (float(r) for r in args.rates.split(",")):
        run = serve_once(cell, sys_, seed=args.seed, seconds=args.seconds,
                         rate=rate)
        win = run.window_reqs
        mid = (run.w0 + run.w1) / 2
        waits = [(r.due, r.admit_t0 - r.due) for r in win]
        first = [w for d, w in waits if d < mid]
        second = [w for d, w in waits if d >= mid]
        row = {"rate_per_s": rate,
               **timings.end_to_end(win, run.w0, run.w1, 0.0),
               "delivered_tok_s": timings.tokens_in(run.reqs, run.w0, run.w1)
               / (run.w1 - run.w0),
               "backlog_start": backlog(run.reqs, run.w0),
               "backlog_end": backlog(run.reqs, run.w1),
               "wait_ms_first_half": 1e3 * float(np.mean(first)),
               "wait_ms_second_half": 1e3 * float(np.mean(second)),
               "refused_admits": run.rec.refused}
        row.pop("setup_s")
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
