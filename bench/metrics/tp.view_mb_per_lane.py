"""Sharded page pool (``serve/paging.py`` ``lane_view_bytes``): per chip,
the bytes of the dense K/V view a meshed tick gathers from the pool for one
lane -- ``view_bytes`` over ``lanes`` (the lane bucket) of the live
``engine.decode_tick`` spans inside the window, their median, 0 where the
tick reads the pool in place -- in MB of 10**6 bytes (traced run).  Per
lane, so the reading does not follow the bucket the traffic happens to
fill.  Spans without ``view_bytes`` are not read."""

import numpy as np


def read(run):
    v = [args["view_bytes"] / args["lanes"]
         for name, _, _, args in run.spans
         if name == "engine.decode_tick" and args.get("active", 0) >= 1
         and "view_bytes" in args]
    return float(np.median(v)) / 1e6 if v else None
