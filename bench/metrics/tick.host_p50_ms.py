"""Decode tick (``PagedRuntime.decode_tick``): median host time of a tick
that ran at least one lane -- its ``engine.decode_tick`` span less the
``tick.wait`` span inside it, the blocking read of the tick's result
(traced run).  What is left is staging, dispatch and commit: the host work
each tick adds to the gap between tokens."""

import bisect

import numpy as np


def read(run):
    waits = sorted((t0, dur) for name, t0, dur, _ in run.spans
                   if name == "tick.wait")
    starts = [t0 for t0, _ in waits]
    host = []
    for name, t0, dur, args in run.spans:
        if name != "engine.decode_tick" or args.get("active", 0) < 1:
            continue
        j = bisect.bisect_left(starts, t0)
        if j < len(waits) and waits[j][0] <= t0 + dur:
            host.append(dur - waits[j][1])
    return 1e3 * float(np.median(host)) if host else None
