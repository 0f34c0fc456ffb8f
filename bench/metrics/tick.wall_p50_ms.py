"""Decode tick (``paged_programs`` ``tick``/``tick_sched_counted``): median
wall time of the program's ``engine.decode_tick`` spans that ran at least
one lane, inside the window (traced run)."""

import numpy as np


def read(run):
    t = [dur for name, _, dur, args in run.spans
         if name == "engine.decode_tick" and args.get("active", 0) >= 1]
    return 1e3 * float(np.median(t)) if t else None
