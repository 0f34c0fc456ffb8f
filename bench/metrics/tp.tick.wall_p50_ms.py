"""Sharded decode tick (``PagedRuntime.decode_tick`` on a meshed engine):
median wall time of the program's ``engine.decode_tick`` spans that ran at
least one lane on more than one chip (``chips`` > 1), inside the window
(traced run).  Spans without ``chips`` (an unmeshed engine, or a program
that does not record it) are not read."""

import numpy as np


def read(run):
    t = [dur for name, _, dur, args in run.spans
         if name == "engine.decode_tick" and args.get("active", 0) >= 1
         and args.get("chips", 0) > 1]
    return 1e3 * float(np.median(t)) if t else None
