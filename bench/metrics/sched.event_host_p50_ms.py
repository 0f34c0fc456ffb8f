"""Front end and scheduler: median host time of one mapping event -- the
sum of the program's ``sched.stage``, ``sched.decide`` and ``sched.adopt``
spans that share an ``ev`` (traced run).  The fused path's decision runs in
the decode tick and has no ``sched.decide``."""

import numpy as np


def read(run):
    per_event = {}
    for name, _, dur, args in run.spans:
        if name in ("sched.stage", "sched.decide", "sched.adopt"):
            per_event[args["ev"]] = per_event.get(args["ev"], 0.0) + dur
    if not per_event:
        return None
    return 1e3 * float(np.median(list(per_event.values())))
