"""Admission (``PagedRuntime.admit``): median time of an admit call that
took its request -- page reservation, the prefill and the first token's
read-back -- over the window's requests (harness timestamps around
``ServeEngine.admit``).  The program's ``engine.admit`` spans are not used
because they also time the refused calls of a full pool, which take
microseconds and would swamp the median."""

import numpy as np


def read(run):
    t = [r.token_t[0] - r.admit_t0 for r in run.window_reqs]
    return 1e3 * float(np.median(t)) if t else None
