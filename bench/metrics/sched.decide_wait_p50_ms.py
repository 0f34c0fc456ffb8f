"""Front end and scheduler (``HeftFrontEnd.run_continuous``): median time
from when the loop first saw a request due to when its mapping event's
plan put it on a replica's queue -- the ``decided_s`` offset of the
program's ``request`` spans that started in the window (traced run).  It
holds the loop's round and, on the fused path, the one-tick pipeline delay
of the in-tick decision."""

import numpy as np


def read(run):
    t = [a["decided_s"] for name, _, _, a in run.spans if name == "request"]
    return 1e3 * float(np.median(t)) if t else None
