"""Admission: median wait of a decided request for the ``admit`` call that
took it -- ``admitted_s`` less ``decided_s`` of the program's ``request``
spans (traced run).  It holds the queue behind busy slots or a full page
pool, and the other admits of the same loop round."""

import numpy as np


def read(run):
    t = [a["admitted_s"] - a["decided_s"] for name, _, _, a in run.spans
         if name == "request"]
    return 1e3 * float(np.median(t)) if t else None
