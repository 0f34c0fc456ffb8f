"""Front end and scheduler (``HeftFrontEnd.run_continuous``, the fused
``MappingFabric``): median wait from a request's due time to the start of
the ``admit`` call that took it, over the window's requests (harness
timestamps).  It holds the loop's polling delay, the fused decision's
one-tick pipeline delay and the queue behind busy slots."""

import numpy as np


def read(run):
    waits = [r.admit_t0 - r.due for r in run.window_reqs]
    return 1e3 * float(np.median(waits)) if waits else None
