"""Page pool and slots (``PagePool``): mean share of a replica's
``max_batch`` slots that a decode tick ran, over the window's ticks
(harness tick records; a tick is a ``decode_tick`` call with at least one
live lane)."""

import numpy as np


def read(run):
    lanes = [len(t.positions) for t in run.ticks]
    if not lanes:
        return None
    return 100.0 * float(np.mean(lanes)) / run.serving["max_batch"]
