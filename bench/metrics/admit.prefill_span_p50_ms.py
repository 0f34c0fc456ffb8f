"""Admission (``PagedRuntime.admit``): median duration of the program's
``engine.admit`` spans that took their request (``admitted`` 1): page
reservation, the prefill and the first token's read-back (traced run).
The program-side twin of ``admit.prefill_p50_ms``."""

import numpy as np


def read(run):
    t = [dur for name, _, dur, args in run.spans
         if name == "engine.admit" and args.get("admitted") == 1]
    return 1e3 * float(np.median(t)) if t else None
