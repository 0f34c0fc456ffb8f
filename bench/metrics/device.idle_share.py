"""Device: share of the profiled sub-window in which no operation ran on
the chips (busy time is the union of operation intervals in the trace,
averaged over the chips)."""


def read(run):
    tr = run.trace
    if tr is None or tr.window_s <= 0:
        return None
    return 100.0 * (1.0 - tr.busy_s / tr.window_s)
