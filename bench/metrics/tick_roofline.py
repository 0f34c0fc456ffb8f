"""Model and kernels: the decode tick's share of its roofline.  The least
time of each profiled tick -- the larger of the operations over the chips'
peak and the bytes over their HBM bandwidth, counted by
``bench/lib/roofline.py`` from the live lanes' positions -- summed, over
the device time of the tick executables in the profiler trace."""


def read(run):
    tr, peak = run.trace, run.peak
    if tr is None or peak is None:
        return None
    ticks = run.profiled_ticks
    if not ticks or len(ticks) != len(tr.tick_device_s):
        return None
    device = sum(tr.tick_device_s)
    if device <= 0:
        return None
    least = sum(run.roofline.least_time_s(
        *run.roofline.tick_work(run.hp, t.positions), peak, run.chips)[0]
        for t in ticks)
    return 100.0 * least / device
