"""Whole decode step: operations the live lanes' tokens need
(``bench/lib/roofline.py``), summed over the window's ticks, over their
summed wall time (harness timestamps around ``decode_tick``) times the
chips' bf16 peak."""


def read(run):
    peak = run.peak
    if peak is None or not run.ticks:
        return None
    flops = sum(run.roofline.tick_work(run.hp, t.positions)[0]
                for t in run.ticks)
    wall = sum(t.t1 - t.t0 for t in run.ticks)
    return 100.0 * flops / (wall * run.chips * peak["bf16_flops_per_s"])
