"""Front end and scheduler (``HeftFrontEnd.run_continuous``): share of the
window in which the serving loop had work -- 100 less the share covered by
the program's ``loop.idle`` spans, iterations with nothing in flight,
queued or pending (traced run).  ``None`` where the program writes no
``request`` spans, and so no ``loop.idle`` spans either."""


def read(run):
    if not any(name == "request" for name, _, _, _ in run.spans):
        return None
    idle = sum(min(t0 + dur, run.w1) - t0 for name, t0, dur, _ in run.spans
               if name == "loop.idle")
    return 100.0 * (1.0 - idle / (run.w1 - run.w0))
