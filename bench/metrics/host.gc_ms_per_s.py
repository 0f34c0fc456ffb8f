"""Host runtime: milliseconds of Python garbage collection per second of
the window -- the program's ``host.gc`` spans, one per collection while the
serving loop runs traced (traced run).  Reads 0.0 when no collection ran;
``None`` where the program writes no ``request`` spans, and so has no
collection hook either."""


def read(run):
    if not any(name == "request" for name, _, _, _ in run.spans):
        return None
    gc = sum(min(t0 + dur, run.w1) - t0 for name, t0, dur, _ in run.spans
             if name == "host.gc")
    return 1e3 * gc / (run.w1 - run.w0)
