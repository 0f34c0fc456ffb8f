"""Plain HEFT_RT, the scheduler the serving front end runs (arXiv:2207.11360,
Section III-B): at each mapping event sort the ready queue by descending
average execution time (stable), then give each request in turn to the
replica with the earliest finish time ``T_avail + Exec`` (ties to the
lowest index) and advance that replica's ``T_avail``.

Computed in float32, the precision of the program's device registers, so a
correct decision agrees with this one bit for bit.
"""

from __future__ import annotations

import numpy as np


def heft_rt(avg, exec_times, avail):
    """One mapping event -> (order, assignment, start, finish, new_avail)."""
    keys = np.asarray(avg, dtype=np.float32)
    keys = np.where(np.isnan(keys), np.float32(-np.inf), keys)
    ex = np.asarray(exec_times, dtype=np.float32)
    avail = np.array(avail, dtype=np.float32)
    order = np.argsort(-keys, kind="stable")
    n = len(order)
    assignment = np.full(n, -1, np.int64)
    start = np.full(n, np.inf, np.float32)
    finish = np.full(n, np.inf, np.float32)
    for i, t in enumerate(order):
        fin = avail + ex[t]
        pe = int(np.argmin(fin))
        if np.isfinite(fin[pe]):
            assignment[i], start[i], finish[i] = pe, avail[pe], fin[pe]
            avail[pe] = fin[pe]
    return order, assignment, start, finish, avail


def same_decision(got, want) -> bool:
    """Integer lanes equal, float lanes equal bit for bit."""
    for g, w in zip(got, want):
        g, w = np.asarray(g), np.asarray(w)
        if g.shape != w.shape:
            return False
        if w.dtype.kind == "f":
            if not np.array_equal(g.astype(np.float32).view(np.int32),
                                  w.astype(np.float32).view(np.int32)):
                return False
        elif not np.array_equal(g.astype(np.int64), w.astype(np.int64)):
            return False
    return True
